"""psrun — an *executable* sharded parameter server on the device mesh.

Where ``core.ps.simulate`` reproduces SSPTable/ESSPTable semantics inside a
single vectorized ``lax.scan`` (one device, global knowledge), this package
*runs* them: parameter shards live on the ``"model"`` mesh axis, the ``P``
workers are partitioned over the ``"data"`` axis, and every clock executes
as a ``shard_map`` step in which workers materialize views against their
device-resident caches, compute updates locally, push them to the owning
shard, and advance their per-channel ``cview`` clocks lazily (SSP) or
eagerly on push (ESSP) under the bounded-staleness gate.

The runtime calls the simulator's policy functions (``core.ps.enforce``,
``deliver``, ``ship`` and the rest) on each shard's reader rows and adds
the data plane: sharding, collectives, the RNG row slices.  The simulator
is the *oracle* for everything else: both produce the same
``core.ps.Trace`` schema, a seeded run is bit-identical between the two,
and SSP/ESSP/VAP runs must satisfy the staleness / value-bound invariants
checked by ``core.theory`` / ``core.valuebound``.  See ``psrun.validate``
for the cross-validation entry points and ``tests/test_psrun.py`` for the
contract.
"""
from .runtime import PSRuntime, PSState, default_mesh, make_run_fn
from .validate import cross_validate, trace_max_diff, trace_max_ulp

__all__ = ["PSRuntime", "PSState", "default_mesh", "make_run_fn",
           "cross_validate", "trace_max_diff", "trace_max_ulp"]
