"""Batched consistency-model sweep engine: one XLA program per family.

The paper's empirical claims (C1–C6) are all *sweeps*: staleness profiles,
convergence curves, robustness and straggler ablations measured across
consistency models, staleness bounds, delivery rates, and seeds.  The seed
implementation re-traced and re-compiled ``simulate`` once per configuration
in a Python loop — compile time, not simulation time, dominated every paper
figure.

This module compiles ``simulate`` **once per config family** and ``vmap``s
it over the whole (config-grid × seeds) batch:

- a *family* is the static structure of a config — ``(model,
  read_my_writes, max_extra_delay)`` — everything that selects Python-level
  control flow inside the simulator.  Numeric knobs (``staleness``,
  ``push_prob``, ``v0``, ``straggler_*``) are pytree data leaves of
  ``ConsistencyConfig`` and batch freely;
- within a family the ring window is *harmonized* to the maximum
  ``effective_window`` so every config shares one compiled shape.  For
  bounded models results are unchanged (updates older than the bound are
  visible to every reader before they would fold either way), but float
  summation order differs from a run with a smaller window — compare
  against ``simulate`` with the same window (``SweepResult.harmonized``)
  when checking bit-identity.  For unbounded models (async/vap) the window
  is part of the simulated physics, so ``cfg.family`` already splits
  configs with different windows into separate compiles;
- with multiple devices the flattened (config × seed) batch is sharded over
  a 1-D mesh via ``shard_map`` (pad-to-multiple, slice after), spreading a
  paper figure across a pod with the same single compile;
- traced consumers (``core.tune``, the traced ``TimeModel``) can ride
  *inside* the compiled program via ``post``: a callable ``post(trace, cfg,
  seed, cfg_idx) -> pytree`` applied to each (config, seed) trace on device,
  before anything is fetched to host.  The ``trace`` a ``post`` callback
  receives follows the Trace-producer contract documented in ``core/ps.py``
  (all fields, clock axis leading), so the same callback works on traces
  from the executable runtime (``repro.psrun``) unchanged.  With
  ``keep_traces=False`` the full per-clock traces are dropped on device and
  only the (typically tiny) post outputs come back — a frontier over
  hundreds of grid points then moves O(points x T) floats instead of
  O(points x T x P^2).

Example::

    res = sweep(app, [ssp(1), ssp(3), ssp(7)], n_clocks=200, seeds=4)
    res.n_compiles            # 1 — one program for the whole figure
    res.trace(2, seed_idx=1)  # plain Trace for ssp(7), seed 1
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .consistency import DATA_FIELDS, ConsistencyConfig
from .ps import PSApp, Trace, simulate

# Incremented inside the traced function: one tick per (re)trace, i.e. per
# compiled program.  `benchmarks/sweep_bench.py` uses this to demonstrate
# batched-vs-sequential compile counts.
_TRACE_COUNTER = {"count": 0}

_KNOB_DTYPES = {"staleness": jnp.int32, "straggler_workers": jnp.int32,
                "s_xpod": jnp.int32, "agg_clocks": jnp.int32}


def trace_count() -> int:
    return _TRACE_COUNTER["count"]


def family_window(configs: Sequence[ConsistencyConfig]) -> int:
    """Harmonized ring window for one family: the max effective window."""
    return max(c.effective_window for c in configs)


def stack_configs(configs: Sequence[ConsistencyConfig],
                  window: int | None = None) -> ConsistencyConfig:
    """Stack same-family configs into one batched config (leaves [N])."""
    fams = {c.family for c in configs}
    if len(fams) != 1:
        raise ValueError(f"cannot stack configs across families: {fams}")
    window = window or family_window(configs)
    knobs = {
        name: jnp.asarray([getattr(c, name) for c in configs],
                          _KNOB_DTYPES.get(name, jnp.float32))
        for name in DATA_FIELDS
    }
    c0 = configs[0]
    # Pin the comm-substrate decision statically: after stacking, the knob
    # leaves are arrays (comm_active could no longer derive it from
    # values), and the family guarantees all members share it.
    return ConsistencyConfig(
        model=c0.model, read_my_writes=c0.read_my_writes, window=window,
        max_extra_delay=c0.max_extra_delay, n_pods=c0.n_pods,
        quant=c0.quant, wire=c0.comm_active, **knobs)


@dataclass
class SweepResult:
    """Per-config batched traces plus compile/timing evidence.

    ``traces[i]`` has every `Trace` leaf batched with a leading ``[n_seeds]``
    axis, aligned with ``configs[i]``.  ``harmonized[i]`` is ``configs[i]``
    with its family's shared ring window applied — a standalone
    ``simulate(app, harmonized[i], n_clocks, seed)`` reproduces
    ``trace(i, j)`` exactly.
    """

    configs: list
    harmonized: list
    seeds: np.ndarray
    traces: list
    n_compiles: int
    t_first_s: float          # first execution, including compile
    t_exec_s: float | None    # steady-state re-execution (timeit=True)
    families: dict = field(default_factory=dict)
    posts: list = field(default_factory=list)   # per-config batched post out

    def trace(self, i: int, seed_idx: int = 0) -> Trace:
        """Unbatched `Trace` for config ``i`` at seed index ``seed_idx``.

        Unavailable when the sweep ran with ``keep_traces=False``."""
        if self.traces[i] is None:
            raise ValueError("sweep ran with keep_traces=False; only `posts` "
                             "outputs were kept")
        return jax.tree_util.tree_map(lambda x: x[seed_idx], self.traces[i])

    def post(self, i: int, seed_idx: int | None = None):
        """Post-callback output for config ``i`` (one seed, or batched)."""
        if not self.posts or self.posts[i] is None:
            raise ValueError("sweep ran without a post callback")
        if seed_idx is None:
            return self.posts[i]
        return jax.tree_util.tree_map(lambda x: x[seed_idx], self.posts[i])


def _device_mesh(devices):
    if devices is None:
        devices = jax.devices()
    return list(devices)


def _family_runner(app: PSApp, n_clocks: int, record_views: bool, devices,
                   post=None, keep_traces: bool = True, mesh=None,
                   mesh_axis: str = "batch", obs=None):
    """Build the once-compiled runner for one family: `simulate` vmapped
    over a flat (config × seed) batch, sharded over devices when more than
    one is available.  Returns ``fn(stacked_flat, seeds_flat, idx_flat) ->
    {"trace": Trace|None, "post": pytree|None}``; repeated calls with the
    same batch shape reuse the compiled program.

    By default the batch shards over a 1-D ``("batch",)`` mesh spanning
    ``devices``; pass ``mesh``/``mesh_axis`` to shard it over one named
    axis of an existing mesh instead — e.g. the "pod" axis of a
    `launch.mesh.make_pods_mesh` 3-D mesh, spreading a sweep across pods
    while each pod's ``("data","model")`` devices stay free for the
    runtime (the batch is replicated over the non-sharded axes)."""

    def one(cfg, seed, cfg_idx):
        _TRACE_COUNTER["count"] += 1          # fires once per trace/compile
        tr = simulate(app, cfg, n_clocks, seed=seed,
                      record_views=record_views, obs=obs)
        return {
            "trace": tr if (keep_traces or post is None) else None,
            "post": None if post is None else post(tr, cfg, seed, cfg_idx),
        }

    batched = jax.vmap(one, in_axes=(0, 0, 0))
    if mesh is None:
        if len(devices) == 1:
            return jax.jit(batched)
        from ..launch.mesh import make_batch_mesh
        mesh, mesh_axis = make_batch_mesh(devices), "batch"
    n_shards = mesh.shape[mesh_axis]
    if n_shards == 1:
        return jax.jit(batched)

    from jax.sharding import PartitionSpec as P

    spec = P(mesh_axis)
    sharded = jax.jit(jax.shard_map(batched, mesh=mesh,
                                    in_specs=(spec, spec, spec),
                                    out_specs=spec, check_vma=False))

    def fn(stacked_flat, seeds_flat, idx_flat):
        n = seeds_flat.shape[0]
        pad = (-n) % n_shards
        if pad:
            padder = lambda x: jnp.concatenate(
                [x, jnp.broadcast_to(x[:1], (pad,) + x.shape[1:])])
            stacked_flat = jax.tree_util.tree_map(padder, stacked_flat)
            seeds_flat = padder(seeds_flat)
            idx_flat = padder(idx_flat)
        out = sharded(stacked_flat, seeds_flat, idx_flat)
        if pad:
            out = jax.tree_util.tree_map(lambda x: x[:n], out)
        return out

    return fn


def sweep(app: PSApp, configs: Sequence[ConsistencyConfig], n_clocks: int,
          seeds: int | Sequence[int] = 1, record_views: bool = False,
          devices=None, timeit: bool = False, post=None,
          keep_traces: bool = True, mesh=None,
          mesh_axis: str = "batch", obs=None) -> SweepResult:
    """Run every (config, seed) pair with one compiled program per family.

    Args:
      app: the PS application.
      configs: any mix of consistency configs; they are grouped by
        ``cfg.family`` and each group compiles exactly once.
      n_clocks: clocks to simulate.
      seeds: seed count (``k`` → seeds 0..k-1) or explicit seed values.
      record_views: record worker-0 views per clock (`Trace.views0`).
      devices: devices to shard the batch over (default: all local devices;
        a single device runs the plain vmap).
      timeit: re-execute each family once more to measure steady-state
        execution time (`t_exec_s`) separately from compile (`t_first_s`).
      post: optional traced consumer ``post(trace, cfg, seed, cfg_idx) ->
        pytree`` applied to every (config, seed) trace *inside* the compiled
        program (``cfg_idx`` is the config's index in ``configs``, e.g. for
        `TimeModel` RNG folding).  Outputs land in ``SweepResult.posts``,
        batched per config like ``traces``.
      keep_traces: when False (requires ``post``), drop the full traces on
        device and return only the post outputs.
      mesh, mesh_axis: shard the flat batch over one named axis of an
        existing mesh instead of the default 1-D batch mesh — e.g.
        ``mesh=make_pods_mesh(), mesh_axis="pod"`` spreads the sweep over
        the pod axis of the multi-pod mesh (replicated over the within-pod
        axes).  ``devices`` is ignored when ``mesh`` is given.
      obs: optional `repro.obs.ObsSpec` — thread telemetry accumulators
        through every simulated run; each trace's ``obs`` pytree comes
        back batched like any other `Trace` leaf.  ``None`` (default)
        compiles the exact pre-obs program.
    """
    if not keep_traces and post is None:
        raise ValueError("keep_traces=False requires a post callback")
    configs = list(configs)
    if isinstance(seeds, (int, np.integer)):
        seeds = np.arange(seeds)
    seeds = np.asarray(seeds, np.uint32)
    S = len(seeds)
    devices = _device_mesh(devices)

    groups: dict[tuple, list[int]] = {}
    for i, c in enumerate(configs):
        groups.setdefault(c.family, []).append(i)

    traces: list[Any] = [None] * len(configs)
    posts: list[Any] = [None] * len(configs)
    harmonized: list[Any] = [None] * len(configs)
    fam_info = {}
    t_first = 0.0
    t_exec = 0.0 if timeit else None
    for fam, idxs in groups.items():
        group = [configs[i] for i in idxs]
        W = family_window(group)
        stacked = stack_configs(group, window=W)
        for i in idxs:
            harmonized[i] = configs[i].replace(window=W)
        # flatten (config × seed): config-major, seed-minor
        rep = lambda x: jnp.repeat(x, S, axis=0)
        stacked_flat = jax.tree_util.tree_map(rep, stacked)
        seeds_flat = jnp.tile(jnp.asarray(seeds), len(group))
        idx_flat = jnp.repeat(jnp.asarray(idxs, jnp.uint32), S)

        fn = _family_runner(app, n_clocks, record_views, devices,
                            post=post, keep_traces=keep_traces,
                            mesh=mesh, mesh_axis=mesh_axis, obs=obs)
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(stacked_flat, seeds_flat, idx_flat))
        t_first += time.perf_counter() - t0
        if timeit:
            t0 = time.perf_counter()
            out = jax.block_until_ready(fn(stacked_flat, seeds_flat, idx_flat))
            t_exec += time.perf_counter() - t0
        for j, i in enumerate(idxs):
            sl = slice(j * S, (j + 1) * S)
            per_cfg = jax.tree_util.tree_map(lambda x: x[sl], out)
            traces[i] = per_cfg["trace"]
            posts[i] = per_cfg["post"]
        fam_info[fam] = {"configs": len(group), "window": W}

    return SweepResult(configs=configs, harmonized=harmonized, seeds=seeds,
                       traces=traces, n_compiles=len(groups),
                       t_first_s=t_first, t_exec_s=t_exec, families=fam_info,
                       posts=posts)
