"""The executable PS runtime: one `shard_map` clock step on a device mesh.

Layout (mesh axes ``("data", "model")``, built by `launch.mesh.make_ps_mesh`;
the hierarchical runtime in ``repro.pods`` reuses this module with worker
axes ``("pod", "data")`` on a 3-D mesh from `launch.mesh.make_pods_mesh`):

- the flat parameter vector (dim ``d``, zero-padded to divide the model
  axis) is sharded over ``"model"``: each model shard *owns* a contiguous
  coordinate block of the table — the server side;
- the ``P`` workers are partitioned over the *worker axes* (``"data"``, or
  ``("pod","data")`` pod-major — ``P`` must divide by the product of their
  sizes); each worker shard holds its workers' local state, the reader rows
  of the per-channel clock matrix ``cview[r, q]``, and (with the model
  axis) its block of every producer's in-transit update ring — the client
  cache;
- the update ring ``uring[W, P, d_block]`` is replicated over the worker
  axes and sharded over ``"model"``: every reader can see every producer's
  updates for the coordinates its column owns, which is exactly the cache
  layout of ESSPTable clients subscribed to all table rows.  Under the pod
  axis this replication *is* the per-pod parameter-shard replica: each pod
  holds a full copy of the table, and the per-clock all-gather of fresh
  updates over the worker axes is the eager delta channel that keeps the
  replicas' contents reconciled (only the newest clock's updates — one
  ``[P, d]`` delta, not the ``[W, P, d]`` replica — cross the pod
  boundary), while ``cview`` decides what each reader may *see* of them
  (two-tier staleness: `core.delays.staleness_bound_matrix`).

Per clock, inside ``shard_map`` (collectives annotated):

1. consistency enforcement advances the local reader rows of ``cview``
   (blocking fetches; VAP needs the global suffix-aggregate inf-norms —
   one ``pmax`` over ``"model"``);
2. views materialize shard-locally through ``kernels.ops.ring_view``
   (readers × owned coordinates — the Pallas path on TPU), then assemble
   per-reader full views with an ``all_gather`` over ``"model"``;
3. each worker runs ``app.worker_update`` on its own worker shard;
4. updates are pushed to the owning shards: ``all_gather`` over the worker
   axes then keep the owned coordinate block (a host-mesh stand-in for the
   per-shard all-to-all a network PS would do), written into the ring;
   the oldest ring slot folds into the shard's base (the delta-compressed
   fold: ``P`` producer updates collapse into one ``[d_block]`` vector);
5. the end-of-clock delivery matrix (the synthetic network model shared
   with the simulator — `core.delays`, two-tier under ``cfg.n_pods > 1``)
   advances ``cview`` eagerly for ESSP/async/VAP; SSP ignores pushes
   (pull-based);
6. the clock's record (losses over the full data, staleness, deliveries)
   becomes one row of the `Trace`: the table assembles with an
   ``all_gather`` over ``"model"``, reader 0's view reaches every worker
   shard through a ``psum`` over the worker axes, and each shard
   evaluates the objective over each of its own workers' data
   (``app.loss_sum``); one ``psum`` over the worker axes hands every
   shard all workers' sums, added in worker order as ``app.loss`` adds
   them (`core.ps.fold_workers`).

Each step runs under a ``jax.named_scope``: ``psrun.enforce`` (1, with
the suffix norms), ``psrun.view`` (2), ``psrun.update`` (3),
``psrun.push`` (4 and 5) and ``psrun.record`` (6), so a profiler trace
can charge every device op of the clock to its stage; each collective
runs under a nested ``exchange`` scope, which splits a stage's
collective time from its compute.

RNG and arithmetic mirror ``core.ps.simulate`` *exactly* (same key splits,
same per-coordinate reduction orders), which is what makes the simulator an
executable oracle: a seeded BSP run matches bit for bit, and the numeric
knobs of `ConsistencyConfig` stay jit *arguments* (pytree data), so
re-running with different staleness/push_prob/straggler knobs reuses the
compiled program — one compile per config family, like ``core.sweep``.

Mid-run state
-------------
The compiled step carries an explicit `PSState` (clock, base, ring, cview,
worker locals, RNG key), exposed through ``init_state`` / ``run_from``:
``run_from(state, n)`` returns the per-clock `Trace` plus the advanced
state, and resuming from a saved state reproduces the uninterrupted run
bit for bit (``checkpoint.io.save_runtime`` round-trips it through disk —
`tests/test_pods.py` pins the determinism).
"""
from __future__ import annotations

from dataclasses import dataclass, replace
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P_

from ..comm import substrate as comm
from ..comm import wire
from ..core.consistency import ConsistencyConfig
from ..core.delays import ChurnSchedule, churn_live, churn_rates, \
    delivery_matrix, pod_of, staleness_bound_matrix
from ..core.ps import PSApp, Trace, enforce_vap, fold_workers
from ..kernels import ops
from ..kernels.ref import RING_EMPTY, RING_INVALID
from ..launch.mesh import make_ps_mesh
from ..obs import metrics as obsm

# Ticks once per (re)trace of the runtime body, i.e. once per compiled
# program — the same compile-count evidence `core.sweep` keeps.  Numeric
# knob changes must NOT tick it (one compile per config family).
_TRACE_COUNTER = {"count": 0}


def trace_count() -> int:
    return _TRACE_COUNTER["count"]


@jax.tree_util.register_dataclass
@dataclass(frozen=True)
class PSState:
    """Mid-run runtime state (everything the clock step carries).

    ``base``/``uring`` are in the runtime's padded coordinate layout
    (``dpad`` divides the model axis); ``clock`` is the next clock to
    execute.  A `PSState` is an ordinary pytree of arrays, so
    ``checkpoint.io.save`` / ``restore`` round-trip it unchanged.
    """

    clock: jax.Array           # [] i32 — next clock to execute
    base: jax.Array            # [dpad] folded (globally visible) updates
    #                            (under the comm substrate: constant x0 —
    #                            folds go to comm["base_pod"] per pod)
    uring: jax.Array           # [W, P, dpad] in-transit update ring
    uclock: jax.Array          # [W] clock stored in each ring slot
    cview: jax.Array           # [P, P] per-channel visibility clocks
    local: Any                 # worker-local state (leaves lead with P)
    rng: jax.Array             # PRNG key (the simulator's key stream)
    comm: Any = None           # comm-substrate state (repro.comm: acc,
    #                            res, xring, base_pod, xbase_pod) when
    #                            cfg.comm_active; None on the dense path


def default_mesh(n_workers: int, devices=None):
    """The widest ``("data","model")`` mesh for ``n_workers`` that stays in
    the bit-identity regime: the data axis is the largest divisor of the
    device count that divides the worker count while keeping >= 2 workers
    per shard; an even leftover becomes 2 model-shard columns."""
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    data = 1
    for cand in range(min(n, n_workers // 2), 0, -1):
        if n_workers % cand == 0 and n % cand == 0:
            data = cand
            break
    rest = n // data
    model = 2 if (rest > 1 and rest % 2 == 0) else 1
    return make_ps_mesh(data=data, model=model, devices=devices)


def _layout(app: PSApp, mesh, worker_axes):
    """Validate the (app, mesh) pairing and derive the shard geometry."""
    assert set(worker_axes) | {"model"} <= set(mesh.axis_names), \
        (mesh.axis_names, worker_axes)
    DP = 1
    for ax in worker_axes:
        DP *= mesh.shape[ax]
    M = mesh.shape["model"]
    P, d = app.n_workers, app.dim
    if P % DP:
        raise ValueError(
            f"n_workers={P} must divide by the worker axes "
            f"{tuple(worker_axes)} of total size {DP}; build a smaller "
            f"mesh with launch.mesh.make_ps_mesh/make_pods_mesh")
    dpad = -(-d // M) * M
    return DP, M, P // DP, dpad, dpad // M


def _local_fixed(app: PSApp) -> bool:
    """Whether ``app.worker_update`` hands its worker-local state back
    unchanged (MF's ratings, read only by the loss).  The runtime then
    keeps that state out of the scan's carry and out of the program's
    outputs, and returns the arrays it was given, placed once with the
    program's input sharding: a segment neither carries, moves nor writes
    out a copy of it."""
    one = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype),
                       app.local0)
    scalar = jax.ShapeDtypeStruct((), jnp.int32)
    jaxpr = jax.make_jaxpr(app.worker_update)(
        jax.ShapeDtypeStruct((app.dim,), jnp.float32), one, scalar, scalar,
        jax.ShapeDtypeStruct((2,), jnp.uint32)).jaxpr
    n = len(jax.tree.leaves(one))
    return all(o is i for o, i in zip(jaxpr.outvars[1:],
                                      jaxpr.invars[1:1 + n], strict=True))


def make_run_fn(app: PSApp, cfg: ConsistencyConfig, n_clocks: int,
                mesh=None, record_views: bool = False,
                worker_axes: tuple = ("data",),
                schedule: ChurnSchedule | None = None,
                obs: obsm.ObsSpec | None = None,
                faults: wire.WireFaults | None = None):
    """Build the jitted runtime for one config *family* on ``mesh``.

    Returns a callable ``fn(seed, cfg, schedule=None) -> Trace``.
    ``cfg``'s numeric knobs are traced jit arguments — calling with
    different staleness/push_prob/straggler values (same model, same ring
    window) reuses the compiled program.  The ``cfg`` given here only
    fixes the static structure (model, window, read_my_writes, n_pods).
    Likewise ``schedule`` here only fixes the churn *structure* (present
    or not, which optional arrays it carries, the in-flight policy): the
    actual liveness/regime arrays are traced jit arguments too, so
    same-shape schedules share one compile.

    The callable also exposes the state-carrying entry points
    ``fn.init_state(seed) -> PSState`` and ``fn.run_from(state, cfg,
    schedule) -> (Trace, PSState)``; ``fn(seed, cfg)`` is exactly
    ``fn.run_from(fn.init_state(seed), cfg)[0]``.  ``fn.lower(state, cfg)``
    lowers ``run_from``'s program without running it.  Each call runs
    under the profiler span ``psrun.dispatch`` (a no-op when no profiler
    records).  Schedules index by
    *absolute* clock, so a resumed segment reads the same slice the
    uninterrupted run would.

    ``worker_axes`` names the mesh axes that partition the workers
    (``("data",)`` for the flat runtime, ``("pod", "data")`` for
    `repro.pods` — pod-major, matching `core.delays.pod_of`).

    ``obs`` (static, `repro.obs.ObsSpec`) threads telemetry accumulators
    through the scan — each worker shard folds its own reader rows, one
    ``psum``/``pmax`` per leaf after the scan merges them, and the result
    lands in ``Trace.obs``.  ``None`` (default) compiles the exact
    pre-obs program.

    ``faults`` (`repro.comm.wire.WireFaults`) makes the cross-pod wire
    lossy: seeded drop/duplicate/delay masks drive the stop-and-wait
    ack/retransmit protocol of ``wire.wire_step``, bit-identical to the
    simulator oracle.  Like the churn schedule, only the *structure*
    (presence + the static rto0/max_retries/max_delay/heal knobs) is
    compiled in; the mask arrays are traced jit arguments.  Requires
    ``cfg.comm_active``.
    """
    mesh = make_ps_mesh() if mesh is None else mesh
    worker_axes = tuple(worker_axes)
    _DP, _M, Pl, dpad, dl = _layout(app, mesh, worker_axes)
    P, d = app.n_workers, app.dim
    W = cfg.effective_window
    if cfg.n_pods > 1 and P % cfg.n_pods:
        raise ValueError(f"n_workers={P} must divide by n_pods={cfg.n_pods}")
    f32 = jnp.float32
    # Static: route cross-pod shipment through the comm substrate — the
    # same compressed state machine as core.ps.simulate's wired mode, so
    # the oracle contract covers the compressed path too.
    wired = cfg.comm_active
    quant0, G = cfg.quant, cfg.n_pods
    obs_enabled = obsm.obs_on(obs)
    churned = schedule is not None
    if churned and schedule.live.shape[1] != P:
        raise ValueError(f"schedule has {schedule.live.shape[1]} workers, "
                         f"app has {P}")
    faulted = faults is not None
    if faulted:
        wire.validate_faults(faults, cfg, P, W)
    local_fixed = _local_fixed(app)

    def body(cfg, clock0, base, uring, uclock, cview, local, rng,
             *extra):
        _i = 0
        cst = flt = sched = None
        if wired:
            cst, _i = extra[_i], _i + 1
        if faulted:
            flt, _i = extra[_i], _i + 1
        if churned:
            sched = extra[_i]
        # local shards: base [dl], uring [W, P, dl], uclock [W] (replicated),
        # cview [Pl, P], local leaves [Pl, ...], rng/clock0 replicated;
        # comm state (wired only): acc/res [P, dl], xring [W, P, dl],
        # base_pod/xbase_pod [G, dl] — all sharded over "model" like uring.
        _TRACE_COUNTER["count"] += 1          # fires once per trace/compile
        di = jax.lax.axis_index(worker_axes)
        mi = jax.lax.axis_index("model")
        rows0 = (di * Pl).astype(jnp.int32)
        worker_ids = rows0 + jnp.arange(Pl, dtype=jnp.int32)
        producer_ids = jnp.arange(P, dtype=jnp.int32)
        eye_l = worker_ids[:, None] == producer_ids[None, :]   # local eye rows
        # Two-tier staleness bound on the local reader rows (`s` intra-pod,
        # `s + s_xpod` cross-pod, `+ agg_clocks - 1` under the substrate;
        # one-tier and exactly `s` when n_pods=1).  The lossy-wire trigger
        # stays *unwidened* — refresh targets are capped on `wire_tip`, so
        # eager firing is safe; only the declared contract carries the
        # `+ retry_budget` widening (oracle mirror).
        s_eff = staleness_bound_matrix(cfg, worker_ids, P)       # [Pl, P]
        if wired:
            pods_all = pod_of(P, G)                            # [P]
            reader_pods = pods_all[worker_ids]                 # [Pl]
            in_pod = reader_pods[:, None] == pods_all[None, :]  # [Pl, P]
            zeros_dl = jnp.zeros((dl,), f32)
        if obs_enabled:
            # channel-tier mask on the local reader rows for the
            # forced-refresh split (all-True when G == 1)
            if wired:
                in_pod_obs = in_pod
            else:
                pods_o = pod_of(P, G)
                in_pod_obs = pods_o[worker_ids][:, None] == pods_o[None, :]

        vmapped_update = jax.vmap(app.worker_update,
                                  in_axes=(0, 0, 0, None, 0))

        # Each stage of the clock runs under a named scope ``psrun.<stage>``
        # (enforce, view, update, push, record), and each collective in it
        # under a nested ``exchange``.  The names reach only the HLO
        # ``op_name`` metadata, which a profiler trace keeps per device op
        # (``chipbench/stages.py`` reads it); the arithmetic is unchanged.
        def exchange():
            return jax.named_scope("exchange")

        def losses(xs, local):
            """``app``'s objective at each of ``xs`` (full ``[d]`` vectors):
            each worker shard sums it over each of its own workers' data
            (``app.loss_sum``) into those workers' slots of a ``[P]``
            vector; one ``psum`` of the vectors (adding zeros: exact) hands
            every shard all of them, folded in worker order as
            ``app.loss`` folds them.  An app without ``loss_sum`` gets
            every worker's locals gathered instead."""
            if app.loss_sum is None:
                with exchange():
                    local_all = jax.tree_util.tree_map(
                        lambda a: jax.lax.all_gather(a, worker_axes, axis=0,
                                                     tiled=True), local)
                return [app.loss(x, local_all) for x in xs]
            parts, counts = zip(*(app.loss_sum(x, local, worker_ids)
                                  for x in xs))
            slots = jnp.zeros((len(xs), P), f32).at[:, worker_ids].set(
                jnp.stack(parts))
            with exchange():
                sums = jax.lax.psum(slots, worker_axes)  # analysis: ignore[unmasked-gather] -- per-worker loss sums, not producer updates; dead workers' frozen locals count, as in the oracle
            return [fold_workers(sums[i]) / (n * P)
                    for i, n in enumerate(counts)]

        local_in = local

        def step(carry, c):
            if obs_enabled:
                *carry, oacc = carry
            if wired:
                base, uring, uclock, cview, local, rng, cst = carry
            else:
                base, uring, uclock, cview, local, rng = carry
            if local_fixed:
                local = local_in
            with jax.named_scope("psrun.enforce"):
                rng, k_upd, k_net = jax.random.split(rng, 3)

                if churned:
                    live_now, died = churn_live(sched, c)     # [P], [P]
                    live_l = jax.lax.dynamic_slice_in_dim(
                        live_now, rows0, Pl)                  # local reader rows
                    rates = churn_rates(cfg, sched, P, c)
                    if sched.drop_inflight:
                        # drop policy: mirror the oracle — a dying worker's
                        # in-flight ring rows (and unshipped comm rows) zero
                        # out the clock it dies.
                        keep = ~died
                        uring = jnp.where(keep[None, :, None], uring, 0.0)
                        if wired:
                            cst = dict(cst,
                                       acc=jnp.where(keep[:, None],
                                                     cst["acc"], 0.0),
                                       res=jnp.where(keep[:, None],
                                                     cst["res"], 0.0),
                                       xring=jnp.where(keep[None, :, None],
                                                       cst["xring"], 0.0))
                        if faulted:
                            # a dying producer's unacked shipment and lane
                            # copies vanish with it (oracle mirror)
                            cst = wire.drop_pending(cst, keep)
                    cview_pre = cview
                else:
                    rates = None

                # global per-producer suffix-aggregate inf-norms: local block
                # norms, max-reduced over the owning shards.
                norms = ops.vap_suffix_norms(uring, uclock, c)
                with exchange():
                    norms = jax.lax.pmax(norms, "model")           # [W+1, P]

                # --- 1. pre-read consistency enforcement (blocking fetches) --
                if cfg.model == "bsp":
                    forced = cview < (c - 1)
                    cview = jnp.full_like(cview, c - 1)
                elif cfg.model in ("ssp", "essp"):
                    forced = cview < (c - s_eff - 1)
                    if wired and faulted:
                        # a faulted cross-pod refresh can only fetch what has
                        # actually *arrived*: wire_tip caps the shipped
                        # boundary (oracle mirror)
                        tgt = jnp.where(in_pod, c - 1,
                                        jnp.minimum(
                                            comm.shipped_through(
                                                c, cfg.agg_clocks),
                                            cst["wire_tip"][None, :]))
                        cview = jnp.where(forced, tgt, cview)
                    elif wired:
                        # cross-pod refreshes fetch what has *shipped* (through
                        # the last aggregation boundary), mirroring the oracle
                        tgt = jnp.where(in_pod, c - 1,
                                        comm.shipped_through(c, cfg.agg_clocks))
                        cview = jnp.where(forced, tgt, cview)
                    else:
                        cview = jnp.where(forced, c - 1, cview)
                elif cfg.model == "vap":
                    cview, forced = enforce_vap(cfg, c, cview, norms, W)
                else:  # async
                    forced = jnp.zeros_like(cview, dtype=bool)

                if cfg.read_my_writes:
                    cview = jnp.where(eye_l, c - 1, cview)

                if churned:
                    # dead readers neither fetch nor advance (oracle mirror)
                    forced = forced & live_l[:, None]
                    cview = jnp.where(live_l[:, None], cview, cview_pre)

                staleness = cview - c                              # [Pl, P]

                kcur = jnp.clip(c - 1 - cview, 0, W)               # [Pl, P]
                intransit_inf = jnp.max(norms[kcur, producer_ids[None, :]])
                with exchange():
                    intransit_inf = jax.lax.pmax(intransit_inf, worker_axes)

            with jax.named_scope("psrun.view"):
                # --- 2. materialize views: shard-local, then assemble --------
                if wired:
                    # intra-pod producers read raw, cross-pod producers read
                    # the shipped wire ring; folded bases assemble per reader
                    # pod — the same three-term sum as the oracle.
                    cv_intra = jnp.where(in_pod, cview, RING_EMPTY)
                    cv_xpod = jnp.where(in_pod, RING_EMPTY, cview)
                    rb = comm.reader_base(base, cst["base_pod"],
                                          cst["xbase_pod"], reader_pods)
                    views_l = (rb
                               + ops.ring_view(zeros_dl, uring, uclock,
                                               cv_intra)
                               + ops.ring_view(zeros_dl, cst["xring"], uclock,
                                               cv_xpod))              # [Pl, dl]
                else:
                    views_l = ops.ring_view(base, uring, uclock, cview)
                with exchange():
                    views = jax.lax.all_gather(views_l, "model", axis=1,
                                               tiled=True)[:, :d]    # [Pl, d]

            with jax.named_scope("psrun.update"):
                # --- 3. worker computation (this shard's workers only) -------
                upd_keys = jax.lax.dynamic_slice_in_dim(
                    jax.random.split(k_upd, P), rows0, Pl)
                u_l, local_new = vmapped_update(views, local, worker_ids, c,
                                                upd_keys)
                u_l = u_l.astype(f32)                              # [Pl, d]
                if churned:
                    # mask dead workers' pushes BEFORE the all-gather so the
                    # gathered [P, d] (and u_l2 on it) matches the oracle's
                    # masked operand bit for bit; freeze their local state.
                    u_l = jnp.where(live_l[:, None], u_l, 0.0)
                    local = jax.tree_util.tree_map(
                        lambda new, old: jnp.where(
                            live_l.reshape((Pl,) + (1,) * (new.ndim - 1)),
                            new, old),
                        local_new, local)
                else:
                    local = local_new

            with jax.named_scope("psrun.push"):
                # --- 4. push to owning shards; fold oldest slot --------------
                # The all-gather over the worker axes is the data plane: under a
                # pod axis it is the eager cross-pod delta channel (one fresh
                # [P, d] update set per clock keeps every pod replica's ring
                # reconciled; visibility stays gated by cview above).
                with exchange():
                    u_all = jax.lax.all_gather(u_l, worker_axes, axis=0,
                                               tiled=True)
                # norm on the gathered [P, d] — the oracle's operand shape, so
                # XLA emits the same reduction and the floats match bit-for-bit
                u_l2 = jnp.linalg.norm(u_all, axis=-1)
                u_all = jnp.pad(u_all, ((0, 0), (0, dpad - d)))
                u_blk = jax.lax.dynamic_slice(u_all, (0, mi * dl), (P, dl))
                slot = jnp.mod(c, W)
                old_valid = uclock[slot] > RING_INVALID
                if wired:
                    w_old = jnp.where(old_valid, 1.0, 0.0)
                    cst = dict(cst,
                               base_pod=cst["base_pod"]
                               + w_old * comm.fold_pods(uring[slot], G),
                               xbase_pod=cst["xbase_pod"]
                               + w_old * comm.fold_pods(cst["xring"][slot], G))
                else:
                    base = base + jnp.where(old_valid, 1.0, 0.0) * jnp.sum(
                        uring[slot], axis=0)
                uring = uring.at[slot].set(u_blk)
                uclock = uclock.at[slot].set(c)
                if wired:
                    # --- 4b. comm substrate: accumulate; ship on boundary ----
                    # thresholds/scales/counts come from the *gathered* full
                    # rows (bit-identical to the oracle's [P, d] sort); the
                    # pack itself is elementwise on the local shard.
                    acc = cst["acc"] + u_blk
                    delta = acc + cst["res"]                     # [P, dl]
                    with exchange():
                        delta_full = jax.lax.all_gather(
                            delta, "model", axis=1, tiled=True)[:, :d]
                    thresh = comm.row_threshold(delta_full, cfg.topk_frac)
                    scale = comm.quant_scale(delta_full, cfg.quant)
                    wire_u, resid = ops.delta_pack(delta, thresh, scale,
                                                   cfg.quant)
                    nnz = comm.selected_count(delta_full, thresh)
                    ship = comm.ship_now(c, cfg.agg_clocks)
                    if churned:
                        # dead producers hold their shipment (drain policy:
                        # acc/res keep the mass until the first boundary
                        # after rejoin) — oracle mirror.
                        ship = ship & live_now                 # [P]
                    if faulted:
                        # stop-and-wait ARQ: a busy producer (previous
                        # shipment unacked) skips the boundary — acc keeps
                        # accumulating and the skipped content rides the
                        # next shipment (oracle mirror).
                        ship = ship & wire.idle(cst)           # [P]
                    ship_b = ship[:, None] if (churned or faulted) else ship
                    wire_u = jnp.where(ship_b, wire_u, jnp.zeros_like(wire_u))
                    floats = comm.wire_floats(nnz, d, cfg.quant)
                    if faulted:
                        # shipments enter the wire ring only when they
                        # *arrive*, via the seq-guarded fold in wire_step
                        # (which also runs retransmits, give-up healing and
                        # instant arrivals, and charges every transmission —
                        # retries included — into ship_floats).
                        cst = dict(cst,
                                   acc=jnp.where(ship_b, jnp.zeros_like(acc),
                                                 acc),
                                   res=jnp.where(ship_b, resid, cst["res"]),
                                   xring=cst["xring"].at[slot].set(
                                       jnp.zeros_like(wire_u)))
                        cst, ship_floats = wire.wire_step(
                            cst, wire_u, floats, ship, c, flt,
                            live=live_now if churned else None)
                    else:
                        cst = dict(cst,
                                   acc=jnp.where(ship_b, jnp.zeros_like(acc),
                                                 acc),
                                   res=jnp.where(ship_b, resid, cst["res"]),
                                   xring=cst["xring"].at[slot].set(wire_u))
                        ship_floats = jnp.where(
                            ship, floats, jnp.zeros((P,), f32))
                else:
                    ship_floats = comm.dense_ship_floats(cfg.model, P, d)
                    if churned:
                        ship_floats = jnp.where(live_now, ship_floats, 0.0)

                # --- 5. end-of-clock delivery (affects reads at c+1) ---------
                if cfg.model == "bsp":
                    delivered = jnp.ones((Pl, P), bool)
                    if churned:
                        delivered = delivered & live_l[:, None]
                        cview = jnp.where(live_l[:, None],
                                          jnp.full_like(cview, c), cview)
                    else:
                        cview = jnp.full_like(cview, c)
                elif cfg.model == "ssp":
                    delivered = jnp.zeros((Pl, P), bool)
                else:  # essp / async / vap: delay-driven eager delivery
                    delivered = jax.lax.dynamic_slice_in_dim(
                        delivery_matrix(k_net, cfg, P, rates), rows0, Pl)
                    if churned:
                        delivered = delivered & live_l[:, None]
                    if wired and faulted:
                        # deliveries carry the latest *arrived* shipment:
                        # boundary target capped by wire_tip (oracle mirror)
                        tgt = jnp.where(in_pod, c,
                                        jnp.minimum(
                                            comm.shipped_end(
                                                c, cfg.agg_clocks),
                                            cst["wire_tip"][None, :]))
                        cview = jnp.where(delivered, jnp.maximum(cview, tgt),
                                          cview)
                    elif wired:
                        tgt = jnp.where(in_pod, c,
                                        comm.shipped_end(c, cfg.agg_clocks))
                        cview = jnp.where(delivered, jnp.maximum(cview, tgt),
                                          cview)
                    else:
                        cview = jnp.where(delivered, c, cview)

            with jax.named_scope("psrun.record"):
                # --- 6. record: the table and reader 0's view, the losses
                # per worker shard (`losses`) ------------------------------
                if wired:
                    x_ref = (base + jnp.sum(cst["base_pod"], axis=0)) + jnp.sum(
                        uring * (uclock[:, None, None] > RING_INVALID),
                        axis=(0, 1))
                else:
                    x_ref = base + jnp.sum(
                        uring * (uclock[:, None, None] > RING_INVALID),
                        axis=(0, 1))
                # reader 0's view, sent from the shard that holds it to every
                # shard: the others add zeros (exact)
                sent = jnp.where(di == 0, views[0], 0.0)
                with exchange():
                    x_ref = jax.lax.all_gather(x_ref, "model", tiled=True)
                    view0 = jax.lax.psum(sent, worker_axes)  # analysis: ignore[unmasked-gather] -- reader 0's view sent to every shard, not a producer reduction; the oracle reads views[0] alike
                x_ref = x_ref[:d]
                loss_ref, loss_view = losses((x_ref, view0), local)
                out = dict(loss_ref=loss_ref, loss_view=loss_view,
                           staleness=staleness, forced=forced,
                           delivered=delivered,
                           u_l2=u_l2, intransit_inf=intransit_inf,
                           ship_floats=ship_floats,
                           live=live_now if churned
                           else jnp.ones((P,), bool))
                if record_views:
                    out["views0"] = view0
                if obs_enabled:
                    # shard-local fold of this clock's step values; shards
                    # merge once after the scan (device_reduce), not per clock
                    oacc = obsm.device_update(
                        oacc, staleness=staleness, forced=forced,
                        delivered=delivered, ship_floats=ship_floats,
                        live=out["live"],
                        live_rows=live_l if churned
                        else jnp.ones((Pl,), bool),
                        in_pod=in_pod_obs)
            if local_fixed:
                local = None
            new_carry = ((base, uring, uclock, cview, local, rng, cst)
                         if wired else
                         (base, uring, uclock, cview, local, rng))
            if obs_enabled:
                new_carry = (*new_carry, oacc)
            return new_carry, out

        clocks = clock0 + jnp.arange(n_clocks, dtype=jnp.int32)
        if local_fixed:
            local = None
        carry0 = ((base, uring, uclock, cview, local, rng, cst)
                  if wired else
                  (base, uring, uclock, cview, local, rng))
        if obs_enabled:
            carry0 = (*carry0, obsm.device_init(P, obs.n_buckets))
        carryT, ys = jax.lax.scan(step, carry0, clocks)
        base, uring, uclock, cview, local, rng = carryT[:6]
        if wired:
            cst = carryT[6]
            x_final = (base + jnp.sum(cst["base_pod"], axis=0)) + jnp.sum(
                uring * (uclock[:, None, None] > RING_INVALID), axis=(0, 1))
        else:
            x_final = base + jnp.sum(
                uring * (uclock[:, None, None] > RING_INVALID), axis=(0, 1))
        state = dict(clock=clock0 + n_clocks, base=base,
                     uring=uring, uclock=uclock, cview=cview,
                     local=local, rng=rng,
                     comm=cst if wired else None)
        ret = {"ys": ys, "x_final": x_final, "state": state}
        if obs_enabled:
            # merge the per-shard accumulators: one psum/pmax per reduced
            # leaf for the whole run (replicated leaves pass through)
            ret["obs"] = obsm.device_reduce(carryT[-1], worker_axes)
        return ret

    local_spec = jax.tree_util.tree_map(lambda _: P_(worker_axes), app.local0)
    ys_specs = {"loss_ref": P_(), "loss_view": P_(),
                "staleness": P_(None, worker_axes, None),
                "forced": P_(None, worker_axes, None),
                "delivered": P_(None, worker_axes, None),
                "u_l2": P_(), "intransit_inf": P_(), "ship_floats": P_(),
                "live": P_()}
    if record_views:
        ys_specs["views0"] = P_()
    comm_specs = None
    if wired:
        comm_specs = dict(acc=P_(None, "model"), res=P_(None, "model"),
                          xring=P_(None, None, "model"),
                          base_pod=P_(None, "model"),
                          xbase_pod=P_(None, "model"))
        if faulted:
            # ARQ leaves: the pending payload shards like acc; the per-
            # producer scalars ([P]) are replicated (every shard runs the
            # same protocol decisions off the replicated fault masks)
            comm_specs.update({
                k: P_(None, "model") if k == "pend" else P_()
                for k in wire.WIRE_KEYS})
    state_specs = dict(clock=P_(), base=P_("model"),
                       uring=P_(None, None, "model"), uclock=P_(),
                       cview=P_(worker_axes, None),
                       local=None if local_fixed else local_spec,
                       rng=P_(), comm=comm_specs)
    in_specs = [P_(), P_(), P_("model"), P_(None, None, "model"), P_(),
                P_(worker_axes, None), local_spec, P_()]
    if wired:
        in_specs.append(comm_specs)
    if faulted:
        # fault masks are replicated: every shard needs all P producers'
        # fault rows (like the churn schedule)
        in_specs.append(jax.tree_util.tree_map(lambda _: P_(), faults))
    if churned:
        # the schedule is replicated: every shard reads the full per-clock
        # liveness rows (it needs producer liveness for all P)
        in_specs.append(jax.tree_util.tree_map(lambda _: P_(), schedule))
    out_specs = {"ys": ys_specs, "x_final": P_("model"),
                 "state": state_specs}
    if obs_enabled:
        # post-reduce the accumulators are replicated on every shard
        out_specs["obs"] = jax.tree_util.tree_map(
            lambda _: P_(), obsm.device_init(P, obs.n_buckets))
    sharded = jax.shard_map(
        body, mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=out_specs,
        check_vma=False)

    def run(state: PSState, cfg, sched, flt):
        args = (cfg, state.clock, state.base, state.uring,
                state.uclock, state.cview, state.local, state.rng)
        if wired:
            args += (state.comm,)
        if faulted:
            args += (flt,)
        if churned:
            args += (sched,)
        out = sharded(*args)
        ys = out["ys"]
        trace = Trace(loss_ref=ys["loss_ref"], loss_view=ys["loss_view"],
                      staleness=ys["staleness"], forced=ys["forced"],
                      delivered=ys["delivered"], u_l2=ys["u_l2"],
                      intransit_inf=ys["intransit_inf"],
                      ship_floats=ys["ship_floats"], live=ys["live"],
                      views0=ys.get("views0"),
                      x_final=out["x_final"][:d],
                      locals_final=out["state"]["local"],
                      obs=out.get("obs"))
        return trace, PSState(**out["state"])

    jit_run = jax.jit(run)
    local_shardings = jax.tree_util.tree_map(
        lambda _: NamedSharding(mesh, P_(worker_axes)), app.local0)

    def place_local(local):
        return jax.device_put(local, local_shardings) if local_fixed else local

    def jitted(state: PSState, *rest):
        if not local_fixed:
            return jit_run(state, *rest)
        state = replace(state, local=place_local(state.local))
        trace, new = jit_run(state, *rest)
        return (replace(trace, locals_final=state.local),
                replace(new, local=state.local))

    placed = {k: jax.tree_util.tree_map(
        lambda spec: NamedSharding(mesh, spec), v,
        is_leaf=lambda x: isinstance(x, P_))
        for k, v in state_specs.items() if v is not None and k != "local"}

    @partial(jax.jit, out_shardings=placed)
    def fresh(x0, rng):
        state = dict(
            clock=jnp.zeros((), jnp.int32),
            base=jnp.pad(x0.astype(f32), (0, dpad - d)),
            uring=jnp.zeros((W, P, dpad), f32),
            uclock=jnp.full((W,), RING_EMPTY, jnp.int32),
            cview=jnp.full((P, P), -1, jnp.int32), rng=rng)
        if wired:
            state["comm"] = ({**comm.init_state(W, P, dpad, G),
                              **wire.init_wire_state(P, dpad)} if faulted
                             else comm.init_state(W, P, dpad, G))
        return state

    def init_state(seed) -> PSState:
        """Clock-0 state for ``seed`` (the simulator's initial conditions,
        in the runtime's padded layout), made where the program takes it:
        every leaf laid out with the program's input sharding, so the
        first segment runs the program every later one runs and no chip
        holds more than its shard of the ring."""
        made = fresh(app.x0, jax.random.PRNGKey(seed))
        return PSState(local=jax.device_put(app.local0, local_shardings),
                       comm=made.pop("comm", None), **made)

    def _norm_cfg(cfg_run: ConsistencyConfig | None) -> ConsistencyConfig:
        c = cfg if cfg_run is None else cfg_run
        if c.effective_window != W:
            raise ValueError(
                f"runtime compiled for ring window {W}, got "
                f"{c.effective_window}; set cfg.window explicitly or build "
                f"a new run fn")
        if c.comm_active != wired or (wired and c.quant != quant0):
            raise ValueError(
                f"runtime compiled with comm_active={wired} "
                f"(quant={quant0!r}); got comm_active={c.comm_active} "
                f"(quant={c.quant!r}) — build a new run fn for a "
                f"different comm structure")
        # normalize the static window/wire flag so every same-family call
        # shares one pytree treedef (and therefore one jit cache entry)
        return c.replace(window=W, wire=wired)

    def _norm_sched(sched):
        s = schedule if sched is None else sched
        if (s is not None) != churned:
            raise ValueError(
                f"runtime compiled with churn={'on' if churned else 'off'}; "
                f"build a new run fn to change the churn structure")
        if s is not None and s.live.shape[1] != P:
            raise ValueError(f"schedule has {s.live.shape[1]} workers, "
                             f"app has {P}")
        return s

    def _norm_faults(flt):
        f = faults if flt is None else flt
        if (f is not None) != faulted:
            raise ValueError(
                f"runtime compiled with faults="
                f"{'on' if faulted else 'off'}; build a new run fn to "
                f"change the fault structure")
        if f is not None and wire.faults_key(f) != wire.faults_key(faults):
            raise ValueError(
                f"runtime compiled with ARQ knobs "
                f"{wire.faults_key(faults)}, got {wire.faults_key(f)}; "
                f"the knobs are static — build a new run fn")
        return f

    def _args(state, cfg_run, schedule, faults):
        return (state, _norm_cfg(cfg_run), _norm_sched(schedule),
                _norm_faults(faults))

    def run_from(state: PSState, cfg_run: ConsistencyConfig | None = None,
                 schedule: ChurnSchedule | None = None,
                 faults: wire.WireFaults | None = None):
        """Advance ``state`` by ``n_clocks``; returns ``(Trace, PSState)``.
        Bit-identical to running the clocks uninterrupted."""
        with jax.profiler.TraceAnnotation("psrun.dispatch"):
            return jitted(*_args(state, cfg_run, schedule, faults))

    def fn(seed, cfg_run: ConsistencyConfig | None = None,
           schedule: ChurnSchedule | None = None,
           faults: wire.WireFaults | None = None) -> Trace:
        with jax.profiler.TraceAnnotation("psrun.dispatch"):
            return jitted(*_args(init_state(seed), cfg_run, schedule,
                                 faults))[0]

    def lower(state: PSState, cfg_run: ConsistencyConfig | None = None,
              schedule: ChurnSchedule | None = None,
              faults: wire.WireFaults | None = None):
        """``run_from``'s program for ``state``, lowered (``jax.stages``):
        ``.compile().as_text()`` names the stages and kernels it holds."""
        return jit_run.lower(*_args(state, cfg_run, schedule, faults))

    fn.init_state = init_state
    fn.run_from = run_from
    fn.lower = lower
    return fn


def _churn_key(schedule: ChurnSchedule | None):
    """The churn *structure* a compiled program is specialized on: presence,
    which optional arrays the schedule carries, and the in-flight policy.
    Array shapes/values stay jit-traced (jit retraces on new shapes)."""
    if schedule is None:
        return None
    return (schedule.drop_inflight,
            schedule.straggler_workers is not None,
            schedule.bw_scale is not None)


class PSRuntime:
    """Executable sharded PS: ``PSRuntime(mesh).run(app, cfg, n_clocks)``.

    Produces the same `core.ps.Trace` schema as ``core.ps.simulate`` (the
    *Trace-producer contract*: identical fields, leading clock axis, same
    RNG stream), executed over the mesh instead of vectorized on one
    device.  Compiled programs are cached per (app, config family, ring
    window, n_clocks, churn structure) — numeric knob changes (and
    same-structure churn schedules) re-use them.

    ``init_state`` / ``run_from`` expose the mid-run `PSState` for
    checkpointing: ``run_from`` resumed from a saved state reproduces the
    uninterrupted trace bit for bit — with or without a churn schedule
    (schedules index by absolute clock, so segments line up exactly; see
    `pods.elastic` for the pod-rejoin recipe built on this).
    """

    worker_axes: tuple = ("data",)

    def __init__(self, mesh=None):
        self.mesh = self._default_mesh() if mesh is None else mesh
        self._cache: dict = {}

    def _default_mesh(self):
        return make_ps_mesh()

    def run_fn(self, app: PSApp, cfg: ConsistencyConfig, n_clocks: int,
               record_views: bool = False,
               schedule: ChurnSchedule | None = None,
               obs: obsm.ObsSpec | None = None,
               faults: wire.WireFaults | None = None):
        """The cached jitted ``fn(seed, cfg) -> Trace`` for this family."""
        obs = obs if obsm.obs_on(obs) else None   # one cache entry for off
        key = (id(app), cfg.family, cfg.effective_window, n_clocks,
               record_views, _churn_key(schedule), obs,
               wire.faults_key(faults))
        fn = self._cache.get(key)
        if fn is None:
            fn = make_run_fn(app, cfg, n_clocks, mesh=self.mesh,
                             record_views=record_views,
                             worker_axes=self.worker_axes,
                             schedule=schedule, obs=obs, faults=faults)
            self._cache[key] = fn
        return fn

    def run(self, app: PSApp, cfg: ConsistencyConfig, n_clocks: int,
            seed=0, record_views: bool = False,
            schedule: ChurnSchedule | None = None,
            obs: obsm.ObsSpec | None = None,
            faults: wire.WireFaults | None = None) -> Trace:
        """Run ``n_clocks`` of the app under ``cfg`` on the mesh."""
        return self.run_fn(app, cfg, n_clocks, record_views,
                           schedule, obs, faults)(seed, cfg, schedule,
                                                  faults)

    def init_state(self, app: PSApp, cfg: ConsistencyConfig, seed=0,
                   n_clocks: int = 1,
                   faults: wire.WireFaults | None = None) -> PSState:
        """Clock-0 `PSState` (``n_clocks`` only selects the compiled fn)."""
        return self.run_fn(app, cfg, n_clocks,
                           faults=faults).init_state(seed)

    def run_from(self, app: PSApp, cfg: ConsistencyConfig, n_clocks: int,
                 state: PSState, record_views: bool = False,
                 schedule: ChurnSchedule | None = None,
                 obs: obsm.ObsSpec | None = None,
                 faults: wire.WireFaults | None = None):
        """Advance ``state`` by ``n_clocks`` -> ``(Trace, PSState)``."""
        return self.run_fn(app, cfg, n_clocks, record_views,
                           schedule, obs, faults).run_from(
                               state, cfg, schedule, faults)
