"""Vectorized parameter-server simulator (the paper's ESSPTable, in JAX).

The simulator reproduces the *semantics* of SSPTable/ESSPTable — per-row
cache clocks, lazy-vs-eager delivery, bounded staleness, value bounds — in a
single deterministic ``lax.scan`` over clocks, with all ``P`` workers
vectorized via ``vmap``.  This is what lets us measure the paper's claims
(staleness distributions, convergence per clock, robustness, variance) with
full control over the network-delay model and with exact repeatability.

Mechanics
---------
The global model is a flat vector ``x ∈ R^d`` (apps pack/unpack their own
structure).  Updates are additive (``x ← x + u``), matching the paper's INC
semantics; they are kept in a ring buffer of the last ``W`` clocks, and the
visibility of producer ``q``'s updates to reader ``r`` is tracked by a
per-channel clock matrix ``cview[r, q]`` — the generalization of ESSPTable's
per-row ``c_param``.  The reader's view is::

    view[r] = base + Σ_{q, c' ≤ cview[r,q]} u[q, c']

where ``base`` holds all updates old enough to be visible to everyone
(folded out of the ring).  A consistency model is exactly a policy for
advancing ``cview`` (see ``consistency.py``).

Delivery model: at the end of each clock, every (reader, producer) channel
independently delivers the fresh update with probability ``push_prob``
(unless the channel is "congested" that clock, probability
``straggler_prob``), giving geometric delivery delays with a heavy-tail knob
— the simulator analogue of the paper's 1 GbE cluster network.  SSP ignores
these pushes (SSPTable is pull-based): its caches refresh only when a read
would violate the staleness bound.  ESSP applies them eagerly.

Hierarchical (multi-pod) mode
-----------------------------
With ``cfg.n_pods > 1`` the ``P`` workers are partitioned into contiguous
pod blocks and every channel is classified intra-pod or cross-pod
(``core.delays.same_pod_mask``).  Each pod conceptually holds a full
*replica* of the parameter shards: a reader's view of an intra-pod producer
is governed exactly as before, while cross-pod visibility rides the
*reconciliation channel* of the second network tier —

- **delivery** is two-tier: cross-pod pushes land with probability scaled
  by ``t_net_intra / t_net_xpod`` (``core.delays.channel_push_prob``).
  ESSP/async/VAP reconcile *eagerly* (pushes cross the pod boundary every
  clock as they do intra-pod, only slower); BSP/SSP reconcile *clock-gated*
  (BSP's barrier syncs everything; SSP pulls a cross-pod channel only when
  its bound trips);
- **enforcement** is two-tier: SSP/ESSP force a blocking refresh at
  staleness ``s`` intra-pod and ``s + s_xpod`` cross-pod, so per-channel
  lag is bounded by ``s_intra + s_xpod`` (the bounded-async invariant of
  Wei et al., arXiv:1312.7869), and replica divergence — how far two pods'
  visible prefixes of one producer can drift apart — by the same bound
  (see ``repro.pods.reconcile``).

``n_pods=1`` (the default) is bit-identical to the flat simulator, and BSP
traces are bit-identical across *any* pod count (the barrier drains both
tiers every clock).  The executable counterpart is ``repro.pods``
(``PodsRuntime`` on a 3-D ``("pod","data","model")`` mesh), cross-validated
against this mode exactly like ``repro.psrun`` is against the flat mode.

With ``cfg.comm_active`` (the comm substrate, `repro.comm`) the cross-pod
wire stops being free: each producer accumulates raw updates and ships one
aggregated, top-k-sparsified, quantized delta every ``agg_clocks`` clocks
(error-feedback residual re-ships dropped mass); cross-pod readers
materialize their views from the shipped *wire ring* while intra-pod
readers keep reading raw; cross-pod visibility advances only to shipment
boundaries (bound widened to ``s + s_xpod + agg_clocks - 1``); and
``Trace.ship_floats`` records the bits-weighted floats each shipment put
on the wire.  The substrate is off by default — the dense path is
byte-identical to the pre-substrate simulator — and covered by the same
oracle contract (ssp/essp/async; bsp's barrier and vap's synchronous value
bound don't route through it).

Everything (drift of staleness, forced synchronous fetches, update
magnitudes, losses, per-worker views) is recorded per clock into a `Trace`.

Hot path & sweeps
-----------------
The per-clock view materialization and the VAP suffix-aggregate norms go
through ``kernels.ops`` (pure-jnp reference on CPU, Pallas kernels on
TPU/interpret — see ``kernels/ps_view.py``).  The numeric knobs of
``ConsistencyConfig`` (staleness, push_prob, v0, straggler_*) are consumed
as *values*, never as Python control flow, so they may be traced arrays:
``core.sweep`` vmaps ``simulate`` over an entire config grid × seed batch in
one compiled program.  Only ``cfg.model``/``read_my_writes`` and the ring
window select code structure and must be concrete.

The Trace-producer contract
---------------------------
Two engines produce `Trace`s and must stay interchangeable to every
consumer (``core.staleness``, ``core.theory``, ``core.valuebound``,
``core.timemodel``, the benchmarks):

- ``simulate`` (this module) — the vectorized single-program *oracle*,
  covering both the flat (``n_pods=1``) and hierarchical (``n_pods>1``)
  modes;
- ``repro.psrun.PSRuntime`` — the executable runtime, which runs the same
  clock step sharded over a ``("data","model")`` device mesh;
- ``repro.pods.PodsRuntime`` — the hierarchical runtime on a 3-D
  ``("pod","data","model")`` mesh (replicated parameter shards per pod,
  cross-pod reconciliation), sharing the clock-step machinery with psrun.

All fill every `Trace` field with the clock axis leading, derive all
randomness from the same key stream (``split(rng, 3)`` per clock; worker
keys ``split(k_upd, P)``; delivery from ``k_net``), and keep identical
per-coordinate reduction orders — which is why a seeded BSP run is
bit-identical between them, and SSP/ESSP runs are too (asserted by
``psrun.validate.cross_validate`` since the bit-match was promoted into
the contract; VAP agrees to fusion tolerance with exactly-equal
decisions).  Anything that changes a `Trace` field, the key derivation, or
a reduction order here must be mirrored in ``psrun/runtime.py`` —
`tests/test_psrun.py` and `tests/test_pods.py` enforce the contract.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp

from ..comm import substrate as comm
from ..comm import wire
from ..kernels import ops
from ..kernels.ref import RING_EMPTY, RING_INVALID
from ..obs import metrics as obsm
from .consistency import ConsistencyConfig
from .delays import ChurnSchedule, churn_live, churn_rates, \
    delivery_matrix, pod_of, same_pod_mask, staleness_bound_matrix


@dataclass
class PSApp:
    """An ML application running against the simulated parameter server.

    Attributes:
      name: identifier.
      dim: size of the flat parameter vector.
      n_workers: number of PS workers ``P``.
      x0: initial parameters, shape ``[dim]``.
      local0: worker-local state pytree; every leaf has leading axis ``P``
        (data partitions, Gibbs assignments, doc-topic counts, ...).
      worker_update: ``(view[d], local, worker_id, clock, rng) -> (u[d],
        local')`` — one clock of work for one worker, vmapped by the
        simulator.  ``u`` is the additive update sent to the server.
      loss: ``(x[d], locals) -> scalar`` global training objective, where
        ``locals`` is the stacked worker-local state.
      loss_sum: ``(x[d], locals, worker_ids) -> (sums[B], count)``,
        optional: the objective summed over each of a block of ``B``
        workers' data (``locals`` leaves lead with the block's workers,
        ``worker_ids`` their ids), each sum of ``count`` terms (a Python
        int, the same for every worker), so that ``loss`` is every
        worker's sum added in worker order (`fold_workers`) over ``count *
        n_workers``.  The runtimes evaluate it on each worker shard's own
        data, put the sums in their workers' slots of a ``[P]`` vector,
        add the shards' vectors with one ``psum`` (exact: one shard fills
        each slot) and fold it as ``loss`` does, so the objective's order
        of additions is the same on every mesh.  Without it the runtimes
        gather every worker's locals and call ``loss``.
    """

    name: str
    dim: int
    n_workers: int
    x0: jax.Array
    local0: Any
    worker_update: Callable
    loss: Callable
    loss_sum: Callable | None = None


def fold_workers(sums) -> jax.Array:
    """``[P]`` per-worker sums added in worker order, a left fold: the one
    order every engine uses, whatever a mesh's reduction would do."""
    total = sums[0]
    for p in range(1, sums.shape[0]):
        total = total + sums[p]
    return total


@jax.tree_util.register_dataclass
@dataclass(frozen=True)
class Trace:
    """Per-clock traces from a simulation (leading axis = clock)."""

    loss_ref: jax.Array        # [T] loss of the reference sequence x_t
    loss_view: jax.Array       # [T] loss of worker 0's (stale) view
    staleness: jax.Array       # [T, P, P] clock differential cview[r,q] - c
    forced: jax.Array          # [T, P, P] synchronous (blocking) fetches
    delivered: jax.Array       # [T, P, P] background deliveries this clock
    u_l2: jax.Array            # [T, P] l2 norm of each worker's update
    intransit_inf: jax.Array   # [T] max inf-norm of in-transit aggregates
    ship_floats: jax.Array     # [T, P] bits-weighted floats each producer
    #                            put on the cross-pod wire this clock
    #                            (comm substrate: quantized values + sparse
    #                            indices at shipment clocks, 0 otherwise;
    #                            dense path: d for push models, 0 for
    #                            pull-based ssp) — see repro.comm
    live: jax.Array            # [T, P] worker liveness per clock (all True
    #                            without a ChurnSchedule): dead workers
    #                            push nothing, their reader rows freeze —
    #                            consumers must re-derive staleness claims
    #                            over the live set (psrun.validate)
    views0: jax.Array | None   # [T, d] worker-0 views (if record_views)
    x_final: jax.Array         # [d] final reference parameters
    locals_final: Any          # final worker-local state
    obs: Any = None            # telemetry accumulators (repro.obs) when the
    #                            run collected them (obs=ObsSpec()); None —
    #                            an empty pytree — otherwise, so traces
    #                            stack/compare exactly as before


def _delivery(rng, cfg: ConsistencyConfig, P: int, rates=None):
    """Sample the end-of-clock delivery matrix (see core/delays.py)."""
    return delivery_matrix(rng, cfg, P, rates)


def enforce_vap(cfg: ConsistencyConfig, c, cview, norms, W: int):
    """Force delivery of oldest in-transit updates so that the per-producer
    aggregated in-transit update satisfies ``||.||_inf <= v_t`` (paper
    eq. 1, v_t = v0/sqrt(t+1)).

    ``norms[k, q]`` is the inf-norm of the suffix aggregate of producer q's
    newest ``k`` clocks (kernels/ps_view.py); we keep in transit the
    largest suffix that satisfies the bound and force-deliver the rest.
    ``cview`` may be the full [P, P] matrix (simulator) or the shard-local
    reader rows [Pl, P] (the runtimes) — the same math serves both engines.
    """
    v_t = cfg.v0 / jnp.sqrt(c.astype(jnp.float32) + 1.0)
    ok = norms <= v_t                                  # [W+1, P]
    ok = ok.at[0].set(True)                            # empty suffix always ok
    # Per (reader, producer) channel: keep the *longest* suffix k that
    # (a) satisfies the bound and (b) does not exceed the channel's
    # current in-transit length (we can only deliver, never undeliver).
    kcur = jnp.clip(c - 1 - cview, 0, W)               # [r, q] suffix length now
    ks = jnp.arange(W + 1, dtype=jnp.int32)[:, None, None]
    cond = ok[:, None, :] & (ks <= kcur[None, :, :])   # [W+1, r, q]
    kbest = jnp.max(jnp.where(cond, ks, -1), axis=0)   # [r, q]
    required = c - 1 - kbest
    forced = cview < required
    return jnp.maximum(cview, required), forced


def simulate(app: PSApp, cfg: ConsistencyConfig, n_clocks: int,
             seed=0, record_views: bool = False,
             schedule: ChurnSchedule | None = None,
             obs: obsm.ObsSpec | None = None,
             faults: wire.WireFaults | None = None) -> Trace:
    """Run ``n_clocks`` of the app under the given consistency model.

    ``schedule`` (a `core.delays.ChurnSchedule`) makes the fleet churn:
    dead workers run no update (their pushes are zeroed before entering
    the ring, their worker-local state and reader rows of ``cview``
    freeze, and — under the comm substrate — they ship nothing), while the
    RNG stream, delivery sampling, and every survivor channel stay exactly
    the no-churn stream: survivors' floats are bit-identical between a
    schedule and its all-live restriction wherever no dead content flows.
    A rejoining worker trips the SSP/ESSP bound on its first read and
    catches up through one forced refresh burst, so the (re-derived)
    staleness contract over *live* readers holds unconditionally.

    ``obs`` (a `repro.obs.ObsSpec`, static) threads a telemetry
    accumulator pytree through the scan carry — pure arithmetic on values
    the step already computes, folded on device and returned as
    ``Trace.obs``.  ``None`` (the default) compiles the exact pre-obs
    program: every other `Trace` field is bit-identical either way.

    ``faults`` (a `repro.comm.wire.WireFaults`, comm substrate only)
    makes the cross-pod wire lossy: shipments drop/duplicate/delay per
    the seeded masks and the substrate answers with the stop-and-wait
    ack/retransmit protocol of `comm.wire` — sequence-guarded
    dedup-on-fold, exponential backoff (retries re-charged into
    ``Trace.ship_floats``), give-up mass self-healing through the
    error-feedback residual, and cross-pod visibility capped by what has
    actually *arrived* (``wire_tip``).  The staleness contract widens by
    ``faults.retry_budget`` clocks.  A neutral schedule
    (`wire.no_faults`) is bit-identical to ``faults=None``.
    """
    P, d = app.n_workers, app.dim
    W = cfg.effective_window
    f32 = jnp.float32
    churned = schedule is not None
    if churned and schedule.live.shape[1] != P:
        raise ValueError(f"schedule has {schedule.live.shape[1]} workers, "
                         f"app has {P}")
    # Static: route cross-pod shipment through the comm substrate
    # (k-clock aggregation + sparse/quantized wire with error feedback —
    # see repro.comm).  Off (the default) is byte-identical to the
    # pre-substrate simulator.
    wired = cfg.comm_active
    G = cfg.n_pods
    obs_enabled = obsm.obs_on(obs)
    faulted = faults is not None
    if faulted:
        wire.validate_faults(faults, cfg, P, W)

    base0 = app.x0.astype(f32)
    uring0 = jnp.zeros((W, P, d), f32)
    uclock0 = jnp.full((W,), RING_EMPTY, jnp.int32)   # slot -> clock stored
    cview0 = jnp.full((P, P), -1, jnp.int32)      # everyone saw "clock -1"
    rng0 = jax.random.PRNGKey(seed)
    # Two-tier staleness bound (hierarchical mode): `s` on intra-pod
    # channels, `s + s_xpod` across pods (+ `agg_clocks - 1` under the
    # substrate).  With n_pods=1 every channel is intra-pod and this is
    # exactly `s` (integer ops — bit-identical).  Under a lossy wire the
    # *trigger* deliberately stays at the unwidened bound: the refresh
    # target is capped on `wire_tip`, so firing eagerly is always safe
    # and keeps views as fresh as arrivals allow; only the *declared*
    # contract (events / validate / model checker) carries the
    # `+ retry_budget` widening for the lag an in-flight shipment can
    # still impose.
    s_eff = staleness_bound_matrix(cfg, jnp.arange(P), P)
    if wired:
        in_pod = same_pod_mask(P, G)                  # [P(r), P(q)]
        reader_pods = pod_of(P, G)                    # [P]
        zeros_d = jnp.zeros((d,), f32)
        comm0 = comm.init_state(W, P, d, G)
        if faulted:
            comm0 = {**comm0, **wire.init_wire_state(P, d)}
    if obs_enabled:
        # channel-tier mask for the forced-refresh split (all-True when
        # G == 1: every forced fetch is intra-pod)
        in_pod_obs = in_pod if wired else same_pod_mask(P, G)

    vmapped_update = jax.vmap(app.worker_update,
                              in_axes=(0, 0, 0, None, 0))
    worker_ids = jnp.arange(P, dtype=jnp.int32)

    def step(carry, c):
        if obs_enabled:
            *carry, oacc = carry
        if wired:
            (base, uring, uclock, cview, local, rng, cst) = carry
        else:
            base, uring, uclock, cview, local, rng = carry
        rng, k_upd, k_net = jax.random.split(rng, 3)

        if churned:
            live_now, died = churn_live(schedule, c)        # [P], [P]
            rates = churn_rates(cfg, schedule, P, c)
            if schedule.drop_inflight:
                # drop policy: a worker dying this clock takes its
                # in-flight (and, wired, unshipped) mass with it — the
                # reference sequence loses those updates too.
                keep = ~died
                uring = jnp.where(keep[None, :, None], uring, 0.0)
                if wired:
                    cst = dict(cst,
                               acc=jnp.where(keep[:, None], cst["acc"], 0.0),
                               res=jnp.where(keep[:, None], cst["res"], 0.0),
                               xring=jnp.where(keep[None, :, None],
                                               cst["xring"], 0.0))
                    if faulted:
                        # the dying producer's pending shipment and
                        # in-flight copy vanish with it too
                        cst = wire.drop_pending(cst, keep)
            cview_pre = cview
        else:
            rates = None

        # Per-producer suffix-aggregate inf-norms of the newest k clocks
        # (kernels/ps_view.py): drives both VAP enforcement and the
        # in-transit metric below.
        norms = ops.vap_suffix_norms(uring, uclock, c)      # [W+1, P]

        # --- 1. pre-read consistency enforcement (blocking fetches) -------
        if cfg.model == "bsp":
            forced = cview < (c - 1)
            cview = jnp.full_like(cview, c - 1)
        elif cfg.model in ("ssp", "essp"):
            # SSP condition: a read at clock c must include all updates of
            # clocks <= c - s_eff - 1 (s intra-pod, s + s_xpod cross-pod,
            # + agg_clocks - 1 under the comm substrate).  Lazy SSP
            # refreshes the whole channel from the server (which holds
            # everything through c-1) exactly when the bound trips — on a
            # cross-pod channel that is the clock-gated reconciliation
            # pull; ESSP rarely trips thanks to (two-tier) pushes.  Under
            # the substrate a cross-pod refresh can only fetch what has
            # *shipped* (through the last aggregation boundary).
            forced = cview < (c - s_eff - 1)
            if wired and faulted:
                # a faulted cross-pod refresh can only fetch what has
                # actually *arrived*: wire_tip caps the shipped boundary
                tgt = jnp.where(in_pod, c - 1,
                                jnp.minimum(
                                    comm.shipped_through(c, cfg.agg_clocks),
                                    cst["wire_tip"][None, :]))
                cview = jnp.where(forced, tgt, cview)
            elif wired:
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
            eye = jnp.eye(P, dtype=bool)
            cview = jnp.where(eye, c - 1, cview)

        if churned:
            # dead readers neither fetch nor advance: their cview rows
            # freeze at death, which is what trips the bound (one forced
            # burst) on their first read back — the catch-up mechanism.
            forced = forced & live_now[:, None]
            cview = jnp.where(live_now[:, None], cview, cview_pre)

        staleness = cview - c                               # [P, P]

        # VAP-condition metric: max over (reader, producer) channels of the
        # inf-norm of the aggregated in-transit updates at read time.  The
        # channel (r, q) has exactly the newest `c - 1 - cview[r,q]` clocks
        # of producer q in transit, so its norm is one gather from `norms`.
        kcur = jnp.clip(c - 1 - cview, 0, W)                # [P(r), P(q)]
        intransit_inf = jnp.max(norms[kcur, jnp.arange(P)[None, :]])

        # --- 2. materialize views ----------------------------------------
        # visibility mask x update ring -> per-reader views (Pallas on TPU).
        # NOTE on the VAP few-ulp drift PR 3 pinned: under a *multi-device*
        # compilation (sharded sweep, the runtimes) XLA's CPU backend
        # instruction-selects the scan body differently when the VAP
        # enforcement graph is present — a replay of the worker update on
        # bit-identical recorded inputs reproduces the plain-jit value, not
        # the sharded one, and optimization barriers around every stage
        # leave the drift byte-identical, so it is backend codegen
        # (FMA/vectorization of the loop body), not fusion across stages or
        # semantic divergence.  Decisions stay exact; float drift is
        # bounded to a few ulp/value and is app-dependent (MF/LDA are
        # exactly stable).  `tests/test_sweep.py` pins it to a strict ulp
        # budget and asserts MF bit-identity.
        if wired:
            # Split the view per channel tier: intra-pod producers read
            # raw, cross-pod producers read the shipped (compressed) wire
            # ring; the folded base is assembled per reader pod
            # (comm.reader_base).  Masked-out channels see nothing
            # (cview pinned below every stored clock).
            cv_intra = jnp.where(in_pod, cview, RING_EMPTY)
            cv_xpod = jnp.where(in_pod, RING_EMPTY, cview)
            rb = comm.reader_base(base, cst["base_pod"], cst["xbase_pod"],
                                  reader_pods)
            views = (rb + ops.ring_view(zeros_d, uring, uclock, cv_intra)
                     + ops.ring_view(zeros_d, cst["xring"], uclock,
                                     cv_xpod))
        else:
            views = ops.ring_view(base, uring, uclock, cview)

        # --- 3. worker computation ----------------------------------------
        upd_keys = jax.random.split(k_upd, P)
        u, local_new = vmapped_update(views, local, worker_ids, c, upd_keys)
        u = u.astype(f32)
        if churned:
            # dead workers push nothing and their local state freezes;
            # the update still *runs* (vmap has no ragged lanes) but its
            # output is discarded, so survivor lanes are untouched.
            u = jnp.where(live_now[:, None], u, 0.0)
            local = jax.tree_util.tree_map(
                lambda new, old: jnp.where(
                    live_now.reshape((P,) + (1,) * (new.ndim - 1)),
                    new, old),
                local_new, local)
        else:
            local = local_new

        # --- 4. commit to server: fold oldest slot, write newest ----------
        slot = jnp.mod(c, W)
        old_valid = uclock[slot] > RING_INVALID
        if wired:
            # recycled slots fold per producer pod: raw into base_pod,
            # wire into xbase_pod (base itself stays x0 — reader bases are
            # assembled per pod in comm.reader_base).
            w_old = jnp.where(old_valid, 1.0, 0.0)
            cst = dict(cst,
                       base_pod=cst["base_pod"]
                       + w_old * comm.fold_pods(uring[slot], G),
                       xbase_pod=cst["xbase_pod"]
                       + w_old * comm.fold_pods(cst["xring"][slot], G))
        else:
            base = base + jnp.where(old_valid, 1.0, 0.0) * jnp.sum(uring[slot], axis=0)
        uring = uring.at[slot].set(u)
        uclock = uclock.at[slot].set(c)
        if wired:
            # --- 4b. comm substrate: accumulate, and ship on boundary ----
            acc = cst["acc"] + u
            delta = acc + cst["res"]                    # [P, d]
            thresh = comm.row_threshold(delta, cfg.topk_frac)
            scale = comm.quant_scale(delta, cfg.quant)
            wire_u, resid = ops.delta_pack(delta, thresh, scale, cfg.quant)
            nnz = comm.selected_count(delta, thresh)
            ship = comm.ship_now(c, cfg.agg_clocks)     # traced bool
            if churned:
                # dead producers hold their shipment: acc/res keep the
                # unshipped mass (drain policy) and release it at the
                # first boundary after rejoin — catching up through the
                # wire ring.
                ship = ship & live_now                  # [P]
            if faulted:
                # stop-and-wait ARQ: a busy producer (previous shipment
                # unacked) skips the boundary — acc keeps accumulating
                # and the skipped content rides the next shipment.
                ship = ship & wire.idle(cst)            # [P]
            ship_b = ship[:, None] if (churned or faulted) else ship
            wire_u = jnp.where(ship_b, wire_u, jnp.zeros_like(wire_u))
            floats = comm.wire_floats(nnz, d, cfg.quant)
            if faulted:
                # the recycled ring slot clears; shipments enter the
                # wire ring only when they *arrive*, via the
                # seq-guarded fold inside wire_step (which also runs
                # retransmits, give-up healing, and this clock's
                # instant arrivals, and charges every transmission —
                # retries included — into ship_floats).
                cst = dict(cst,
                           acc=jnp.where(ship_b, jnp.zeros_like(acc), acc),
                           res=jnp.where(ship_b, resid, cst["res"]),
                           xring=cst["xring"].at[slot].set(
                               jnp.zeros_like(wire_u)))
                cst, ship_floats = wire.wire_step(
                    cst, wire_u, floats, ship, c, faults,
                    live=live_now if churned else None)
            else:
                cst = dict(cst,
                           acc=jnp.where(ship_b, jnp.zeros_like(acc), acc),
                           res=jnp.where(ship_b, resid, cst["res"]),
                           xring=cst["xring"].at[slot].set(wire_u))
                ship_floats = jnp.where(
                    ship, floats, jnp.zeros((P,), f32))
        else:
            ship_floats = comm.dense_ship_floats(cfg.model, P, d)
            if churned:
                ship_floats = jnp.where(live_now, ship_floats, 0.0)

        # --- 5. end-of-clock delivery (affects reads at c+1) --------------
        if cfg.model == "bsp":
            delivered = jnp.ones((P, P), bool)
            if churned:
                # the barrier drains to live readers only; dead rows stay
                # frozen (and catch up through the barrier on rejoin)
                delivered = delivered & live_now[:, None]
                cview = jnp.where(live_now[:, None],
                                  jnp.full_like(cview, c), cview)
            else:
                cview = jnp.full_like(cview, c)
        elif cfg.model == "ssp":
            delivered = jnp.zeros((P, P), bool)   # pull-based: no pushes
        else:  # essp / async / vap: delay-driven eager delivery
            delivered = _delivery(k_net, cfg, P, rates)
            if churned:
                # pushes to dead readers are lost (their caches are gone);
                # the sampling itself is unmasked so survivor channels see
                # the identical RNG draws with or without churn.
                delivered = delivered & live_now[:, None]
            if wired and faulted:
                # a cross-pod delivery carries the latest *arrived*
                # shipment: the boundary target capped by wire_tip
                # (updated by this clock's arrivals in wire_step above)
                tgt = jnp.where(in_pod, c,
                                jnp.minimum(
                                    comm.shipped_end(c, cfg.agg_clocks),
                                    cst["wire_tip"][None, :]))
                cview = jnp.where(delivered, jnp.maximum(cview, tgt),
                                  cview)
            elif wired:
                # a cross-pod delivery carries the latest *shipment*, so
                # visibility advances only to the aggregation boundary
                # (== c when agg_clocks == 1).
                tgt = jnp.where(in_pod, c,
                                comm.shipped_end(c, cfg.agg_clocks))
                cview = jnp.where(delivered, jnp.maximum(cview, tgt),
                                  cview)
            else:
                cview = jnp.where(delivered, c, cview)

        # --- 6. record ------------------------------------------------------
        if wired:
            x_ref = (base + jnp.sum(cst["base_pod"], axis=0)) + jnp.sum(
                uring * (uclock[:, None, None] > RING_INVALID), axis=(0, 1))
        else:
            x_ref = base + jnp.sum(
                uring * (uclock[:, None, None] > RING_INVALID), axis=(0, 1))
        loss_ref = app.loss(x_ref, local)
        loss_view = app.loss(views[0], local)
        out = dict(loss_ref=loss_ref, loss_view=loss_view,
                   staleness=staleness, forced=forced, delivered=delivered,
                   u_l2=jnp.linalg.norm(u, axis=-1),
                   intransit_inf=intransit_inf, ship_floats=ship_floats,
                   live=live_now if churned else jnp.ones((P,), bool))
        if record_views:
            out["views0"] = views[0]
        if obs_enabled:
            # fold this clock's already-computed step values into the
            # accumulators — the only obs work inside the compiled step
            oacc = obsm.device_update(
                oacc, staleness=staleness, forced=forced,
                delivered=delivered, ship_floats=ship_floats,
                live=out["live"], live_rows=out["live"],
                in_pod=in_pod_obs)
        new_carry = ((base, uring, uclock, cview, local, rng, cst)
                     if wired else
                     (base, uring, uclock, cview, local, rng))
        if obs_enabled:
            new_carry = (*new_carry, oacc)
        return new_carry, out

    carry0 = ((base0, uring0, uclock0, cview0, app.local0, rng0, comm0)
              if wired else
              (base0, uring0, uclock0, cview0, app.local0, rng0))
    if obs_enabled:
        carry0 = (*carry0, obsm.device_init(P, obs.n_buckets))
    carryT, ys = jax.lax.scan(step, carry0,
                              jnp.arange(n_clocks, dtype=jnp.int32))
    base, uring, uclock, _, local = carryT[0], carryT[1], carryT[2], \
        carryT[3], carryT[4]
    if wired:
        cst = carryT[6]
        x_final = (base + jnp.sum(cst["base_pod"], axis=0)) + jnp.sum(
            uring * (uclock[:, None, None] > RING_INVALID), axis=(0, 1))
    else:
        x_final = base + jnp.sum(
            uring * (uclock[:, None, None] > RING_INVALID), axis=(0, 1))
    return Trace(
        loss_ref=ys["loss_ref"], loss_view=ys["loss_view"],
        staleness=ys["staleness"], forced=ys["forced"],
        delivered=ys["delivered"], u_l2=ys["u_l2"],
        intransit_inf=ys["intransit_inf"], ship_floats=ys["ship_floats"],
        live=ys["live"], views0=ys.get("views0"), x_final=x_final,
        locals_final=local, obs=carryT[-1] if obs_enabled else None)


def simulate_jit(app: PSApp, cfg: ConsistencyConfig, n_clocks: int,
                 seed=0, record_views: bool = False,
                 schedule: ChurnSchedule | None = None,
                 obs: obsm.ObsSpec | None = None,
                 faults: wire.WireFaults | None = None) -> Trace:
    """jit-compiled run; ``seed`` may be a traced int (vmap over seeds).

    The schedule's (and fault schedule's) arrays enter as jit arguments,
    so re-running with a different same-shape schedule reuses the
    compiled program (``None`` is an empty pytree — presence is part of
    the trace structure)."""
    fn = jax.jit(lambda sd, sch, flt: simulate(
        app, cfg, n_clocks, sd, record_views, schedule=sch, obs=obs,
        faults=flt))
    return fn(jnp.asarray(seed, jnp.uint32), schedule, faults)
