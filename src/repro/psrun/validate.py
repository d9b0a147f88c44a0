"""Cross-validation of the executable runtime against the simulator oracle.

Levels of contract, matched to what each consistency model promises:

- **bsp** — the network model is deterministic (full barrier), so a seeded
  run must be *bit-identical* to ``core.ps.simulate``: every `Trace` field,
  every float.
- **ssp / essp** — *also bit-asserted* (promoted from "holds in practice"
  in PR 4): the runtime replays the simulator's RNG stream through the
  shared synthetic delay model, so every float must match, and the
  bounded-staleness invariant must hold — at read time every channel
  satisfies ``-(s_eff+1) <= cview[r,q] - c <= -1`` where ``s_eff`` is the
  per-channel (two-tier, when ``cfg.n_pods > 1``) bound.
- **vap** — the value-bound condition of paper eq. 1, via
  ``core.valuebound.check_condition``, with integer decisions
  (staleness/forced/delivered) exactly equal to the oracle and floats
  within a strict ulp budget (``trace_max_ulp``).

Bit-identity caveats (pinned by ``tests/test_psrun.py`` /
``tests/test_sweep.py``): it holds whenever each worker shard carries >1
worker (a batch-of-1 vmapped worker step can compile to different fused
arithmetic than the oracle's batch-of-P — 1 ulp; the mesh factories keep
the >1 regime).  VAP floats can drift a few ulp/value under *multi-device*
compilation: XLA's backend instruction-selects the scan body differently
when the enforcement graph is present (measured: a replay of the worker
update on bit-identical recorded inputs reproduces the plain-jit value,
and optimization barriers around every stage leave the drift
byte-identical — backend codegen, not semantic divergence; MF/LDA are
exactly stable, and decisions are always exact).
"""
from __future__ import annotations

import numpy as np

from ..core import valuebound
from ..core.consistency import ConsistencyConfig
from ..core.delays import staleness_bound_matrix
from ..core.ps import PSApp, Trace, simulate
from .runtime import PSRuntime

TRACE_FIELDS = ("loss_ref", "loss_view", "staleness", "forced", "delivered",
                "u_l2", "intransit_inf", "ship_floats", "live", "x_final")
# the integer decisions: exact under every model and on every backend
INT_FIELDS = ("staleness", "forced", "delivered", "live")

# Float drift budget for VAP under multi-device compilation (see module
# doc), asserted in ulp units so it stays scale-free.  Measured drift on
# the contract tests compounds ~ulp/clock: <= 14 ulp over 40 flat clocks
# (P=4), <= 64 over 20 hierarchical clocks (P=8).  128 gives slack without
# ever admitting a semantic bug — the old rtol=1e-5/atol<1e-4 pins admitted
# thousands of ulp on the same traces (MF/LDA need none of this: they are
# bit-exact, asserted separately).
VAP_ULP_BUDGET = 128.0


def trace_max_diff(got: Trace, want: Trace) -> dict:
    """Max absolute difference per `Trace` field (0.0 everywhere == exact)."""
    out = {}
    for name in TRACE_FIELDS:
        a = np.asarray(getattr(got, name)).astype(np.float64)
        b = np.asarray(getattr(want, name)).astype(np.float64)
        out[name] = float(np.abs(a - b).max()) if a.size else 0.0
    return out


def trace_max_ulp(got: Trace, want: Trace) -> dict:
    """Max drift per field, in float32 ulp *of the field's scale*.

    The scale-free version of :func:`trace_max_diff`: ``max|a-b| /
    spacing(max|want|)`` per field, so "a few ulp" means the same thing
    for a loss of 1e-3 and a loss of 1e3.  Measured against the field's
    largest magnitude (not elementwise) because the drift is absolute
    round-off accumulated while values were large — elementwise ulp would
    diverge spuriously as a converging field approaches zero.
    """
    out = {}
    for name in TRACE_FIELDS:
        a = np.asarray(getattr(got, name)).astype(np.float64)
        b = np.asarray(getattr(want, name)).astype(np.float64)
        if not a.size:
            out[name] = 0.0
            continue
        scale = np.float32(max(np.abs(b).max(), np.abs(a).max(), 1e-30))
        out[name] = float(np.abs(a - b).max() / np.spacing(scale))
    return out


def check_staleness_bound(trace: Trace, cfg: ConsistencyConfig,
                          retry_budget: int = 0) -> dict:
    """SSP/ESSP invariant: every read is at most ``s_eff+1`` clocks stale
    and never fresher than the barrier (``-1``).

    ``s_eff`` is per-channel: ``staleness`` intra-pod, ``staleness +
    s_xpod`` across pods (`core.delays.staleness_bound_matrix`) — the
    two-tier contract collapses to the flat one at ``n_pods=1``.
    ``retry_budget`` widens the cross-pod tier for lossy-wire runs whose
    fault trace is *conforming* (`comm.wire.WireFaults.retry_budget`);
    non-conforming traces (a shipment gave up) can exceed any finite
    bound and should not be asserted here.

    Under churn the contract is re-derived over the *live* set: a dead
    worker runs no read, so its frozen rows are excluded via
    ``Trace.live``, and the bound is asserted for every read a live
    worker actually performs — including the rejoin read, which the
    enforcement step repairs with a forced burst before the worker
    computes.  ``live_frac`` reports how much of the matrix the check
    covered (1.0 without churn).
    """
    st = np.asarray(trace.staleness)
    P = st.shape[-1]
    readers = np.arange(st.shape[-2])  # Pl reader rows (= P in the oracle)
    s_eff = np.asarray(staleness_bound_matrix(cfg, readers, P,
                                              retry_budget=retry_budget))
    live = np.asarray(trace.live) if trace.live is not None else None
    if live is not None and live.shape[-1] == st.shape[-2]:
        live_r = live[:, :, None]                   # mask dead reader rows
    else:  # hand-made traces without the field: check everything
        live_r = np.ones_like(st, dtype=bool)
    viol_old = int(((st < -(s_eff + 1)) & live_r).sum())
    viol_fresh = int(((st > -1) & live_r).sum())
    st_live = st[np.broadcast_to(live_r, st.shape)]
    return {"violations": viol_old + viol_fresh,
            "min": int(st_live.min()), "max": int(st_live.max()),
            "bound": -(int(np.max(s_eff)) + 1),
            "live_frac": float(np.broadcast_to(live_r, st.shape).mean())}


def cross_validate(app: PSApp, cfg: ConsistencyConfig, n_clocks: int,
                   runtime: PSRuntime | None = None, seed=0,
                   return_trace: bool = False, schedule=None,
                   faults=None) -> dict:
    """Run both engines and check the model-appropriate oracle contract.

    Returns a dict with ``ok`` plus the per-model evidence.  BSP/SSP/ESSP
    compare bit-for-bit against ``simulate`` (SSP/ESSP additionally check
    the (two-tier) staleness bound) and also report ``ints_exact`` (the
    `INT_FIELDS` decisions) and ``max_ulp`` per field, so a float drift
    can be told apart from a decision that differs; VAP checks the value
    bound, exact decisions, and the ulp drift budget.
    ``return_trace=True`` adds the runtime's `Trace` under ``"trace"`` so
    callers layering further checks (``pods.validate``) don't re-execute
    the run.  ``schedule`` (a
    `core.delays.ChurnSchedule`) runs *both* engines under the same fleet
    churn — the bit-identity contract covers the survivor set too.
    ``faults`` (a `comm.wire.WireFaults`) runs both engines over the same
    lossy wire; bit-identity is still asserted, but the staleness bound is
    *not* (an arbitrary fault mask may be non-conforming — give-ups void
    any finite bound; `tests/test_wire.py` asserts the widened bound on
    conforming schedules separately).
    """
    runtime = runtime or PSRuntime()
    tr = runtime.run(app, cfg, n_clocks, seed=seed, schedule=schedule,
                     faults=faults)
    out: dict = {"model": cfg.model}

    def _oracle():
        import dataclasses

        import jax
        # The app's data enter as arguments, as they do in the runtime.
        # Closed over, they become constants that XLA may fold, and the
        # TPU then reduces the loss in another order (LDA: one [P*ntok]
        # reduce instead of [P, ntok]) — a few ulp in the loss.
        return jax.jit(
            lambda sd, x0, local0: simulate(
                dataclasses.replace(app, x0=x0, local0=local0), cfg,
                n_clocks, seed=sd, schedule=schedule, faults=faults))(
            np.uint32(seed), app.x0, app.local0)

    if cfg.model in ("bsp", "ssp", "essp"):
        want = _oracle()
        diffs = trace_max_diff(tr, want)
        out["max_diff"] = diffs
        out["max_ulp"] = trace_max_ulp(tr, want)
        out["ints_exact"] = all(
            np.array_equal(np.asarray(getattr(tr, name)),
                           np.asarray(getattr(want, name)))
            for name in INT_FIELDS)
        out["ok"] = all(v == 0.0 for v in diffs.values())
        if cfg.model in ("ssp", "essp") and faults is None:
            chk = check_staleness_bound(tr, cfg)
            out.update(chk)
            out["ok"] = out["ok"] and chk["violations"] == 0
    elif cfg.model == "vap":
        chk = valuebound.check_condition(tr, float(cfg.v0))
        out.update(chk)
        want = _oracle()
        decisions_ok = all(
            np.array_equal(np.asarray(getattr(tr, name)),
                           np.asarray(getattr(want, name)))
            for name in ("staleness", "forced", "delivered"))
        ulps = trace_max_ulp(tr, want)
        out["decisions_exact"] = decisions_ok
        out["max_ulp"] = ulps
        out["ok"] = (chk["violations"] == 0 and decisions_ok
                     and max(ulps.values()) <= VAP_ULP_BUDGET)
    else:  # async has no bound to check; just require finite traces
        out["ok"] = bool(np.isfinite(np.asarray(tr.loss_ref)).all())
    if return_trace:
        out["trace"] = tr
    return out
