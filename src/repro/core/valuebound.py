"""VAP value-bound schedules and condition checking.

The enforcement itself lives in `ps.enforce_vap`, called by the clock step's
shared `ps.enforce` (it needs the in-transit ring buffer); this module holds the schedule definitions and the post-hoc
verification used by tests/benchmarks (paper eq. 1 and Theorem 1's
`v_t = v0/sqrt(t)` requirement).
"""
from __future__ import annotations

import numpy as np

from .ps import Trace


def v_schedule(v0: float, kind: str = "inv_sqrt"):
    """Returns v_t as a function of the clock (0-indexed).

    - ``inv_sqrt``: the paper's v0/sqrt(t+1) (Theorem 1's decreasing bound);
    - ``constant``: fixed threshold (the [Li et al. 2013] style bound the
      paper criticizes — no convergence guarantee as updates shrink);
    - ``inv_t``: faster decay (stress case: forces ~full synchronization).
    """
    if kind == "inv_sqrt":
        return lambda t: v0 / np.sqrt(t + 1.0)
    if kind == "constant":
        return lambda t: v0
    if kind == "inv_t":
        return lambda t: v0 / (t + 1.0)
    raise ValueError(kind)


def check_condition(trace: Trace, v0: float, kind: str = "inv_sqrt",
                    tol: float = 1e-6) -> dict:
    """Verify ``intransit_inf[t] <= v_t`` over a simulation trace.

    The trace measures the aggregate at read time of clock c against the
    bound with t = c (the enforcement clock).
    """
    it = np.asarray(trace.intransit_inf)
    sched = v_schedule(v0, kind)
    vt = np.array([sched(t) for t in range(len(it))])
    # reads at clock c check in-transit accumulated through clock c-1
    viol = it[1:] > vt[:-1] + tol
    return {
        "violations": int(viol.sum()),
        "violation_frac": float(viol.mean()) if len(viol) else 0.0,
        "max_intransit": float(it.max()),
        "bound_final": float(vt[-1]),
    }


def sync_cost(trace: Trace) -> dict:
    """Forced synchronous deliveries — the paper's impracticality metric."""
    forced = np.asarray(trace.forced)
    T, P, _ = forced.shape
    per_clock = forced.sum(axis=(1, 2))
    return {
        "forced_total": int(forced.sum()),
        "forced_per_clock": float(per_clock.mean()),
        "full_sync_fraction": float(
            (per_clock >= P * (P - 1) * 0.9).mean()),
    }
