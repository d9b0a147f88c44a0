"""The comparison that decides ``correct``.

Both sides give the same readings of the job's first clocks (the first
two segments, which run in set-up through the window's own compiled call):

- the consistency decisions per clock, ``staleness``, ``forced`` and
  ``delivered`` (integers: compared exactly);
- ``loss_ref`` (the table) and ``loss_view`` (worker 0's view) per clock;
- ``ring``, for each clock the ring holds after the first segment, the
  norm of each worker's update over each parameter leaf (L and R);
- ``intransit_inf``, the largest in-transit aggregate per clock (the
  suffix-norm kernel's output as the record uses it);
- ``change``, the norm of each parameter leaf's change over the clocks,
  taken from the state the next segment starts from.

Each number compared is one gap, worst case first:

- ``decisions``: integer entries that differ;
- ``loss``: largest relative gap of either loss over the clocks;
- ``grad``: the ``ring`` norms, by the worst (clock, worker, leaf): the
  gap between the two norms over the reference's norm of that entry or of
  the median entry, whichever is larger (a clock missing from the
  program's ring reads a norm of 0);
- ``change``: the same over the parameter leaves' change, leaving out a
  leaf whose reference gradient (the first clock's summed update) is under
  a thousandth of the median leaf's;
- ``intransit``: largest relative gap of ``intransit_inf``.

A non-finite reading of the program gives an infinite gap.
"""
from __future__ import annotations

import math

import numpy as np

INTS = ("staleness", "forced", "delivered")
NUMBERS = ("decisions", "loss", "grad", "change", "intransit")


def _rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    if not np.all(np.isfinite(a)):
        return math.inf
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-30)))


def _worst_leaf(prog, ref) -> float:
    """``|norm_prog - norm_ref| / max(norm_ref, median norm_ref)``, worst
    entry."""
    p = np.asarray(prog, np.float64).ravel()
    r = np.asarray(ref, np.float64).ravel()
    if not np.all(np.isfinite(p)):
        return math.inf
    return float(np.max(np.abs(p - r) / np.maximum(r, np.median(r))))


def gaps(prog: dict, ref: dict) -> dict:
    """Every number compared, program against reference."""
    decisions = sum(int(np.sum(np.asarray(prog[f]) != np.asarray(ref[f])))
                    for f in INTS)
    grad_leaf = ref["grad_leaf"]
    med = np.median(list(grad_leaf.values()))
    moved = [n for n, g in grad_leaf.items() if g >= 1e-3 * med]
    clocks = sorted(ref["ring"])
    return {
        "decisions": decisions,
        "loss": max(_rel(prog["loss_ref"], ref["loss_ref"]),
                    _rel(prog["loss_view"], ref["loss_view"])),
        "grad": _worst_leaf([prog["ring"][c] for c in clocks],
                            [ref["ring"][c] for c in clocks]),
        "change": _worst_leaf([prog["change"][n] for n in moved],
                              [ref["change"][n] for n in moved]),
        "intransit": _rel(prog["intransit_inf"], ref["intransit_inf"]),
    }


def judge(values: dict, limits: dict) -> tuple[bool, dict]:
    """``(correct, checks)``: each number that has a limit, beside it."""
    checks = {name: {"value": values[name], "limit": limits[name]}
              for name in NUMBERS if name in limits}
    ok = all(c["value"] <= c["limit"] for c in checks.values())
    return ok, checks
