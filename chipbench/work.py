"""Operations and bytes the algorithm needs, from the cell's shapes alone.

These count the work of the parameter server's protocol as it is specified
(dense additive updates of ``d`` floats, a ring of the last ``W`` clocks'
updates of ``P`` producers, a full view per reader), not what any one
implementation compiles to, so a faster implementation of the same
semantics is measured against the same numbers.  Float32 throughout.
"""
from __future__ import annotations

F32 = 4
I32 = 4


def ring_view(W: int, P: int, R: int, d: int) -> dict:
    """``R`` reader views over ``d`` coordinates: the ring ``[W, P, d]``
    read once, the base read, the ``[R, d]`` views written, and per ring
    slot the ``[R, P] x [P, d]`` multiply-accumulates of the mask."""
    return {"flops": 2 * W * R * P * d,
            "bytes": F32 * (W * P * d + d + R * d)}


def vap_suffix_norms(W: int, P: int, d: int) -> dict:
    """Suffix-aggregate inf-norms of ``P`` producers over ``W`` clocks: the
    ring read once; per element one add into the running suffix and one
    absolute-max."""
    return {"flops": 2 * W * P * d, "bytes": F32 * W * P * d}


def mf_clock(n: int, m: int, k: int, P: int, B: int, N: int, W: int,
             objectives: int = 2) -> dict:
    """One clock of matrix factorisation on the PS, for all ``P`` workers.

    - view: ``ring_view`` for all ``P`` readers;
    - update: each worker reads ``B`` ratings (row, column, value), gathers
      a row of L and a column of R (``k`` floats each) per rating, and
      writes its dense update of ``d`` floats into the ring;
    - fold: the oldest slot (``P x d``) is read and added into the base;
    - objectives: ``objectives`` evaluations of the mean squared error over
      all ``N`` ratings, each reading the ratings and the ``d`` parameters
      once (the factors fit on chip, so a rating's rows need not be read
      again per rating).
    """
    d = (n + m) * k
    view = ring_view(W, P, P, d)
    rating = 2 * I32 + F32
    flops = (view["flops"]
             + P * B * 8 * k                 # e = v - <L_i, R_j>; dL; dR
             + P * d                         # fold: P producers into base
             + objectives * N * (2 * k + 3))
    nbytes = (view["bytes"]
              + P * (B * rating + 2 * B * k * F32 + d * F32)
              + F32 * (P * d + 2 * d)
              + objectives * (N * rating + d * F32))
    return {"flops": flops, "bytes": nbytes}
