"""Pallas TPU kernels for the PS simulator's per-clock hot path.

Two kernels back `core/ps.py` (dispatched via `ops.ring_view` /
`ops.vap_suffix_norms`; the pure-jnp contracts live in `ref.py`):

1. ``ring_view`` — masked ring-buffer view materialization.  The reader
   views ``view[r] = base + Σ_{w,q visible} uring[w,q]`` are a [P, W·P]
   visibility mask times the [W·P, d] update ring.  Rather than
   materializing the mask @ ring matmul with a broadcast (what XLA does for
   the reference einsum), the kernel streams d-blocks of the ring through
   VMEM once and accumulates one small [R,P] × [P, block] MXU matmul per
   ring slot, with the visibility mask computed in-register from the slot
   clock and the per-channel ``cview`` clocks.

2. ``vap_suffix_norms`` — per-producer inf-norms of the suffix aggregates of
   the newest k clocks (k = 0..W), the quantity the paper's VAP model
   bounds by ``v_t``.  Replaces a Python-unrolled O(W²) chain of einsums
   over the full [W,P,d] ring with a single pass per d-block: a running
   suffix over 256-lane chunks of the block, kept in registers, and a
   max-reduction into the [W+1, P] output, accumulated across d-blocks via
   output revisiting (constant index map, innermost grid dim — the
   TPU-legal accumulation pattern, cf. mf_sgd.py).

Both kernels stream the ring in blocks of thousands of lanes
(`block_lanes`: as wide as a fixed VMEM budget allows for the shape), so
a call takes tens to hundreds of grid steps, not one per 128 lanes.  The
block need not divide ``d``: the grid is ``cdiv(d, block)`` and the last
block is ragged.  Its lanes past ``d`` are read as anything; ``ring_view``
leaves them out of the write-back, ``vap_suffix_norms`` masks them before
its max.  The sublane axis is the worker count P (small: 4–16), which
Mosaic pads.  W is a small static ring window (≤ ~16), so per-slot loops
are unrolled.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import RING_INVALID

LANE, SUBLANE = 128, 8
# Lanes `vap_suffix_norms` adds and max-es at a time, in registers: on a v5e
# 256 takes a third less time than 128 at the cells' shapes, and 512 or
# 1024 no less than 256 (PERF.md).
CHUNK = 256
# Bytes of VMEM for a call's double-buffered blocks.  On a v5e the calls at
# the cells' shapes gain 0.1 % in sum at twice it (sweep in PERF.md).
VMEM_BUDGET = 16 << 20


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def block_lanes(W: int, P: int, R: int, d: int) -> int:
    """Lanes a grid step streams: the largest multiple of 128 whose
    double-buffered f32 blocks fit `VMEM_BUDGET`, at most ``d`` rounded up
    to 128.  A lane holds the ring's ``W·P`` rows and, for ``R`` readers,
    the base row and the ``R`` view rows, each rounded up to 8 sublanes;
    ``R = 0`` is ``vap_suffix_norms``, which streams the ring alone."""
    rows = W * _round_up(P, SUBLANE)
    if R:
        rows += SUBLANE + _round_up(R, SUBLANE)
    fit = VMEM_BUDGET // (2 * 4 * rows) // LANE * LANE
    return min(max(fit, LANE), _round_up(d, LANE))


def supported(uring) -> bool:
    W, P, _ = uring.shape
    return P <= 128 and W <= 64


def _compiler_params():
    return pltpu.CompilerParams(dimension_semantics=("arbitrary",),
                                vmem_limit_bytes=2 * VMEM_BUDGET)


def _ring_view_kernel(uclock_ref, cview_ref, base_ref, uring_ref, out_ref):
    W = uring_ref.shape[0]
    cview = cview_ref[...]                                   # [R, P] int32
    acc = jnp.broadcast_to(base_ref[...], out_ref.shape).astype(jnp.float32)
    for w in range(W):                                       # static unroll
        uc = uclock_ref[w, 0]
        vis = (cview >= uc) & (uc > RING_INVALID)            # [R(r), P(q)]
        acc = acc + jnp.dot(vis.astype(jnp.float32), uring_ref[w],
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)
    out_ref[...] = acc


def ring_view(base, uring, uclock, cview, *, block_d: int | None = None,
              interpret: bool = False):
    """Contract identical to `ref.ring_view`: ``cview`` is [R, P] for R
    readers (a worker shard's rows, R <= P) of P producers.  ``block_d``
    (a multiple of 128) overrides `block_lanes`."""
    W, P, d = uring.shape
    R = cview.shape[0]
    block_d = block_d or block_lanes(W, P, R, d)
    return pl.pallas_call(
        _ring_view_kernel,
        grid=(pl.cdiv(d, block_d),),
        in_specs=[
            pl.BlockSpec((W, 1), lambda i: (0, 0)),           # uclock
            pl.BlockSpec((R, P), lambda i: (0, 0)),           # cview
            pl.BlockSpec((1, block_d), lambda i: (0, i)),     # base
            pl.BlockSpec((W, P, block_d), lambda i: (0, 0, i)),
        ],
        out_specs=pl.BlockSpec((R, block_d), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((R, d), jnp.float32),
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="ring_view",
    )(uclock.reshape(W, 1), cview, base.reshape(1, d),
      uring.astype(jnp.float32))


def _suffix_norms_kernel(uclock_ref, c_ref, uring_ref, out_ref, *, d):
    i = pl.program_id(0)
    W, P, block_d = uring_ref.shape
    c = c_ref[0, 0]
    sel = [[uclock_ref[w, 0] == c - k for w in range(W)]     # scalars
           for k in range(1, W + 1)]

    @pl.when(i == 0)
    def _init():                                             # norms are >= 0
        out_ref[...] = jnp.zeros_like(out_ref)

    def piece(start, width, peaks, tail=0):
        """The suffixes over lanes [start, start + width) of the block, their
        absolute values max-ed into ``peaks`` (one [P, width] per k); with a
        ``tail``, lanes from ``start + tail`` on lie past d and hold
        anything."""
        lanes = pl.ds(start, width)
        ring = [uring_ref[w, :, lanes] for w in range(W)]
        suffix = jnp.zeros((P, width), jnp.float32)
        out = []
        for k in range(W):                                   # static unroll
            for w in range(W):
                suffix = suffix + jnp.where(sel[k][w], ring[w], 0.0)
            mag = jnp.abs(suffix)
            if tail:
                inside = jax.lax.broadcasted_iota(jnp.int32, mag.shape, 1)
                mag = jnp.where(inside < tail, mag, 0.0)
            out.append(jnp.maximum(peaks[k], mag))
        return out

    def zeros(width):
        return [jnp.zeros((P, width), jnp.float32) for _ in range(W)]

    def block(lanes):
        """The block's first ``lanes`` lanes (those < d): `CHUNK`-lane
        pieces in a loop, then what is left in 128-lane pieces, the last
        ragged where d ends inside it."""
        full = lanes // CHUNK
        norms = [jnp.zeros((P,), jnp.float32)] * W
        if full:
            peaks = jax.lax.fori_loop(
                0, full,
                lambda j, p: piece(pl.multiple_of(j * CHUNK, CHUNK), CHUNK, p),
                zeros(CHUNK))
            norms = [jnp.max(p, axis=-1) for p in peaks]
        for start in range(full * CHUNK, lanes, LANE):
            tail = lanes - start
            edge = piece(start, LANE, zeros(LANE), tail if tail < LANE else 0)
            norms = [jnp.maximum(n, jnp.max(e, axis=-1))
                     for n, e in zip(norms, edge)]
        for k in range(W):
            out_ref[k + 1, :] = jnp.maximum(out_ref[k + 1, :], norms[k])

    n = pl.cdiv(d, block_d)
    last = d - (n - 1) * block_d
    if last == block_d:
        block(block_d)
        return

    @pl.when(i < n - 1)
    def _inner():
        block(block_d)

    @pl.when(i == n - 1)
    def _edge():
        block(last)


def vap_suffix_norms(uring, uclock, c, *, block_d: int | None = None,
                     interpret: bool = False):
    """Contract identical to `ref.vap_suffix_norms`.  ``block_d`` (a
    multiple of 128) overrides `block_lanes`."""
    W, P, d = uring.shape
    block_d = block_d or block_lanes(W, P, 0, d)
    return pl.pallas_call(
        functools.partial(_suffix_norms_kernel, d=d),
        grid=(pl.cdiv(d, block_d),),
        in_specs=[
            pl.BlockSpec((W, 1), lambda i: (0, 0)),           # uclock
            pl.BlockSpec((1, 1), lambda i: (0, 0)),           # clock c
            pl.BlockSpec((W, P, block_d), lambda i: (0, 0, i)),
        ],
        out_specs=pl.BlockSpec((W + 1, P), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((W + 1, P), jnp.float32),
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="vap_suffix_norms",
    )(uclock.reshape(W, 1), jnp.asarray(c, jnp.int32).reshape(1, 1),
      uring.astype(jnp.float32))
