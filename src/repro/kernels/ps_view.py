"""Pallas TPU kernels for the PS simulator's per-clock hot path.

Two kernels back `core/ps.py` (dispatched via `ops.ring_view` /
`ops.vap_suffix_norms`; the pure-jnp contracts live in `ref.py`):

1. ``ring_view`` — masked ring-buffer view materialization.  The reader
   views ``view[r] = base + Σ_{w,q visible} uring[w,q]`` are a [P, W·P]
   visibility mask times the [W·P, d] update ring.  Rather than
   materializing the mask @ ring matmul with a broadcast (what XLA does for
   the reference einsum), the kernel streams d-blocks of the ring through
   VMEM once and accumulates one small [P,P] × [P, block_d] MXU matmul per
   ring slot, with the visibility mask computed in-register from the slot
   clock and the per-channel ``cview`` clocks.

2. ``vap_suffix_norms`` — per-producer inf-norms of the suffix aggregates of
   the newest k clocks (k = 0..W), the quantity the paper's VAP model
   bounds by ``v_t``.  Replaces a Python-unrolled O(W²) chain of einsums
   over the full [W,P,d] ring with a single pass per d-block: a running
   suffix accumulator in VMEM and a max-reduction into the [W+1, P] output,
   accumulated across d-blocks via output revisiting (constant index map,
   innermost grid dim — the TPU-legal accumulation pattern, cf. mf_sgd.py).

Both kernels keep the last axis blocked at a multiple of 128 lanes; the
sublane axis is the worker count P (small: 4–16), which Mosaic pads.  W is
a small static ring window (≤ ~16), so per-slot loops are unrolled.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .ref import RING_INVALID


def supported(uring, block_d: int = 128) -> bool:
    W, P, d = uring.shape
    return d % block_d == 0 and P <= 128 and W <= 64


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


def ring_view(base, uring, uclock, cview, *, block_d: int = 128,
              interpret: bool = False):
    """Contract identical to `ref.ring_view`: ``cview`` is [R, P] for R
    readers (a worker shard's rows, R <= P) of P producers."""
    W, P, d = uring.shape
    R = cview.shape[0]
    block_d = min(block_d, d)
    assert d % block_d == 0
    return pl.pallas_call(
        _ring_view_kernel,
        grid=(d // block_d,),
        in_specs=[
            pl.BlockSpec((W, 1), lambda i: (0, 0)),           # uclock
            pl.BlockSpec((R, P), lambda i: (0, 0)),           # cview
            pl.BlockSpec((1, block_d), lambda i: (0, i)),     # base
            pl.BlockSpec((W, P, block_d), lambda i: (0, 0, i)),
        ],
        out_specs=pl.BlockSpec((R, block_d), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((R, d), jnp.float32),
        interpret=interpret,
    )(uclock.reshape(W, 1), cview, base.reshape(1, d),
      uring.astype(jnp.float32))


def _suffix_norms_kernel(uclock_ref, c_ref, uring_ref, out_ref):
    i = pl.program_id(0)
    W, P, block_d = uring_ref.shape
    c = c_ref[0, 0]

    @pl.when(i == 0)
    def _init():                                             # norms are >= 0
        out_ref[...] = jnp.zeros_like(out_ref)

    suffix = jnp.zeros((P, block_d), jnp.float32)
    for k in range(1, W + 1):                                # static unroll
        for w in range(W):
            sel = uclock_ref[w, 0] == c - k                  # scalar
            suffix = suffix + jnp.where(sel, uring_ref[w], 0.0)
        norm_k = jnp.max(jnp.abs(suffix), axis=-1)           # [P]
        out_ref[k, :] = jnp.maximum(out_ref[k, :], norm_k)


def vap_suffix_norms(uring, uclock, c, *, block_d: int = 128,
                     interpret: bool = False):
    """Contract identical to `ref.vap_suffix_norms`."""
    W, P, d = uring.shape
    block_d = min(block_d, d)
    assert d % block_d == 0
    return pl.pallas_call(
        _suffix_norms_kernel,
        grid=(d // block_d,),
        in_specs=[
            pl.BlockSpec((W, 1), lambda i: (0, 0)),           # uclock
            pl.BlockSpec((1, 1), lambda i: (0, 0)),           # clock c
            pl.BlockSpec((W, P, block_d), lambda i: (0, 0, i)),
        ],
        out_specs=pl.BlockSpec((W + 1, P), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((W + 1, P), jnp.float32),
        interpret=interpret,
    )(uclock.reshape(W, 1), jnp.asarray(c, jnp.int32).reshape(1, 1),
      uring.astype(jnp.float32))
