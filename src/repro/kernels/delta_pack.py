"""Pallas TPU kernel for the comm substrate's hot compaction path.

``delta_pack`` backs `repro.comm`'s per-shipment pack (dispatched via
`ops.delta_pack`; the pure-jnp contract lives in `ref.delta_pack`): one
VMEM pass per d-block does the masked top-k select/scatter, the value
quantization, and the error-feedback residual fold —

    mask     = |delta| >= thresh          (thresh = k-th largest |row|)
    wire     = Q(where(mask, delta, 0))
    residual = where(mask, delta - Q(delta), delta)

so the shipped delta and the held-back residual are produced together
without materializing the mask or a second pass over the rows.  The
per-row threshold/scale scalars ride in as [P, 1] blocks (computed
upstream by ``comm.substrate.row_threshold`` / ``quant_scale`` — a sort is
not kernel material), and ``quant`` is static: each format compiles its
own elementwise body.

The last axis is blocked at a multiple of 128 lanes, the sublane axis is the worker count P (small; Mosaic pads), and
the grid is 1-D over d-blocks.  Verified against the jnp reference under
``interpret=True`` by ``tests/test_comm.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def supported(delta, block_d: int = 128) -> bool:
    P, d = delta.shape
    return d % block_d == 0 and P <= 128


def _delta_pack_kernel(thresh_ref, scale_ref, delta_ref, wire_ref, res_ref,
                       *, quant: str):
    delta = delta_ref[...]                                 # [P, block_d]
    mask = jnp.abs(delta) >= thresh_ref[...]               # [P,1] broadcast
    if quant == "f32":
        q = delta
        res = jnp.where(mask, 0.0, delta)
    elif quant == "bf16":
        q = delta.astype(jnp.bfloat16).astype(jnp.float32)
        res = jnp.where(mask, delta - q, delta)
    else:  # int8
        s = scale_ref[...]                                 # [P, 1]
        q = jnp.clip(jnp.round(delta / s), -127.0, 127.0) * s
        res = jnp.where(mask, delta - q, delta)
    wire_ref[...] = jnp.where(mask, q, 0.0)
    res_ref[...] = res


def delta_pack(delta, thresh, scale, quant: str = "f32", *,
               block_d: int = 128, interpret: bool = False):
    """Contract identical to `ref.delta_pack`."""
    P, d = delta.shape
    block_d = min(block_d, d)
    assert d % block_d == 0
    kernel = functools.partial(_delta_pack_kernel, quant=quant)
    return pl.pallas_call(
        kernel,
        grid=(d // block_d,),
        in_specs=[
            pl.BlockSpec((P, 1), lambda i: (0, 0)),        # thresh
            pl.BlockSpec((P, 1), lambda i: (0, 0)),        # scale
            pl.BlockSpec((P, block_d), lambda i: (0, i)),  # delta
        ],
        out_specs=[
            pl.BlockSpec((P, block_d), lambda i: (0, i)),
            pl.BlockSpec((P, block_d), lambda i: (0, i)),
        ],
        out_shape=[jax.ShapeDtypeStruct((P, d), jnp.float32),
                   jax.ShapeDtypeStruct((P, d), jnp.float32)],
        interpret=interpret,
        name="delta_pack",
    )(thresh.reshape(P, 1).astype(jnp.float32),
      scale.reshape(P, 1).astype(jnp.float32),
      delta.astype(jnp.float32))
