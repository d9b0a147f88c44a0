"""Pallas TPU kernel for MF's full-data objective in dense-block form.

The objective is the squared error over every observed rating.  Where the
ratings are dense enough, it is cheaper to evaluate it over the whole
user x item grid than per rating:

    sse = Σ_(u,i) C[u,i] · (V[u,i] − (L R)[u,i])²

``C`` counts how often each (user, item) pair occurs among the ratings
(drawn with replacement, so a duplicate keeps its weight) and ``V`` holds
the rating where ``C > 0``.  No per-rating row of L or column of R is
gathered: each grid step (i, j)

  1. loads an L [block_n, K] and an R [K, block_m] tile into VMEM,
  2. forms the prediction tile on the MXU at HIGHEST precision (the f32
     contraction; Mosaic's default would be one bf16 pass),
  3. adds ``C·(V − pred)²`` into row-block i's [8, block_m] partial sums,
     accumulated over the innermost column axis j (the TPU-legal
     accumulation pattern, cf. mf_sgd.py).

The partial sums are added up outside the kernel.  Blocks need not divide
the grid: the last row and column blocks read past the arrays, and only
those edge blocks mask what they read by position.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

HIGHEST = jax.lax.Precision.HIGHEST
BLOCK_N, BLOCK_M = 4096, 512     # the fastest of a sweep on a v5e (PERF.md)


def supported(n: int, m_pad: int) -> bool:
    """Rows fold into sublane groups of 8; columns are whole 128-lane
    vregs."""
    return n % 8 == 0 and m_pad % 128 == 0


def _vmem_bytes(block_n: int, block_m: int, k: int) -> int:
    """Double-buffered tiles (V f32, C int8, R and L f32 padded to their
    tiling, the partial sums) plus room for three f32 tile temporaries."""
    kp8, kp128 = -(-k // 8) * 8, -(-k // 128) * 128
    tile = block_n * block_m
    buffers = 2 * (4 * tile + tile + 4 * kp8 * block_m
                   + 4 * block_n * kp128 + 4 * 8 * block_m)
    return buffers + 3 * 4 * tile + (4 << 20)


def _sse_kernel(L_ref, R_ref, V_ref, C_ref, out_ref, *, n, m, n_i, n_j):
    i, j = pl.program_id(0), pl.program_id(1)
    block_n, block_m = V_ref.shape

    @pl.when(j == 0)
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    pred = jnp.dot(L_ref[...], R_ref[...], precision=HIGHEST,
                   preferred_element_type=jnp.float32)
    err = V_ref[...] - pred
    term = C_ref[...].astype(jnp.float32) * (err * err)

    def add(t):
        out_ref[...] += jnp.sum(t.reshape(block_n // 8, 8, block_m), axis=0)

    ragged_rows, ragged_cols = n % block_n != 0, m % block_m != 0
    if not (ragged_rows or ragged_cols):
        add(term)
        return
    edge = ((i == n_i - 1) & ragged_rows) | ((j == n_j - 1) & ragged_cols)

    @pl.when(jnp.logical_not(edge))
    def _inner():
        add(term)

    @pl.when(edge)
    def _edge():
        rows = i * block_n + jax.lax.broadcasted_iota(jnp.int32, term.shape, 0)
        cols = j * block_m + jax.lax.broadcasted_iota(jnp.int32, term.shape, 1)
        add(jnp.where((rows < n) & (cols < m), term, 0.0))


def mf_sse(L, R, V, C, *, block_n: int = BLOCK_N, block_m: int = BLOCK_M,
           interpret: bool = False):
    """Contract identical to `ref.mf_sse`, for ``R`` already padded to
    ``V``'s width: ``L [n, k]``, ``R [k, m_pad]``, ``V [n, m_pad]`` f32,
    ``C [n, m_pad]`` int8.  Returns the f32 sum of squared errors."""
    n, k = L.shape
    m = V.shape[1]
    assert R.shape == (k, m) and V.shape == C.shape == (n, m)
    assert supported(n, m), (n, m)
    block_n, block_m = min(block_n, n), min(block_m, m)
    n_i, n_j = pl.cdiv(n, block_n), pl.cdiv(m, block_m)
    partial = pl.pallas_call(
        functools.partial(_sse_kernel, n=n, m=m, n_i=n_i, n_j=n_j),
        grid=(n_i, n_j),
        in_specs=[
            pl.BlockSpec((block_n, k), lambda i, j: (i, 0)),
            pl.BlockSpec((k, block_m), lambda i, j: (0, j)),
            pl.BlockSpec((block_n, block_m), lambda i, j: (i, j)),
            pl.BlockSpec((block_n, block_m), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((None, 8, block_m), lambda i, j: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_i, 8, block_m), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_bytes(block_n, block_m, k)),
        name="mf_sse",
        interpret=interpret,
    )(L, R, V, C)
    return jnp.sum(partial)
