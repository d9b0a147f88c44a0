"""Pallas TPU flash attention (blocked, online softmax, GQA-aware).

TPU adaptation notes (DESIGN.md §Hardware adaptation):
- Q/K tiles sized to MXU multiples (block_q x block_k default 128x128); the
  kv stream is the innermost grid dimension so the Q tile and the running
  softmax state stay resident in VMEM across the online-softmax update.
- The running max/denominator (m, l) and the f32 output accumulator live in
  VMEM scratch; the output is cast once on the final kv block.
- Masking is positional (q_pos/kv_pos tiles), so the same kernel serves
  full-causal, sliding-window and padded layouts; kv tiles with no visible
  keys are skipped via `pl.when` — no MXU work issued (the pure-jnp
  reference cannot skip, which is exactly the 2x causal waste the §Perf
  log measures).
- GQA: one program instance serves all `rep` = H/Hkv query heads of one kv
  head — they share the K/V tile in VMEM (the q tile is [rep*block_q, d]).

Grid: (batch*kv_heads, q_blocks, kv_blocks).

Validated under interpret=True against `ref.attention_dense` in
tests/test_kernel_flash.py (shape/dtype sweeps + hypothesis cases).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float(np.finfo(np.float32).min) / 2


def supported(q, k, v) -> bool:
    B, Sq, H, Dk = q.shape
    _, Sk, Hkv, _ = k.shape
    return (H % Hkv == 0 and Dk % 8 == 0 and v.shape[-1] % 8 == 0)


def _kernel(qpos_ref, kpos_ref, q_ref, k_ref, v_ref, o_ref,
            m_ref, l_ref, acc_ref,
            *, scale, causal, window, rep, n_kv):
    kv_idx = pl.program_id(2)

    @pl.when(kv_idx == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_pos = qpos_ref[...]                                   # [block_q, 1]
    k_pos = kpos_ref[...]                                   # [1, block_k]
    valid = jnp.broadcast_to(k_pos >= 0,
                             (q_pos.shape[0], k_pos.shape[1]))
    if causal:
        valid = valid & (k_pos <= q_pos)
    if window is not None:
        valid = valid & (k_pos > (q_pos - window))

    @pl.when(jnp.any(valid))
    def _compute():
        rq, bq, dk = q_ref.shape
        q = q_ref[...].reshape(rq * bq, dk)                 # [rep*bq, d]
        k = k_ref[...]                                      # [bk, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale     # [rep*bq, bk]
        vmask = jnp.tile(valid, (rep, 1))
        s = jnp.where(vmask, s, NEG_INF)

        m_prev = m_ref[...]                                 # [rep*bq, 1]
        m_blk = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_blk)
        m_safe = jnp.where(m_new <= NEG_INF, 0.0, m_new)
        p = jnp.exp(s - m_safe)
        p = jnp.where(vmask, p, 0.0)
        corr = jnp.where(m_prev <= NEG_INF, 0.0, jnp.exp(m_prev - m_safe))
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, -1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(kv_idx == n_kv - 1)
    def _finalize():
        rq, bq, dv = o_ref.shape
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / l).astype(
            o_ref.dtype).reshape(rq, bq, dv)


def flash_attention(q, k, v, *, scale, q_pos, kv_pos, causal=True,
                    window=None, block_q: int = 128, block_k: int = 128,
                    interpret: bool = False):
    """Contract identical to `ref.attention` (q [B,Sq,H,Dk], k [B,Sk,Hkv,Dk],
    v [B,Sk,Hkv,Dv] -> [B,Sq,H,Dv]); padded kv slots carry kv_pos = -1."""
    B, Sq, H, Dk = q.shape
    Sk, Hkv, Dv = k.shape[1], k.shape[2], v.shape[-1]
    rep = H // Hkv
    block_q = min(block_q, max(8, Sq))
    block_k = min(block_k, Sk)

    pq = (-Sq) % block_q
    pk = (-Sk) % block_k
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, ((0, 0), (0, pq)), constant_values=2**30)
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
        kv_pos = jnp.pad(kv_pos, ((0, 0), (0, pk)), constant_values=-1)
    Sq_p, Sk_p = Sq + pq, Sk + pk
    n_q, n_kv = Sq_p // block_q, Sk_p // block_k

    # [B*Hkv, rep, Sq_p, Dk]: all q heads of one kv group share K/V tiles.
    q_r = q.reshape(B, Sq_p, Hkv, rep, Dk).transpose(0, 2, 3, 1, 4)
    q_r = q_r.reshape(B * Hkv, rep, Sq_p, Dk)
    k_r = k.transpose(0, 2, 1, 3).reshape(B * Hkv, Sk_p, Dk)
    v_r = v.transpose(0, 2, 1, 3).reshape(B * Hkv, Sk_p, Dv)
    # Positions ride as a column [B*Hkv, Sq_p, 1] (q) and a row
    # [B*Hkv, 1, Sk_p] (kv): Mosaic needs the last two block dims to be
    # (8, 128)-aligned or whole, which a [1, block] slice of [B*Hkv, S]
    # is not.
    qpos_r = jnp.repeat(q_pos, Hkv, axis=0)[:, :, None]
    kpos_r = jnp.repeat(kv_pos, Hkv, axis=0)[:, None, :]

    kernel = functools.partial(_kernel, scale=scale, causal=causal,
                               window=window, rep=rep, n_kv=n_kv)

    out = pl.pallas_call(
        kernel,
        grid=(B * Hkv, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((None, block_q, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, 1, block_k), lambda b, i, j: (b, 0, j)),
            pl.BlockSpec((None, rep, block_q, Dk),
                         lambda b, i, j: (b, 0, i, 0)),
            pl.BlockSpec((None, block_k, Dk), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((None, block_k, Dv), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((None, rep, block_q, Dv),
                               lambda b, i, j: (b, 0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * Hkv, rep, Sq_p, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((rep * block_q, 1), jnp.float32),
            pltpu.VMEM((rep * block_q, 1), jnp.float32),
            pltpu.VMEM((rep * block_q, Dv), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention",
    )(qpos_r, kpos_r, q_r, k_r, v_r)

    out = out.reshape(B, Hkv, rep, Sq_p, Dv)[:, :, :, :Sq]
    return out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, Dv)
