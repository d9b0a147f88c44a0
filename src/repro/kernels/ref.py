"""Pure-jnp reference oracles for every kernel.

These are the semantics contracts: Pallas kernels must match them (tests
sweep shapes/dtypes with assert_allclose), and on CPU the ops dispatch here.

``attention`` is written *blocked* (lax.scan over KV chunks with online
softmax) so that even the reference path never materializes S×S logits —
required for the 32k/500k dry-run shapes.  ``attention_dense`` is the naive
quadratic oracle used only in tests at small sizes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = float(np.finfo(np.float32).min) / 2


# ==========================================================================
# attention
# ==========================================================================
def _block_mask(q_pos, kv_pos, causal, window):
    """[B,Sq,Ck] visibility of kv positions (pad slots have kv_pos < 0)."""
    valid = (kv_pos >= 0)[:, None, :]
    if causal:
        valid = valid & (kv_pos[:, None, :] <= q_pos[:, :, None])
    if window is not None:
        valid = valid & (kv_pos[:, None, :] > q_pos[:, :, None] - window)
    return valid


def attention_dense(q, k, v, *, scale, q_pos, kv_pos, causal=True,
                    window=None):
    """Naive quadratic oracle. q [B,Sq,H,Dk], k [B,Sk,Hkv,Dk],
    v [B,Sk,Hkv,Dv] -> [B,Sq,H,Dv]."""
    B, Sq, H, Dk = q.shape
    Hkv = k.shape[2]
    rep = H // Hkv
    qg = q.reshape(B, Sq, Hkv, rep, Dk)
    s = jnp.einsum("bqgrd,bkgd->bgrqk", qg, k,
                   preferred_element_type=jnp.float32) * scale
    mask = _block_mask(q_pos, kv_pos, causal, window)       # [B,Sq,Sk]
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bgrqk,bkgd->bqgrd", w.astype(v.dtype), v)
    return out.reshape(B, Sq, H, v.shape[-1])


def attention(q, k, v, *, scale, q_pos, kv_pos, causal=True, window=None,
              kv_chunk=1024, q_chunk=2048, assume_prefix=False):
    """Blocked flash-style attention (online softmax over KV chunks, outer
    map over Q chunks).

    Shapes as `attention_dense`; Dk and Dv may differ (MLA uses this as MQA
    over the latent).  Never materializes more than
    [B,Hkv,rep,q_chunk,kv_chunk] logits at a time.

    ``assume_prefix=True`` asserts that positions are ``arange`` (the
    standard full-forward layout): causal q-chunks then only visit their
    *static* KV prefix (and, with a window, only the in-window suffix of
    that prefix) — skipping fully-masked KV blocks.  This halves causal
    attention flops vs the oblivious blocked loop (§Perf llama3-8b log);
    it is what the Pallas kernel's `pl.when` skip does on TPU.
    """
    Sq_full = q.shape[1]
    if (assume_prefix and causal and Sq_full == k.shape[1]
            and Sq_full > q_chunk and Sq_full % q_chunk == 0):
        nq = Sq_full // q_chunk
        outs = []
        for i in range(nq):                      # static loop: shapes differ
            sl = slice(i * q_chunk, (i + 1) * q_chunk)
            end = (i + 1) * q_chunk              # static causal KV prefix
            start = 0
            if window is not None:               # static window lower bound
                start = max(0, i * q_chunk - window)
            outs.append(_attention_impl(
                q[:, sl], k[:, start:end], v[:, start:end], scale=scale,
                q_pos=q_pos[:, sl], kv_pos=kv_pos[:, start:end],
                causal=True, window=window, kv_chunk=kv_chunk))
        return jnp.concatenate(outs, axis=1)
    if Sq_full > q_chunk and Sq_full % q_chunk == 0:
        nq = Sq_full // q_chunk
        qs = q.reshape(q.shape[0], nq, q_chunk, *q.shape[2:]).transpose(
            1, 0, 2, 3, 4)
        ps = q_pos.reshape(q_pos.shape[0], nq, q_chunk).transpose(1, 0, 2)
        out = jax.lax.map(
            lambda args: _attention_impl(
                args[0], k, v, scale=scale, q_pos=args[1], kv_pos=kv_pos,
                causal=causal, window=window, kv_chunk=kv_chunk),
            (qs, ps))
        return out.transpose(1, 0, 2, 3, 4).reshape(
            q.shape[0], Sq_full, q.shape[2], v.shape[-1])
    return _attention_impl(q, k, v, scale=scale, q_pos=q_pos, kv_pos=kv_pos,
                           causal=causal, window=window, kv_chunk=kv_chunk)


def _attention_impl(q, k, v, *, scale, q_pos, kv_pos, causal, window,
                    kv_chunk):
    B, Sq, H, Dk = q.shape
    _, Sk, Hkv, _ = k.shape
    Dv = v.shape[-1]
    rep = H // Hkv
    C = min(kv_chunk, Sk)
    nc = -(-Sk // C)
    pad = nc * C - Sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_pos = jnp.pad(kv_pos, ((0, 0), (0, pad)), constant_values=-1)

    qg = q.reshape(B, Sq, Hkv, rep, Dk)
    kc = k.reshape(B, nc, C, Hkv, Dk).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, nc, C, Hkv, Dv).transpose(1, 0, 2, 3, 4)
    pc = kv_pos.reshape(B, nc, C).transpose(1, 0, 2)

    m0 = jnp.full((B, Hkv, rep, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Hkv, rep, Sq), jnp.float32)
    a0 = jnp.zeros((B, Hkv, rep, Sq, Dv), jnp.float32)

    def body(carry, blk):
        m, l, acc = carry
        kb, vb, pb = blk
        s = jnp.einsum("bqgrd,bkgd->bgrqk", qg, kb,
                       preferred_element_type=jnp.float32) * scale
        mask = _block_mask(q_pos, pb, causal, window)        # [B,Sq,C]
        s = jnp.where(mask[:, None, None], s, NEG_INF)
        m_blk = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m, m_blk)
        m_safe = jnp.where(m_new <= NEG_INF, 0.0, m_new)
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(mask[:, None, None], p, 0.0)
        corr = jnp.exp(jnp.where(m <= NEG_INF, NEG_INF, m - m_safe))
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bgrqk,bkgd->bgrqd", p.astype(v.dtype), vb,
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new), None

    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (kc, vc, pc))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    out = out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, Dv)
    return out.astype(q.dtype)


# ==========================================================================
# PS simulator ring-buffer ops (core/ps.py per-clock hot path)
# ==========================================================================
RING_INVALID = -(10**8)   # uclock values below this mark empty ring slots
RING_EMPTY = -(10**9)     # initial uclock fill (no clock stored yet)
# Both sentinels are part of the Trace-producer contract (core/ps.py):
# the simulator and the psrun runtime import them from here so the two
# engines' validity masks can never silently diverge.


def ring_view(base, uring, uclock, cview):
    """Materialize per-reader parameter views from the update ring.

    base [d], uring [W,P,d] (slot, producer, dim), uclock [W] (clock stored
    in each slot; < RING_INVALID when empty), cview [P,P] (reader, producer)
    visibility clocks.  Returns views [P,d]:

        view[r] = base + Σ_{w,q : uclock[w] <= cview[r,q], slot valid} uring[w,q]
    """
    valid = uclock > RING_INVALID
    vis = (uclock[None, :, None] <= cview[:, None, :]) & valid[None, :, None]
    # HIGHEST keeps the f32 ring out of the TPU's default bf16 pass; the
    # CPU computes f32 either way.
    return base[None, :] + jnp.einsum("rwq,wqd->rd", vis.astype(uring.dtype),
                                      uring,
                                      precision=jax.lax.Precision.HIGHEST)


def delta_pack(delta, thresh, scale, quant: str = "f32"):
    """Error-feedback compression pack of per-producer delta rows.

    ``delta [P, d]`` aggregated deltas, ``thresh [P]`` per-row magnitude
    threshold (the k-th largest ``|delta|``, see
    ``comm.substrate.row_threshold``), ``scale [P]`` int8 dequant scale
    (absmax/127; ignored unless ``quant == "int8"``).  Returns
    ``(wire [P, d], residual [P, d])``::

        mask     = |delta| >= thresh
        wire     = Q(where(mask, delta, 0))          # dequantized values
        residual = where(mask, delta - Q(delta), delta)

    ``quant`` is static ("f32" | "bf16" | "int8").  Mass conservation:
    ``wire + residual == delta`` — *exact* in the "f32" path (selected
    coordinates never round: residual is the masked complement, not a
    subtraction), to float rounding otherwise (residual is computed as
    ``delta - dequant`` so the quantization error re-ships later).
    """
    mask = jnp.abs(delta) >= thresh[:, None]
    if quant == "f32":
        q = delta
        residual = jnp.where(mask, 0.0, delta)
    elif quant == "bf16":
        q = delta.astype(jnp.bfloat16).astype(jnp.float32)
        residual = jnp.where(mask, delta - q, delta)
    elif quant == "int8":
        s = scale[:, None]
        q = jnp.clip(jnp.round(delta / s), -127.0, 127.0) * s
        residual = jnp.where(mask, delta - q, delta)
    else:
        raise ValueError(f"unknown quant {quant!r}")
    wire = jnp.where(mask, q, 0.0)
    return wire, residual


def vap_suffix_norms(uring, uclock, c):
    """Inf-norms of per-producer suffix aggregates of the newest k clocks.

    Returns norms [W+1, P] with norms[k, q] = || Σ_{j=1..k} u_q(c-j) ||_inf
    (norms[0] = 0: the empty suffix).  This is the quantity VAP bounds by
    v_t, and the one-gather source of the in-transit metric in `ps.py`.
    """
    W, P, _ = uring.shape
    ks = jnp.arange(1, W + 1, dtype=uclock.dtype)
    sel = (uclock[None, :] == (c - ks)[:, None]).astype(uring.dtype)  # [k,w]
    contrib = jnp.einsum("kw,wqd->kqd", sel, uring,
                         precision=jax.lax.Precision.HIGHEST)
    suffix = jnp.cumsum(contrib, axis=0)
    norms = jnp.max(jnp.abs(suffix), axis=-1)                         # [W,P]
    return jnp.concatenate([jnp.zeros((1, P), norms.dtype), norms], axis=0)


# ==========================================================================
# MF-SGD block update (the paper's hot loop, dense-block form)
# ==========================================================================
def mf_sgd_block(L, R, D, mask, gamma, lam):
    """One SGD step over a dense block of ratings.

    L [N,K], R [K,M], D [N,M] ratings with validity ``mask`` [N,M].
    Returns (dL, dR, loss) where dL/dR are the additive updates for the
    paper's update equations applied to every observed entry of the block
    (gradient summed over the block) and loss is the squared error.
    """
    E = jnp.where(mask, D - L @ R, 0.0)                     # residual
    cnt = jnp.maximum(jnp.sum(mask, axis=None), 1)
    dL = gamma * (E @ R.T - lam * jnp.sum(mask, 1, keepdims=True) * L)
    dR = gamma * (L.T @ E - lam * jnp.sum(mask, 0, keepdims=True) * R)
    loss = jnp.sum(jnp.square(E)) / cnt
    return dL, dR, loss


def mf_sse(L, R, V, C):
    """MF's squared error over a dense grid of counted ratings.

    L [n,k], R [k,m] (the flat table's own layout), V [n,m_pad] ratings and
    C [n,m_pad] int8 counts with m_pad >= m; columns past m have C = 0.
    Returns Σ C·(V − L R)² in f32: a pair rated twice counts twice, and V
    where C = 0 counts not at all.
    """
    R = jnp.pad(R, ((0, 0), (0, V.shape[1] - R.shape[1])))
    pred = jnp.dot(L, R, precision=jax.lax.Precision.HIGHEST)
    return jnp.sum(C.astype(jnp.float32) * jnp.square(V - pred))


# ==========================================================================
# Mamba-2 SSD (state-space duality) chunked scan
# ==========================================================================
def ssd_chunked(x, dt, A, B, C, chunk):
    """SSD forward (matches Mamba-2's `ssd_minimal_discrete`).

    x  [b, s, h, p]   per-head inputs (p = headdim)
    dt [b, s, h]      softplus-activated step sizes (>= 0)
    A  [h]            negative state decay rates (A < 0)
    B  [b, s, g, n]   input projections (g groups, n = d_state)
    C  [b, s, g, n]   output projections
    Returns y [b, s, h, p] and final state [b, h, p, n].
    """
    b, s, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    s_orig = s
    if s % chunk:
        # pad with dt=0 / x=0 positions: decay exp(0)=1 and zero input leave
        # the carried state untouched; padded outputs are sliced off below.
        pad = chunk - s % chunk
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0), (0, 0)))
        s = s + pad
    nc = s // chunk
    rep = h // g

    xbar = x * dt[..., None]                                # dt-weighted input
    da = dt * A[None, None, :]                              # [b,s,h] log-decay
    # reshape into chunks
    xc = xbar.reshape(b, nc, chunk, h, p)
    dac = da.reshape(b, nc, chunk, h)
    Bc = B.reshape(b, nc, chunk, g, n)
    Cc = C.reshape(b, nc, chunk, g, n)

    # cumulative log decay within chunk
    cum = jnp.cumsum(dac, axis=2)                           # [b,nc,l,h]
    # intra-chunk (dual / quadratic) term:
    #   y_intra[i] = sum_{j<=i} C_i . B_j * exp(cum_i - cum_j) xbar_j
    Bh = jnp.repeat(Bc, rep, axis=3)                        # [b,nc,l,h,n]
    Ch = jnp.repeat(Cc, rep, axis=3)
    scores = jnp.einsum("bclhn,bcmhn->bclmh", Ch, Bh)       # l=query m=key
    # clamp the exponent at 0: the upper triangle (j > i, positive exponent)
    # is masked below, but letting it overflow to inf first produces
    # 0 * inf = NaN in the backward pass of the where().
    decay = jnp.exp(jnp.minimum(
        cum[:, :, :, None, :] - cum[:, :, None, :, :], 0.0))
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    w = jnp.where(causal[None, None, :, :, None], scores * decay, 0.0)
    y_intra = jnp.einsum("bclmh,bcmhp->bclhp", w, xc)

    # chunk summary states: S_c = sum_j exp(cum_last - cum_j) B_j ⊗ xbar_j
    decay_to_end = jnp.exp(cum[:, :, -1:, :] - cum)         # [b,nc,l,h]
    state_c = jnp.einsum("bclhn,bclh,bclhp->bchpn",
                         Bh, decay_to_end, xc)

    # inter-chunk recurrence over chunk summaries
    chunk_decay = jnp.exp(cum[:, :, -1, :])                 # [b,nc,h]

    def body(carry, inp):
        s_prev = carry                                      # [b,h,p,n]
        st, dec = inp                                       # [b,h,p,n], [b,h]
        s_new = s_prev * dec[..., None, None] + st
        return s_new, s_prev

    st_t = state_c.transpose(1, 0, 2, 3, 4)                 # [nc,b,h,p,n]
    dec_t = chunk_decay.transpose(1, 0, 2)                  # [nc,b,h]
    final_state, prev_states = jax.lax.scan(
        body, jnp.zeros((b, h, p, n), jnp.float32), (st_t.astype(jnp.float32),
                                                     dec_t.astype(jnp.float32)))
    prev_states = prev_states.transpose(1, 0, 2, 3, 4)      # [b,nc,h,p,n]

    # inter-chunk contribution: y_inter[i] = C_i exp(cum_i) S_prev
    y_inter = jnp.einsum("bclhn,bclh,bchpn->bclhp",
                         Ch, jnp.exp(cum), prev_states)
    y = (y_intra + y_inter).reshape(b, s, h, p).astype(x.dtype)
    return y[:, :s_orig], final_state


def ssd_recurrent(x, dt, A, B, C, state):
    """Single-token SSD decode step.

    x [b,h,p], dt [b,h], B/C [b,g,n], state [b,h,p,n] -> (y, state')."""
    g = B.shape[1]
    h = x.shape[1]
    rep = h // g
    Bh = jnp.repeat(B, rep, axis=1)                         # [b,h,n]
    Ch = jnp.repeat(C, rep, axis=1)
    decay = jnp.exp(dt * A[None, :])[..., None, None]       # [b,h,1,1]
    upd = (dt[..., None] * x)[..., None] * Bh[:, :, None, :]
    state = state * decay + upd
    y = jnp.einsum("bhpn,bhn->bhp", state, Ch)
    return y, state
