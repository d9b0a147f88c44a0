"""Pallas kernels vs pure-jnp oracles (interpret=True on CPU).

Per instructions: sweep shapes/dtypes and assert_allclose against ref.py.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.mf_sgd import mf_sgd_block
from repro.kernels.mf_sse import mf_sse
from repro.kernels.ssd_scan import ssd
from repro.kernels import ops


def _attn_inputs(B, Sq, Sk, H, Hkv, Dk, Dv, dtype, seed=0):
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(kq, (B, Sq, H, Dk), dtype)
    k = jax.random.normal(kk, (B, Sk, Hkv, Dk), dtype)
    v = jax.random.normal(kv, (B, Sk, Hkv, Dv), dtype)
    qp = jnp.broadcast_to(jnp.arange(Sk - Sq, Sk), (B, Sq))
    kp = jnp.broadcast_to(jnp.arange(Sk), (B, Sk))
    return q, k, v, qp, kp


ATTN_CASES = [
    # B, Sq, Sk, H, Hkv, Dk, Dv, causal, window, dtype
    (2, 128, 128, 4, 2, 32, 32, True, None, jnp.float32),
    (1, 200, 200, 8, 8, 64, 64, True, None, jnp.float32),
    (2, 64, 256, 4, 1, 32, 16, True, None, jnp.float32),   # MQA, Dv != Dk
    (2, 128, 128, 4, 2, 32, 32, True, 48, jnp.float32),    # sliding window
    (2, 128, 128, 4, 2, 32, 32, False, None, jnp.float32),
    (2, 128, 128, 8, 4, 64, 64, True, None, jnp.bfloat16),
]


@pytest.mark.parametrize("case", ATTN_CASES)
def test_flash_attention_matches_dense(case):
    B, Sq, Sk, H, Hkv, Dk, Dv, causal, window, dtype = case
    q, k, v, qp, kp = _attn_inputs(B, Sq, Sk, H, Hkv, Dk, Dv, dtype)
    scale = 1.0 / np.sqrt(Dk)
    want = ref.attention_dense(q, k, v, scale=scale, q_pos=qp, kv_pos=kp,
                               causal=causal, window=window)
    got = flash_attention(q, k, v, scale=scale, q_pos=qp, kv_pos=kp,
                          causal=causal, window=window,
                          block_q=64, block_k=64, interpret=True)
    tol = 6e-2 if dtype == jnp.bfloat16 else 3e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


def test_blocked_ref_matches_dense():
    """The production (CPU) blocked path equals the quadratic oracle."""
    q, k, v, qp, kp = _attn_inputs(2, 96, 96, 4, 2, 32, 32, jnp.float32)
    want = ref.attention_dense(q, k, v, scale=0.18, q_pos=qp, kv_pos=kp)
    got = ref.attention(q, k, v, scale=0.18, q_pos=qp, kv_pos=kp,
                        kv_chunk=32, q_chunk=32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


@pytest.mark.slow
@settings(max_examples=10, deadline=None)
@given(sq=st.sampled_from([32, 64, 96]), sk=st.sampled_from([64, 128]),
       hkv=st.sampled_from([1, 2, 4]), rep=st.sampled_from([1, 2]),
       causal=st.booleans(), seed=st.integers(0, 3))
def test_flash_attention_hypothesis(sq, sk, hkv, rep, causal, seed):
    if sq > sk:
        sq = sk
    q, k, v, qp, kp = _attn_inputs(1, sq, sk, hkv * rep, hkv, 32, 32,
                                   jnp.float32, seed)
    want = ref.attention_dense(q, k, v, scale=0.2, q_pos=qp, kv_pos=kp,
                               causal=causal)
    got = flash_attention(q, k, v, scale=0.2, q_pos=qp, kv_pos=kp,
                          causal=causal, block_q=32, block_k=32,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5)


SSD_CASES = [
    (2, 128, 4, 32, 2, 32, 32, jnp.float32),
    (1, 256, 8, 64, 1, 64, 64, jnp.float32),
    (2, 128, 4, 32, 4, 32, 32, jnp.bfloat16),
]


@pytest.mark.parametrize("case", SSD_CASES)
def test_ssd_kernel_matches_ref(case):
    b, s, h, p, g, n, chunk, dtype = case
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(ks[0], (b, s, h, p), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    B = jax.random.normal(ks[3], (b, s, g, n), dtype)
    C = jax.random.normal(ks[4], (b, s, g, n), dtype)
    yw, stw = ref.ssd_chunked(x, dt, A, B, C, chunk)
    yg, stg = ssd(x, dt, A, B, C, chunk=chunk, interpret=True)
    yw, yg = np.asarray(yw, np.float32), np.asarray(yg, np.float32)
    scale = max(1.0, np.abs(yw).max())
    rtol = 1e-2 if dtype == jnp.bfloat16 else 1e-4
    assert np.abs(yw - yg).max() / scale < rtol
    np.testing.assert_allclose(np.asarray(stg), np.asarray(stw),
                               atol=scale * rtol)


def test_ssd_ref_matches_naive_recurrence():
    """The chunked dual form equals the exact token-by-token recurrence."""
    b, s, h, p, g, n, chunk = 1, 64, 2, 16, 1, 16, 16
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    B = jax.random.normal(ks[3], (b, s, g, n))
    C = jax.random.normal(ks[4], (b, s, g, n))

    y_chunk, st_chunk = ref.ssd_chunked(x, dt, A, B, C, chunk)

    state = jnp.zeros((b, h, p, n))
    ys = []
    for t in range(s):
        yt, state = ref.ssd_recurrent(x[:, t], dt[:, t], A, B[:, t], C[:, t],
                                      state)
        ys.append(yt)
    y_naive = jnp.stack(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_chunk), np.asarray(y_naive),
                               atol=2e-4)
    np.testing.assert_allclose(np.asarray(st_chunk), np.asarray(state),
                               atol=2e-4)


@pytest.mark.parametrize(("N", "M", "K"), [(256, 256, 16), (128, 384, 32),
                                   (128, 128, 8)])
def test_mf_sgd_kernel_matches_ref(N, M, K):
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    L = jax.random.normal(ks[0], (N, K))
    R = jax.random.normal(ks[1], (K, M))
    D = jax.random.normal(ks[2], (N, M))
    mask = jax.random.bernoulli(ks[3], 0.3, (N, M))
    dLw, dRw, lw = ref.mf_sgd_block(L, R, D, mask, 0.1, 1e-3)
    dLg, dRg, lg = mf_sgd_block(L, R, D, mask, 0.1, 1e-3, interpret=True)
    np.testing.assert_allclose(np.asarray(dLg), np.asarray(dLw), atol=1e-3)
    np.testing.assert_allclose(np.asarray(dRg), np.asarray(dRw), atol=1e-3)
    assert abs(float(lw - lg)) < 1e-3


@pytest.mark.slow
@settings(max_examples=6, deadline=None)
@given(nb=st.sampled_from([1, 2]), mb=st.sampled_from([1, 3]),
       k=st.sampled_from([8, 16]), density=st.floats(0.05, 0.9),
       seed=st.integers(0, 2))
def test_mf_sgd_hypothesis(nb, mb, k, density, seed):
    N, M = 128 * nb, 128 * mb
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    L = jax.random.normal(ks[0], (N, k))
    R = jax.random.normal(ks[1], (k, M))
    D = jax.random.normal(ks[2], (N, M))
    mask = jax.random.bernoulli(ks[3], density, (N, M))
    dLw, dRw, lw = ref.mf_sgd_block(L, R, D, mask, 0.05, 1e-4)
    dLg, dRg, lg = mf_sgd_block(L, R, D, mask, 0.05, 1e-4, interpret=True)
    np.testing.assert_allclose(np.asarray(dLg), np.asarray(dLw), atol=1e-3)
    np.testing.assert_allclose(np.asarray(dRg), np.asarray(dRw), atol=1e-3)


MF_SSE_CASES = {
    # n, m, k, block_n, block_m: m is never a multiple of 128
    "ragged-k24": (64, 300, 24, 24, 128),            # ragged row blocks
    "k100": (48, 200, 100, 512, 2048),
    "empty-column-block": (40, 520, 24, 16, 128),
    "value-without-count": (32, 300, 24, 32, 256),   # ragged column blocks
}


@pytest.mark.parametrize("case", sorted(MF_SSE_CASES))
def test_mf_sse_matches_gather_form(case):
    """The dense-block objective equals the per-rating gather form over the
    same ratings: pairs rated 2 and 3 times count 2 and 3 times, a column
    block with no rating adds nothing, and a value where the count is 0
    does not count."""
    n, m, k, block_n, block_m = MF_SSE_CASES[case]
    ks = jax.random.split(jax.random.PRNGKey(7), 6)
    L = 0.3 * jax.random.normal(ks[0], (n, k))
    R = 0.3 * jax.random.normal(ks[1], (k, m))
    D = jax.random.normal(ks[2], (n, m))
    N = n * m // 6
    ii = jax.random.randint(ks[3], (N,), 0, n)
    jj = jax.random.randint(ks[4], (N,), 0, m)
    if case == "empty-column-block":
        jj = jnp.where((jj >= 128) & (jj < 256), jj - 128, jj)
    # duplicated pairs: the first 5 ratings twice more, the next 5 once
    ii = jnp.concatenate([ii, ii[:5], ii[:10]])
    jj = jnp.concatenate([jj, jj[:5], jj[:10]])
    vv = D[ii, jj]
    m_pad = -(-m // 128) * 128
    C = jnp.zeros((n, m_pad), jnp.int8).at[ii, jj].add(jnp.int8(1))
    assert {2, 3} <= set(np.unique(np.asarray(C)).tolist())
    V = jnp.where(C > 0, jnp.pad(D, ((0, 0), (0, m_pad - m))), 0.0)
    if case == "empty-column-block":
        assert not np.asarray(C[:, 128:256]).any()
    if case == "value-without-count":
        V = jnp.where(C > 0, V, 5.0 + jax.random.normal(ks[5], V.shape))
    want = float(jnp.sum(jnp.square(
        vv - jnp.sum(L[ii] * R[:, jj].T, axis=-1))))
    R_pad = jnp.pad(R, ((0, 0), (0, m_pad - m)))
    got = float(mf_sse(L, R_pad, V, C, block_n=block_n, block_m=block_m,
                       interpret=True))
    np.testing.assert_allclose(got, want, rtol=1e-6)
    np.testing.assert_allclose(float(ref.mf_sse(L, R, V, C)), want,
                               rtol=1e-6)


def test_ops_backend_dispatch():
    ops.set_backend("ref")
    try:
        q, k, v, qp, kp = _attn_inputs(1, 32, 32, 2, 2, 16, 16, jnp.float32)
        out = ops.attention(q, k, v, scale=0.25, q_pos=qp, kv_pos=kp)
        assert out.shape == (1, 32, 2, 16)
        ops.set_backend("pallas_interpret")
        out2 = ops.attention(q, k, v, scale=0.25, q_pos=qp, kv_pos=kp)
        np.testing.assert_allclose(np.asarray(out2), np.asarray(out),
                                   atol=3e-5)
    finally:
        ops.set_backend("auto")


def test_static_causal_prefix_matches_dense():
    """§Perf static-causal path: identical numerics, fewer KV blocks."""
    q, k, v, qp, kp = _attn_inputs(2, 96, 96, 4, 2, 32, 32, jnp.float32)
    for win in (None, 24):
        want = ref.attention_dense(q, k, v, scale=0.2, q_pos=qp, kv_pos=kp,
                                   window=win)
        got = ref.attention(q, k, v, scale=0.2, q_pos=qp, kv_pos=kp,
                            window=win, kv_chunk=16, q_chunk=32,
                            assume_prefix=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=3e-5)


def test_static_causal_flag_dispatch():
    from repro.kernels import ops as _ops
    q, k, v, qp, kp = _attn_inputs(1, 64, 64, 2, 2, 16, 16, jnp.float32)
    base = _ops.attention(q, k, v, scale=0.25, q_pos=qp, kv_pos=kp,
                          q_chunk=32, kv_chunk=32)
    _ops.set_flag("static_causal", True)
    try:
        opt = _ops.attention(q, k, v, scale=0.25, q_pos=qp, kv_pos=kp,
                             q_chunk=32, kv_chunk=32)
    finally:
        _ops.set_flag("static_causal", False)
    np.testing.assert_allclose(np.asarray(opt), np.asarray(base), atol=3e-5)


def test_flash_attention_decode_ring_buffer_layout():
    """Serving path on TPU: single-token decode against a ring-buffer KV
    cache.  Slot validity/window are encoded in kv_pos (-1 = empty slot);
    the flash kernel must match the dense decode reference exactly."""
    B, C, H, Hkv, D = 2, 64, 4, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (B, 1, H, D))
    k = jax.random.normal(ks[1], (B, C, Hkv, D))
    v = jax.random.normal(ks[2], (B, C, Hkv, D))
    pos = jnp.array([37, 80])                       # wrapped for sample 1
    # ring-buffer slot positions (as computed by gqa_decode)
    slots = jnp.arange(C)[None, :]
    wraps = (pos[:, None] - slots + C) // C
    slot_pos = slots + wraps * C - C
    slot_pos = jnp.where(slot_pos == pos[:, None], pos[:, None], slot_pos)
    valid = (slot_pos >= 0) & (slot_pos <= pos[:, None])
    kv_pos = jnp.where(valid, slot_pos, -1)
    qp = pos[:, None]

    want = ref.attention_dense(q, k, v, scale=0.18, q_pos=qp, kv_pos=kv_pos,
                               causal=True)
    got = flash_attention(q, k, v, scale=0.18, q_pos=qp, kv_pos=kv_pos,
                          causal=True, block_q=8, block_k=32, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5)
    # and with a sliding window shorter than the filled cache
    want_w = ref.attention_dense(q, k, v, scale=0.18, q_pos=qp,
                                 kv_pos=kv_pos, causal=True, window=24)
    got_w = flash_attention(q, k, v, scale=0.18, q_pos=qp, kv_pos=kv_pos,
                            causal=True, window=24, block_q=8, block_k=32,
                            interpret=True)
    np.testing.assert_allclose(np.asarray(got_w), np.asarray(want_w),
                               atol=3e-5)


def test_attention_custom_vjp_matches_ref_grad():
    """On a Pallas backend the attention gradient is the reference's VJP
    around the flash forward: values and gradients match the ref path."""
    q, k, v, qp, kp = _attn_inputs(2, 64, 64, 4, 2, 16, 16, jnp.float32)

    def loss(q, k, v):
        out = ops.attention(q, k, v, scale=0.25, q_pos=qp, kv_pos=kp)
        return jnp.sum(jnp.sin(out))

    try:
        ops.set_backend("ref")
        want = jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)
        ops.set_backend("pallas_interpret")
        got = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))(q, k, v)
    finally:
        ops.set_backend("auto")
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-4)


@pytest.mark.parametrize("d", [200, 128, 300])
def test_ps_ops_pad_unaligned_width(d):
    """Every width takes the kernel and matches the reference: the ring
    kernels end on a ragged block, ``delta_pack``'s branch pads the lane
    axis to 128 and slices back."""
    from repro.comm import substrate as comm
    rng = np.random.default_rng(d)
    W, P, R, c = 5, 8, 4, 7
    uring = jnp.asarray(rng.normal(size=(W, P, d)).astype(np.float32))
    uclock = jnp.asarray([6, 5, 4, 3, -(10**9)], jnp.int32)
    cview = jnp.asarray(rng.integers(2, c, size=(R, P)).astype(np.int32))
    base = jnp.asarray(rng.normal(size=(d,)).astype(np.float32))
    delta = uring[0]
    thresh = comm.row_threshold(delta, 0.3)
    scale = comm.quant_scale(delta, "f32")
    outs = {}
    for backend in ("ref", "pallas_interpret"):
        ops.set_backend(backend)
        try:
            outs[backend] = (ops.ring_view(base, uring, uclock, cview),
                             ops.vap_suffix_norms(uring, uclock, jnp.int32(c)),
                             *ops.delta_pack(delta, thresh, scale, "f32"))
        finally:
            ops.set_backend("auto")
    for g, w in zip(outs["pallas_interpret"], outs["ref"], strict=True):
        assert g.shape == w.shape
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-5, atol=1e-5)


def test_ring_ops_do_not_pad():
    """On a Pallas backend the ring kernels take the ring at its width: the
    traced program holds the kernel and no pad of its operands or slice of
    its result."""
    W, P, d = 5, 8, 1000
    uring = jnp.ones((W, P, d))
    uclock = jnp.asarray([6, 5, 4, 3, 2], jnp.int32)
    cview = jnp.full((P, P), 6, jnp.int32)
    ops.set_backend("pallas_interpret")
    try:
        jaxprs = [jax.make_jaxpr(ops.ring_view)(jnp.zeros((d,)), uring,
                                                 uclock, cview),
                  jax.make_jaxpr(ops.vap_suffix_norms)(uring, uclock, 7)]
    finally:
        ops.set_backend("auto")
    for jaxpr in jaxprs:
        prims = {e.primitive.name for e in jaxpr.jaxpr.eqns}
        assert "pallas_call" in prims
        assert not prims & {"pad", "slice", "dynamic_slice"}, prims


def test_kernel_name_marks_pallas_programs():
    """The ring-view ``pallas_call`` carries ``name="ring_view"``: a program
    compiled on a Pallas backend holds that name in its ops' ``op_name``
    (how a caller proves a step took the kernel); the reference backend's
    program does not."""
    W, P, d = 3, 4, 128
    uring = jnp.ones((W, P, d))
    uclock = jnp.asarray([2, 1, 0], jnp.int32)
    cview = jnp.full((P, P), 2, jnp.int32)
    named = {}
    for backend in ("pallas_interpret", "ref"):
        ops.set_backend(backend)
        try:
            text = jax.jit(lambda b: ops.ring_view(b, uring, uclock, cview)
                           ).lower(jnp.zeros((d,))).compile().as_text()
        finally:
            ops.set_backend("auto")
        op_names = re.findall(r'op_name="([^"]*)"', text)
        assert op_names
        named[backend] = any("ring_view" in n.split("/") for n in op_names)
    assert named == {"pallas_interpret": True, "ref": False}


@pytest.mark.parametrize("kernel", ["ring_view", "vap_suffix_norms",
                                    "delta_pack", "attention"])
def test_pallas_backend_rejects_unsupported_shape(kernel):
    """No silent fallback: a shape no kernel supports raises."""
    W, P, d = 65, 4, 128                          # W > 64: no ring kernel
    uring = jnp.zeros((W, P, d))
    uclock = jnp.zeros((W,), jnp.int32)
    calls = {
        "ring_view": lambda: ops.ring_view(jnp.zeros((d,)), uring, uclock,
                                           jnp.zeros((P, P), jnp.int32)),
        "vap_suffix_norms": lambda: ops.vap_suffix_norms(uring, uclock, 3),
        "delta_pack": lambda: ops.delta_pack(jnp.zeros((129, d)),
                                             jnp.zeros(129), jnp.ones(129)),
        "attention": lambda: ops.attention(
            *_attn_inputs(1, 16, 16, 2, 2, 12, 12, jnp.float32)[:3],
            scale=0.3, q_pos=jnp.zeros((1, 16), jnp.int32),
            kv_pos=jnp.zeros((1, 16), jnp.int32)),   # head dim 12 % 8 != 0
    }
    ops.set_backend("pallas")
    try:
        with pytest.raises(ValueError, match="no Pallas"):
            calls[kernel]()
    finally:
        ops.set_backend("auto")
