"""jit-friendly dispatch wrappers for the Pallas kernels.

On TPU the Pallas implementations run natively; on CPU the wrappers
dispatch to the pure-jnp references, and tests exercise the Pallas bodies
under ``interpret=True``.  Selection can be forced with
``set_backend("pallas"|"pallas_interpret"|"ref")`` (kernel tests and
benchmarks).

On a Pallas backend nothing falls back in silence: the PS kernels pad the
lane axis ``d`` up to the kernel block (zeros change no sum, inf-norm or
pack) and slice the result, and a shape no kernel supports raises.
The kernels' ``pallas_call``s carry ``name=`` (``ring_view``,
``vap_suffix_norms``, ``delta_pack``, ``mf_sse``, ``flash_attention``): a
compiled program that took one holds its name in its ops' ``op_name``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import ref

_BACKEND = "auto"
_LANE = 128                      # kernel block on the lane axis

# Perf toggles (see EXPERIMENTS.md §Perf): static_causal skips fully-masked
# causal KV blocks in full-sequence attention (positions are arange there).
# Default OFF so baseline dry-runs measure the oblivious blocked loop; the
# §Perf hillclimb runs enable it (and the Pallas kernel always skips).
_FLAGS = {"static_causal": False,
          "kv_chunk": 1024, "q_chunk": 2048}


def set_flag(name: str, value):
    assert name in _FLAGS
    _FLAGS[name] = value


def get_flag(name: str) -> bool:
    return _FLAGS[name]


def set_backend(name: str):
    global _BACKEND
    assert name in ("auto", "ref", "pallas", "pallas_interpret")
    _BACKEND = name


def get_backend() -> str:
    if _BACKEND != "auto":
        return _BACKEND
    platform = jax.default_backend()
    return "pallas" if platform == "tpu" else "ref"


def _pallas() -> bool | None:
    """``interpret`` flag for a kernel on a Pallas backend, or None on the
    reference backend."""
    backend = get_backend()
    if backend not in ("pallas", "pallas_interpret"):
        return None
    return backend == "pallas_interpret"


def _unsupported(kernel: str, shape, need: str):
    raise ValueError(f"no Pallas {kernel} kernel for shape {tuple(shape)} "
                     f"({need}); use set_backend('ref') for this shape")


def _pad_lanes(x):
    pad = (-x.shape[-1]) % _LANE
    if not pad:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])


def attention(q, k, v, *, scale, q_pos, kv_pos, causal=True, window=None,
              kv_chunk=None, q_chunk=None):
    """Blocked attention; see `ref.attention` for the contract.

    On a Pallas backend the forward pass is the flash kernel and the
    backward pass is the VJP of `ref.attention` (a `jax.custom_vjp`)."""
    kv_chunk = kv_chunk or _FLAGS["kv_chunk"]
    q_chunk = q_chunk or _FLAGS["q_chunk"]
    interpret = _pallas()
    if interpret is None:
        return ref.attention(q, k, v, scale=scale, q_pos=q_pos,
                             kv_pos=kv_pos, causal=causal, window=window,
                             kv_chunk=kv_chunk, q_chunk=q_chunk,
                             assume_prefix=_FLAGS["static_causal"])
    from . import flash_attention as fa
    if not fa.supported(q, k, v):
        _unsupported("flash_attention", q.shape,
                     "needs H % Hkv == 0 and head dims % 8 == 0")
    kw = dict(scale=scale, causal=causal, window=window)

    @jax.custom_vjp
    def flash(q, k, v, q_pos, kv_pos):
        return fa.flash_attention(q, k, v, q_pos=q_pos, kv_pos=kv_pos,
                                  interpret=interpret, **kw)

    def fwd(q, k, v, q_pos, kv_pos):
        return flash(q, k, v, q_pos, kv_pos), (q, k, v, q_pos, kv_pos)

    def bwd(res, g):
        q, k, v, q_pos, kv_pos = res
        _, vjp = jax.vjp(
            lambda q, k, v: ref.attention(
                q, k, v, q_pos=q_pos, kv_pos=kv_pos, kv_chunk=kv_chunk,
                q_chunk=q_chunk, **kw),
            q, k, v)
        return (*vjp(g), None, None)

    flash.defvjp(fwd, bwd)
    return flash(q, k, v, q_pos, kv_pos)


def ring_view(base, uring, uclock, cview):
    """PS view materialization; see `ref.ring_view` for the contract."""
    interpret = _pallas()
    if interpret is None:
        return ref.ring_view(base, uring, uclock, cview)
    from . import ps_view
    d = uring.shape[-1]
    uring_p = _pad_lanes(uring)
    if not ps_view.supported(uring_p):
        _unsupported("ring_view", uring.shape, "needs P <= 128, W <= 64")
    return ps_view.ring_view(_pad_lanes(base), uring_p, uclock, cview,
                             interpret=interpret)[:, :d]


def vap_suffix_norms(uring, uclock, c):
    """VAP suffix-aggregate inf-norms; see `ref.vap_suffix_norms`."""
    interpret = _pallas()
    if interpret is None:
        return ref.vap_suffix_norms(uring, uclock, c)
    from . import ps_view
    uring_p = _pad_lanes(uring)
    if not ps_view.supported(uring_p):
        _unsupported("vap_suffix_norms", uring.shape,
                     "needs P <= 128, W <= 64")
    return ps_view.vap_suffix_norms(uring_p, uclock, c, interpret=interpret)


def delta_pack(delta, thresh, scale, quant: str = "f32"):
    """Comm-substrate compression pack; see `ref.delta_pack`."""
    interpret = _pallas()
    if interpret is None:
        return ref.delta_pack(delta, thresh, scale, quant)
    from . import delta_pack as dp
    d = delta.shape[-1]
    delta_p = _pad_lanes(delta)
    if not dp.supported(delta_p):
        _unsupported("delta_pack", delta.shape, "needs P <= 128")
    wire, res = dp.delta_pack(delta_p, thresh, scale, quant,
                              interpret=interpret)
    return wire[:, :d], res[:, :d]


def mf_sse(L, R, V, C):
    """MF's dense-block squared error; see `ref.mf_sse` for the contract.

    On a Pallas backend ``R [k, m]`` is padded with zero columns to ``V``'s
    width (a multiple of 128 lanes)."""
    interpret = _pallas()
    if interpret is None:
        return ref.mf_sse(L, R, V, C)
    from . import mf_sse as sse
    n, m_pad = V.shape
    if not sse.supported(n, m_pad):
        _unsupported("mf_sse", V.shape, "needs n % 8 == 0, m_pad % 128 == 0")
    R = jnp.pad(R, ((0, 0), (0, m_pad - R.shape[1])))
    return sse.mf_sse(L, R, V, C, interpret=interpret)


def mf_sgd_block(L, R, D, mask, gamma, lam):
    backend = get_backend()
    if backend in ("pallas", "pallas_interpret"):
        from . import mf_sgd
        if mf_sgd.supported(L, R, D):
            return mf_sgd.mf_sgd_block(
                L, R, D, mask, gamma, lam,
                interpret=(backend == "pallas_interpret"))
    return ref.mf_sgd_block(L, R, D, mask, gamma, lam)


def ssd(x, dt, A, B, C, chunk=128):
    backend = get_backend()
    if backend in ("pallas", "pallas_interpret"):
        from . import ssd_scan
        if ssd_scan.supported(x, B, chunk):
            return ssd_scan.ssd(x, dt, A, B, C, chunk=chunk,
                                interpret=(backend == "pallas_interpret"))
    return ref.ssd_chunked(x, dt, A, B, C, chunk)


def ssd_decode(x, dt, A, B, C, state):
    # decode step is tiny; always the reference path
    return ref.ssd_recurrent(x, dt, A, B, C, state)
