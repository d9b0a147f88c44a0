"""Kernel micro-benchmarks: Pallas vs pure-jnp reference.

On a TPU the Pallas kernels run compiled; on any other platform they run
in the interpreter, and those rows are correctness-path timings, never
kernel speed.  Every row's derived column names the platform.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.mf_sgd import mf_sgd_block
from repro.kernels.ssd_scan import ssd

from .common import emit, save_json, timed


def run():
    out = {}
    platform = jax.devices()[0].platform
    interpret = platform != "tpu"          # never time the interpreter on TPU
    mode = "interp" if interpret else "native"
    tag = f"platform={platform} interpret={interpret}"
    pal_iters = 1 if interpret else 10
    # flash attention
    B, S, H, Hkv, D = 1, 512, 8, 4, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, Hkv, D))
    v = jax.random.normal(ks[2], (B, S, Hkv, D))
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    fref = jax.jit(functools.partial(ref.attention, scale=0.125,
                                     q_pos=pos, kv_pos=pos))
    us = timed(fref, q, k, v)
    flops = 2 * 2 * B * H * S * S * D / 2   # causal
    emit("kernels/attention_ref_512", us,
         f"gflops={flops/us/1e3:.2f} platform={platform}")
    out["attention_ref_512_us"] = us

    fpal = jax.jit(functools.partial(
        flash_attention, scale=0.125, q_pos=pos, kv_pos=pos,
        interpret=interpret))
    us_p = timed(fpal, q, k, v, iters=pal_iters)
    emit(f"kernels/attention_pallas_{mode}_512", us_p, tag)

    # ssd
    b, s, h, p, g, n = 1, 1024, 8, 64, 1, 64
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    Bm = jax.random.normal(ks[3], (b, s, g, n))
    Cm = jax.random.normal(ks[4], (b, s, g, n))
    fref = jax.jit(lambda *a: ref.ssd_chunked(*a, 128))
    us = timed(fref, x, dt, A, Bm, Cm)
    emit("kernels/ssd_ref_1k", us,
         f"tokens_per_s={s/(us/1e6):.0f} platform={platform}")
    out["ssd_ref_1k_us"] = us
    fpal = jax.jit(functools.partial(ssd, chunk=128, interpret=interpret))
    us_p = timed(fpal, x, dt, A, Bm, Cm, iters=pal_iters)
    emit(f"kernels/ssd_pallas_{mode}_1k", us_p, tag)

    # mf sgd block
    N = M = 512; K = 32
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    L = jax.random.normal(ks[0], (N, K)); R = jax.random.normal(ks[1], (K, M))
    D_ = jax.random.normal(ks[2], (N, M))
    mask = jax.random.bernoulli(ks[3], 0.2, (N, M))
    fref = jax.jit(lambda *a: ref.mf_sgd_block(*a, 0.1, 1e-3))
    us = timed(fref, L, R, D_, mask)
    emit("kernels/mf_sgd_ref_512", us,
         f"ratings_per_s={0.2*N*M/(us/1e6):.2e} platform={platform}")
    out["mf_sgd_ref_512_us"] = us
    fpal = jax.jit(functools.partial(mf_sgd_block, gamma=0.1, lam=1e-3,
                                     interpret=interpret))
    us_p = timed(fpal, L, R, D_, mask, iters=pal_iters)
    emit(f"kernels/mf_sgd_pallas_{mode}_512", us_p, tag)

    save_json("kernels_bench", out)
    return out


if __name__ == "__main__":
    run()
