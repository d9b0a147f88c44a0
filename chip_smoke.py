"""Smoke check: the system's main path, run once on TPU through the entry
points a user calls.

    python chip_smoke.py                # one chip: phases 1-4
    python chip_smoke.py --four-chips   # the sharded runtimes on four chips

One chip:

1. device and compile cache;
2. the PS runtime against its simulator oracle (``psrun.cross_validate``)
   at the repo's default MF and LDA sizes under BSP and ESSP, and once over
   the compressed int8 wire;
3. MF at the paper's rank 100 over the Netflix Prize's 17,770 items,
   through ``PSRuntime.run``;
4. the Qwen3-0.6B trainer (``repro.launch.train.main``) at full width.

``--four-chips`` runs only the sharded runtimes (flat ``psrun`` on a 2x2
``("data","model")`` mesh, and the 2-pod hierarchy) against the simulator
on the same host, and checks where their shards landed.

The script runs in one process and starts none.  Off TPU it exits non-zero
before any phase; a failed phase raises and ends it with a non-zero exit.
Only a run in which every phase passed prints, as its last line,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Clock rates printed here are smoke readings, not benchmark numbers.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import sys
import time
from importlib import metadata
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# Phase 3: MF at the paper's rank over the Netflix Prize's items, at its
# density 100,480,507 / (480,189 x 17,770).  The users are cut to 16,384:
# the dense table cannot hold 480,189 of them yet.  lr was chosen on a CPU
# rehearsal at the same per-row update rates (2,048 users x 2,224 items,
# 512 ratings per worker per clock): 0.5 falls smoothly, 1.0 overshoots.
NETFLIX_USERS, NETFLIX_ITEMS, NETFLIX_RATINGS = 480_189, 17_770, 100_480_507
MF_BIG = dict(n_rows=16_384, n_cols=NETFLIX_ITEMS, rank=100,
              density=NETFLIX_RATINGS / (NETFLIX_USERS * NETFLIX_ITEMS),
              n_workers=8, batch=4096, lr=0.5)
MF_BIG_CLOCKS = 20
ORACLE_CLOCKS = 20
# Phase 4: batch x seq from the described-chip compile's memory_analysis():
# 8 x 512 = 4,096 tokens per step needs 11.7 GiB of the 16 GB (4 x 512:
# 10.2 GiB).
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 8, 512, 5

_COMPILE = {"secs": 0.0, "cache_hits": 0}


def _on_duration(event, duration_secs, **_):
    # tracing + lowering + backend compile (or loading it from the cache)
    if event.startswith("/jax/core/compile/"):
        _COMPILE["secs"] += duration_secs


def _on_event(event, **_):
    if event == "/jax/compilation_cache/cache_hits":
        _COMPILE["cache_hits"] += 1


class SmokeFailure(RuntimeError):
    pass


def require(cond, what):
    if not cond:
        raise SmokeFailure(what)


def say(phase, msg, **fields):
    extra = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"[{phase}] {msg} {extra}".rstrip(), flush=True)


def peak_bytes():
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use", "not reported")


def kernel_delta(before):
    from repro.kernels import ops
    now = ops.kernel_traces()
    return {k: now[k] - before.get(k, 0) for k in now
            if now[k] != before.get(k, 0)}


# --------------------------------------------------------------------------
# phases
# --------------------------------------------------------------------------
def phase_device(expect_count):
    import jax

    from repro.kernels import ops
    from repro.launch.cache import enable_compile_cache
    dev = jax.devices()[0]
    require(dev.platform == "tpu", f"no TPU: JAX found {dev.platform!r}")
    require(len(jax.devices()) == expect_count,
            f"need {expect_count} chip(s), JAX sees {len(jax.devices())}")
    cache = enable_compile_cache()
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_listener(_on_event)
    require(ops.get_backend() == "pallas", "kernels not on the Pallas path")
    say("device", "ok", platform=dev.platform, kind=repr(dev.device_kind),
        count=len(jax.devices()), jax=jax.__version__,
        libtpu=metadata.version("libtpu"), compile_cache=cache,
        kernels=ops.get_backend())
    return dev


def check_oracle(phase, name, res):
    """Integer decisions must match exactly; float drift is reported."""
    drift = {k: v for k, v in res["max_ulp"].items() if v}
    say(phase, name, ints_exact=res["ints_exact"],
        floats_bit_identical=res["ok"],
        max_ulp=max(res["max_ulp"].values()), ulp_by_field=drift or "{}",
        staleness_violations=res.get("violations", "n/a"))
    require(res["ints_exact"], f"{name}: integer trace fields differ")
    require(res.get("violations", 0) == 0, f"{name}: staleness bound broken")


def run_counted(rt, app, cfg, n_clocks):
    """One runtime run, returning the kernels traced into its program."""
    import jax

    from repro.kernels import ops
    before = ops.kernel_traces()
    jax.block_until_ready(rt.run(app, cfg, n_clocks))
    return kernel_delta(before)


def phase_oracle():
    from repro.apps.lda import LDAConfig, make_lda_app
    from repro.apps.matfact import MFConfig, make_mf_app
    from repro.core import bsp, essp
    from repro.core.consistency import compressed, podded
    from repro.psrun import PSRuntime, cross_validate, default_mesh
    mf, lda = make_mf_app(MFConfig()), make_lda_app(LDAConfig())
    rt = PSRuntime(default_mesh(8))
    wire = compressed(podded(essp(2), n_pods=2, s_xpod=3), agg_clocks=2,
                      topk_frac=0.25, quant="int8")
    cases = [("mf bsp", mf, bsp()), ("mf essp(3)", mf, essp(3)),
             ("lda bsp", lda, bsp()), ("lda essp(3)", lda, essp(3)),
             ("mf compressed int8 wire", mf, wire)]
    for name, app, cfg in cases:
        kernels = run_counted(rt, app, cfg, ORACLE_CLOCKS)
        say("oracle", f"{name}: runtime kernels", **kernels)
        require(kernels.get("ring_view", 0) > 0,
                f"{name}: ring_view kernel not in the runtime's program")
        if cfg.comm_active:
            require(kernels.get("delta_pack", 0) > 0,
                    f"{name}: delta_pack kernel not in the runtime's program")
        check_oracle("oracle", name,
                     cross_validate(app, cfg, ORACLE_CLOCKS, runtime=rt))


def phase_mf_big():
    import jax
    import numpy as np

    from repro.apps.matfact import MFConfig, make_mf_app
    from repro.core import essp
    from repro.kernels import ops
    from repro.psrun import PSRuntime, default_mesh
    cfg_mf = MFConfig(**MF_BIG)
    app = make_mf_app(cfg_mf)
    cfg = essp(3)
    rt = PSRuntime(default_mesh(cfg_mf.n_workers))
    ratings = int(np.prod(app.local0["vv"].shape))
    say("mf", "config", users=cfg_mf.n_rows, items=cfg_mf.n_cols,
        rank=cfg_mf.rank, workers=cfg_mf.n_workers, ratings=ratings,
        ratings_per_worker_per_clock=cfg_mf.batch, lr=cfg_mf.lr,
        consistency="essp(3)",
        cut=f"users {NETFLIX_USERS}->{cfg_mf.n_rows} (dense table)")
    W, P, d = cfg.effective_window, app.n_workers, app.dim
    say("mf", "bytes", table=d * 4, ring=W * P * d * 4,
        ratings=sum(x.nbytes for x in jax.tree.leaves(app.local0)))

    before, c0 = ops.kernel_traces(), _COMPILE["secs"]
    t0 = time.perf_counter()
    tr = jax.block_until_ready(rt.run(app, cfg, MF_BIG_CLOCKS))
    first = time.perf_counter() - t0
    compile_s = _COMPILE["secs"] - c0
    kernels = kernel_delta(before)
    t0 = time.perf_counter()
    jax.block_until_ready(rt.run(app, cfg, MF_BIG_CLOCKS))
    steady = time.perf_counter() - t0
    loss = np.asarray(tr.loss_ref)
    say("mf", "run", compile_s=compile_s, first_call_s=first,
        smoke_clocks_per_s=MF_BIG_CLOCKS / steady,
        peak_bytes_in_use=peak_bytes(), **kernels)
    say("mf", "loss_ref", first=float(loss[0]), last=float(loss[-1]))
    require(kernels.get("ring_view", 0) > 0,
            "ring_view kernel not in the MF program")
    require(np.isfinite(loss).all(), "MF loss not finite")
    require(loss[-1] < loss[0], "MF loss did not fall")


def check_attention():
    """The flash forward and its custom-VJP gradient against
    ``ref.attention`` at Qwen3-0.6B's attention widths.  Inputs are f32;
    the TPU's default matmul precision rounds operands to bf16 (2^-8) on
    either side, so the bound is relative to each output's largest value.
    A wrong mask or block index is off by O(1)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops
    B, S, H, Hkv, Dh = 1, 512, 16, 8, 128
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, (B, S, H, Dh))
    k = jax.random.normal(kk, (B, S, Hkv, Dh))
    v = jax.random.normal(kv, (B, S, Hkv, Dh))
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))

    def loss(q, k, v):
        out = ops.attention(q, k, v, scale=Dh ** -0.5, q_pos=pos, kv_pos=pos)
        return jnp.sum(jnp.sin(out)), out

    got = {}
    for backend in ("ref", "pallas"):
        ops.set_backend(backend)
        try:
            got[backend] = jax.jit(jax.value_and_grad(
                loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
        finally:
            ops.set_backend("auto")
    (_, out_r), grads_r = got["ref"]
    (_, out_p), grads_p = got["pallas"]
    rel = {}
    for name, a, b in (("out", out_p, out_r),
                       *zip(("dq", "dk", "dv"), grads_p, grads_r)):
        a, b = np.asarray(a), np.asarray(b)
        rel[name] = float(np.abs(a - b).max() / np.abs(b).max())
    say("train", "flash attention vs ref.attention", shape=(B, S, H, Hkv, Dh),
        max_rel_err=rel)
    require(all(e < 2e-2 for e in rel.values()),
            "flash attention disagrees with ref.attention")


def phase_train():
    from repro.kernels import ops
    from repro.launch.train import main as train_main
    gc.collect()                  # free the MF phase's device buffers first
    check_attention()
    before, c0 = ops.kernel_traces(), _COMPILE["secs"]
    hist = train_main(["--arch", "qwen3-0.6b", "--full",
                       "--consistency", "essp", "--buckets", "8",
                       "--steps", str(TRAIN_STEPS),
                       "--batch", str(TRAIN_BATCH), "--seq", str(TRAIN_SEQ),
                       "--log-every", "1"])
    kernels = kernel_delta(before)
    losses = [h["loss"] for h in hist]
    say("train", "qwen3-0.6b full width", steps=len(losses),
        tokens_per_step=TRAIN_BATCH * TRAIN_SEQ, losses=losses,
        compile_s=_COMPILE["secs"] - c0, peak_bytes_in_use=peak_bytes(),
        **kernels)
    require(len(losses) == TRAIN_STEPS, "trainer took too few steps")
    require(all(math.isfinite(x) for x in losses), "trainer loss not finite")
    require(kernels.get("flash_attention", 0) > 0,
            "flash-attention kernel not in the train step")


def check_placement(name, arrays, n_devices):
    """Each array's distinct blocks sit on disjoint device sets that
    together cover the mesh."""
    for field, arr in arrays.items():
        blocks: dict = {}
        for s in arr.addressable_shards:
            key = tuple(sl.start or 0 for sl in s.index)
            blocks.setdefault(key, set()).add(s.device.id)
        sets = list(blocks.values())
        disjoint = sum(len(s) for s in sets) == len(set().union(*sets))
        say("four-chips", f"{name}: {field} placement",
            blocks={str(k): sorted(v) for k, v in blocks.items()})
        require(len(sets) > 1 and disjoint
                and len(set().union(*sets)) == n_devices,
                f"{name}: {field} shards not spread over the chips")


def phase_four_chips():
    import jax

    from repro.apps.matfact import MFConfig, make_mf_app
    from repro.core import essp
    from repro.core.consistency import compressed, podded
    from repro.launch.mesh import make_ps_mesh
    from repro.pods import PodsRuntime, cross_validate_pods, \
        default_pods_mesh
    from repro.psrun import PSRuntime, cross_validate
    n = len(jax.devices())
    flat = PSRuntime(make_ps_mesh(data=2, model=2))
    cfg = essp(3)
    for name, mf_cfg in (("mf default", MFConfig()),
                         ("mf rank-100", MFConfig(**MF_BIG))):
        app = make_mf_app(mf_cfg)
        n_clocks = MF_BIG_CLOCKS if mf_cfg.rank == 100 else ORACLE_CLOCKS
        check_oracle("four-chips", f"flat 2x2 {name} essp(3)",
                     cross_validate(app, cfg, n_clocks, runtime=flat))
        _, state = flat.run_from(app, cfg, n_clocks,
                                 flat.init_state(app, cfg,
                                                 n_clocks=n_clocks))
        check_placement(f"flat {name}",
                        {"base": state.base, "uring": state.uring}, n)
        del app, state
    pods = PodsRuntime(default_pods_mesh(8, n_pods=2))
    say("four-chips", "pods mesh", shape=dict(pods.mesh.shape))
    app = make_mf_app(MFConfig())
    dense = podded(essp(2), n_pods=2, s_xpod=3)
    for name, pcfg in (("dense essp(2)", dense),
                       ("compressed int8 wire",
                        compressed(dense, agg_clocks=2, topk_frac=0.25,
                                   quant="int8"))):
        res = cross_validate_pods(app, pcfg, ORACLE_CLOCKS, runtime=pods)
        check_oracle("four-chips", f"pods {name}", res)
        if "replica_divergence" in res:
            say("four-chips", f"pods {name}: replica divergence",
                **res["replica_divergence"])
        _, state = pods.run_from(app, pcfg, ORACLE_CLOCKS,
                                 pods.init_state(app, pcfg,
                                                 n_clocks=ORACLE_CLOCKS))
        check_placement(f"pods {name}", {"cview": state.cview}, n)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded runtimes, on four chips")
    args = ap.parse_args(argv)
    import jax
    if jax.devices()[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found "
              f"{jax.devices()[0].platform!r}); nothing was run",
              file=sys.stderr)
        return 2
    t0 = time.perf_counter()
    dev = phase_device(4 if args.four_chips else 1)
    if args.four_chips:
        phase_four_chips()
    else:
        phase_oracle()
        phase_mf_big()
        phase_train()
    say("done", "all phases passed", wall_s=time.perf_counter() - t0,
        compile_s=_COMPILE["secs"], cache_hits=_COMPILE["cache_hits"])
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
