import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"   # the 512 devices are host devices:
# never open an attached chip.  These lines MUST run before any other
# import: jax locks the platform and device count at first initialization.

import argparse          # noqa: E402
import dataclasses       # noqa: E402
import json              # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402

import jax               # noqa: E402
import jax.numpy as jnp  # noqa: E402

from ..configs import ARCHS, INPUT_SHAPES, LONG_CONTEXT_WINDOW, get_config  # noqa: E402
from ..configs.base import ModelConfig, ShapeConfig  # noqa: E402
from ..models.registry import build_model  # noqa: E402
from ..models.layers import push_rules, pop_rules  # noqa: E402
from ..optim.optimizers import adamw  # noqa: E402
from ..psdist.grad_sync import GradSync  # noqa: E402
from ..train.state import make_accum_train_step, make_train_step  # noqa: E402
from ..utils.hlo import analyze, count_op  # noqa: E402
from .mesh import make_production_mesh  # noqa: E402
from . import sharding as shd  # noqa: E402

OUT_DIR = os.environ.get("DRYRUN_DIR", "experiments/dryrun")

# Gradient-accumulation (microbatch) factors for training shapes: the
# paper's update coalescing, sized so activations fit 16 GB/chip v5e HBM.
TRAIN_ACCUM = {
    "llama3-8b": 4,
    "qwen3-4b": 2,
    "deepseek-v2-lite-16b": 8,
    "qwen3-moe-30b-a3b": 4,
    "llama-3.2-vision-11b": 8,
    "jamba-1.5-large-398b": 4,     # §Perf: collective/memory knee at 4
    "mamba2-130m": 4,              # SSD intra-chunk tensors scale with batch
    "whisper-medium": 4,           # 1500-frame encoder activations
    "stablelm-3b": 2,
}


def adapt_config(cfg: ModelConfig, shape: ShapeConfig) -> ModelConfig:
    """Shape-specific config adjustments (DESIGN.md §Decode-shape policy):
    long_500k uses the sliding-window attention variant on every arch whose
    attention is otherwise full (sub-quadratic requirement)."""
    if shape.name == "long_500k" and cfg.attn is not None \
            and cfg.family != "hybrid":
        cfg = cfg.replace(attn=dataclasses.replace(
            cfg.attn, window=LONG_CONTEXT_WINDOW))
    if shape.kind != "train":
        # inference: no dropout-free distinction here, but prefill/decode use
        # bf16 params regardless of training dtype policy.
        cfg = cfg.replace(param_dtype="bfloat16")
    return cfg


def _data_size(mesh) -> int:
    n = mesh.shape["data"]
    if "pod" in mesh.axis_names:
        n *= mesh.shape["pod"]
    return n


def lower_one(arch: str, shape_name: str, *, multi_pod: bool = False,
              sync_mode: str = "bsp", staleness: int = 0, n_buckets: int = 1,
              profile: str | None = None, save: bool = True,
              tag: str = "", accum: int | None = None) -> dict:
    """Lower + compile one (arch x shape x mesh) and extract roofline terms."""
    t0 = time.time()
    shape = INPUT_SHAPES[shape_name]
    cfg = adapt_config(get_config(arch), shape)
    mesh = make_production_mesh(multi_pod=multi_pod)
    model = build_model(cfg)
    profile = profile or shd.profile_for(cfg)
    act_rules = {**shd.activation_rules(shape)}

    push_rules(mesh, act_rules)
    try:
        if shape.kind == "train":
            opt = adamw(1e-4, state_dtype=(
                jnp.bfloat16 if cfg.param_dtype == "bfloat16"
                else jnp.float32))
            sync = GradSync(sync_mode, staleness, n_buckets)
            # microbatch must stay shardable over the data axes
            if accum is None:
                accum = TRAIN_ACCUM.get(arch, 1)
            accum = max(1, min(accum, shape.global_batch // _data_size(mesh)))
            accum_dt = (jnp.bfloat16 if cfg.n_layers >= 72 else jnp.float32)
            step = make_accum_train_step(model, opt, sync, accum=accum,
                                         accum_dtype=accum_dt)
            state_in = shd.state_structs(model, opt, sync, mesh, profile)
            batch_in = shd.batch_specs(cfg, shape, mesh, accum=accum)
            with mesh:
                lowered = jax.jit(step, donate_argnums=0).lower(state_in, batch_in)
        else:
            params_in = shd.param_structs(model.param_specs, mesh, profile)
            batch_in = shd.batch_specs(cfg, shape, mesh)
            shardable = shape.global_batch % _data_size(mesh) == 0
            cache_in = shd.cache_specs(cfg, shape, mesh, shardable)
            if shape.kind == "prefill":
                fn = model.prefill
            else:
                fn = model.decode_step
            with mesh:
                lowered = jax.jit(fn, donate_argnums=2).lower(params_in, batch_in, cache_in)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
    finally:
        pop_rules()

    mem = compiled.memory_analysis()
    # jax 0.4.37 returns a single-element *list* of cost dicts (one per
    # executable); older/newer versions return the dict directly.
    cost = compiled.cost_analysis() or {}
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    hlo = compiled.as_text()
    stats = analyze(hlo)   # multiplicity-aware (scan bodies x trip count)

    chips = 512 if multi_pod else 256
    result = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": chips,
        "profile": profile,
        "kind": shape.kind,
        "sync": {"model": sync_mode, "staleness": staleness,
                 "n_buckets": n_buckets} if shape.kind == "train" else None,
        "n_params": model.n_params,
        # multiplicity-corrected (scan bodies x trips), per device:
        "flops_per_device": stats.flops,
        "bytes_accessed_per_device": stats.bytes_accessed,
        # raw XLA cost analysis (counts every while body ONCE — see
        # utils/hlo.py docstring); kept for reference:
        "flops_xla_raw": float(cost.get("flops", 0.0)),
        "bytes_xla_raw": float(cost.get("bytes accessed", 0.0)),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "total_bytes": (mem.argument_size_in_bytes
                            + mem.output_size_in_bytes
                            + mem.temp_size_in_bytes
                            - mem.alias_size_in_bytes),
        },
        "collectives": stats.as_dict(),
        "hlo_ops": {"dot": count_op(hlo, "dot"),
                    "fusion": count_op(hlo, "fusion"),
                    "while": count_op(hlo, "while")},
        "t_lower_s": round(t_lower, 1),
        "t_compile_s": round(t_compile, 1),
    }
    if save:
        os.makedirs(OUT_DIR, exist_ok=True)
        suffix = f"_{tag}" if tag else ""
        fn_out = f"{OUT_DIR}/{arch}_{shape_name}_{result['mesh']}{suffix}.json"
        with open(fn_out, "w") as f:
            json.dump(result, f, indent=1)
    return result


def fmt_row(r: dict) -> str:
    gb = r["memory"]["total_bytes"] / 2**30
    return (f"{r['arch']:25s} {r['shape']:12s} {r['mesh']:8s} "
            f"flops/dev={r['flops_per_device']:.3e} "
            f"mem/dev={gb:6.2f}GiB "
            f"coll={r['collectives']['total_bytes']/2**20:8.1f}MiB "
            f"({r['t_lower_s'] + r['t_compile_s']:5.1f}s)")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--sync", default="bsp")
    ap.add_argument("--staleness", type=int, default=0)
    ap.add_argument("--buckets", type=int, default=1)
    ap.add_argument("--tag", default="")
    ap.add_argument("--profile", default=None, choices=[None, "tp", "tp_fsdp"])
    ap.add_argument("--accum", type=int, default=None)
    ap.add_argument("--q-chunk", type=int, default=None)
    ap.add_argument("--kv-chunk", type=int, default=None)
    ap.add_argument("--static-causal", action="store_true",
                    help="enable the static causal KV-prefix optimization "
                         "(§Perf hillclimb variant; baseline is oblivious)")
    ap.add_argument("--all", action="store_true")
    args = ap.parse_args()

    archs = ARCHS if (args.all or not args.arch) else [args.arch]
    shapes = list(INPUT_SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    from ..kernels import ops
    if args.static_causal:
        ops.set_flag("static_causal", True)
    if args.q_chunk:
        ops.set_flag("q_chunk", args.q_chunk)
    if args.kv_chunk:
        ops.set_flag("kv_chunk", args.kv_chunk)

    results, failures = [], []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                key = f"{arch}/{shape}/{'2x16x16' if mp else '16x16'}"
                try:
                    r = lower_one(arch, shape, multi_pod=mp,
                                  sync_mode=args.sync,
                                  staleness=args.staleness,
                                  n_buckets=args.buckets, tag=args.tag,
                                  profile=args.profile, accum=args.accum)
                    results.append(r)
                    print("OK  ", fmt_row(r), flush=True)
                except Exception as e:  # noqa: BLE001
                    failures.append((key, repr(e)))
                    print("FAIL", key, repr(e), flush=True)
                    traceback.print_exc()
    print(f"\n{len(results)} ok, {len(failures)} failed")
    for k, e in failures:
        print("  FAIL", k, e[:200])
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
