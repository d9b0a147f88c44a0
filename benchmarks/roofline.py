"""Roofline analysis from the dry-run artifacts (deliverable g).

Three terms per (arch x shape x mesh), all per-chip:

    compute    = HLO_FLOPs / peak_FLOPs          (197 TFLOP/s bf16, v5e)
    memory     = HLO_bytes / HBM_bw              (819 GB/s)
    collective = collective_bytes / ICI_bw       (3 links x 50 GB/s)

The peaks come from `launch.mesh.CHIP_PEAKS` for the dry-run's target chip.

HLO_FLOPs/bytes come from the multiplicity-aware HLO analyzer
(utils/hlo.py) — XLA's cost_analysis counts scan bodies once and is kept in
the artifacts as ``flops_xla_raw`` for reference.

MODEL_FLOPS: 6·N·D for training (N = params, D = tokens; MoE: N_active),
2·N·D for prefill/decode.  The ratio MODEL_FLOPS/HLO_FLOPs exposes
remat/recompute waste (e.g. 0.75 = the extra remat forward).
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.configs import INPUT_SHAPES, get_config  # noqa: E402
from repro.launch.mesh import chip_peaks  # noqa: E402

DRYRUN_DIR = os.environ.get("DRYRUN_DIR", "experiments/dryrun")
TARGET_KIND = "TPU v5 lite"   # the production meshes of launch.mesh are v5e


def active_params(arch: str) -> float:
    """Active (per-token) parameter count — MoE uses top_k experts only."""
    from repro.models.registry import build_model
    cfg = get_config(arch)
    n = build_model(cfg).n_params
    if cfg.moe is None:
        return float(n)
    e, k, ffe, d = (cfg.moe.n_experts, cfg.moe.top_k, cfg.moe.d_ff_expert,
                    cfg.d_model)
    per_layer_routed = e * 3 * d * ffe
    per_layer_active = k * 3 * d * ffe
    if cfg.family == "moe":
        n_moe_layers = cfg.n_layers
    elif cfg.family == "hybrid":
        n_moe_layers = cfg.n_layers // 2
    else:
        n_moe_layers = 0
    return float(n - n_moe_layers * (per_layer_routed - per_layer_active))


def model_flops(arch: str, shape_name: str) -> float:
    """Analytic MODEL_FLOPS per step (global, matmul-only, no attention)."""
    shape = INPUT_SHAPES[shape_name]
    n_act = active_params(arch)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_act * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_act * tokens
    tokens = shape.global_batch          # decode: one token per sequence
    return 2.0 * n_act * tokens


def load_artifacts(pattern: str = "*", include_tagged: bool = False):
    """Baseline artifacts are named <arch>_<shape>_<mesh>.json; §Perf
    variants carry a trailing _<tag> and are excluded by default."""
    arts = []
    for fn in sorted(glob.glob(os.path.join(DRYRUN_DIR, f"{pattern}.json"))):
        stem = os.path.basename(fn)[:-len(".json")]
        if not include_tagged and not (stem.endswith("_16x16")
                                       or stem.endswith("_2x16x16")):
            continue
        with open(fn) as f:
            arts.append(json.load(f))
    return arts


def roofline_row(art: dict) -> dict:
    chips = art["chips"]
    peaks = chip_peaks(TARGET_KIND)
    compute = art["flops_per_device"] / peaks["bf16_flops"]
    memory = art["bytes_accessed_per_device"] / peaks["hbm_bw"]
    coll = (art["collectives"]["total_bytes"]
            / (peaks["ici_link_bw"] * peaks["ici_links"]))
    terms = {"compute": compute, "memory": memory, "collective": coll}
    dominant = max(terms, key=terms.get)
    mf = model_flops(art["arch"], art["shape"])
    hlo_global = art["flops_per_device"] * chips
    useful = mf / hlo_global if hlo_global else 0.0
    bound = max(terms.values())
    # fraction of roofline: useful-model-compute time / dominant term
    mf_time = mf / chips / peaks["bf16_flops"]
    return {
        "arch": art["arch"], "shape": art["shape"], "mesh": art["mesh"],
        "kind": art["kind"],
        "compute_s": compute, "memory_s": memory, "collective_s": coll,
        "dominant": dominant,
        "model_flops": mf, "hlo_flops_global": hlo_global,
        "useful_compute_ratio": useful,
        "roofline_fraction": (mf_time / bound) if bound else 0.0,
        "mem_gib": art["memory"]["total_bytes"] / 2**30,
        "fits_hbm": art["memory"]["total_bytes"] <= 16 * 2**30,
        "coll_counts": art["collectives"]["count_by_op"],
    }


def table(rows, f=sys.stdout):
    hdr = (f"{'arch':26s} {'shape':12s} {'mesh':8s} "
           f"{'compute':>9s} {'memory':>9s} {'collect':>9s} "
           f"{'dominant':>10s} {'useful':>7s} {'roofl%':>7s} "
           f"{'mem GiB':>8s} fits")
    print(hdr, file=f)
    for r in rows:
        print(f"{r['arch']:26s} {r['shape']:12s} {r['mesh']:8s} "
              f"{r['compute_s']:9.4f} {r['memory_s']:9.4f} "
              f"{r['collective_s']:9.4f} {r['dominant']:>10s} "
              f"{r['useful_compute_ratio']:7.3f} "
              f"{100*r['roofline_fraction']:6.1f}% "
              f"{r['mem_gib']:8.2f} {'Y' if r['fits_hbm'] else 'N'}",
              file=f)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", default="16x16")
    ap.add_argument("--pattern", default="*")
    ap.add_argument("--json-out", default=None)
    args = ap.parse_args()
    rows = [roofline_row(a) for a in load_artifacts(args.pattern)
            if a["mesh"] == args.mesh or args.mesh == "all"]
    rows.sort(key=lambda r: (r["arch"], r["shape"], r["mesh"]))
    table(rows)
    if args.json_out:
        with open(args.json_out, "w") as fh:
            json.dump(rows, fh, indent=1)


if __name__ == "__main__":
    main()
