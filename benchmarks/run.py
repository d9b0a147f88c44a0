"""Benchmark harness entry point: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (plus claim summaries at the
end).  Roofline tables are separate (they read dry-run artifacts):
``python -m benchmarks.roofline``.

The harness deliberately does NOT force a multi-device host platform: on
small hosts, 8 fake devices oversubscribe the cores and distort every
timing row.  `benchmarks.psrun_bench` (8 devices) and
`benchmarks.pods_bench` (16, the CI pods-lane topology) force their own
host platforms when run standalone, which is where the sharded clocks/sec
numbers come from; inside this harness they run on whatever topology the
process has (their traces — and therefore their convergence claims — are
mesh-independent by the oracle contract).
"""
from __future__ import annotations

import argparse
import resource
import sys
import time
import traceback


def failed_claims(claim, prefix="") -> list:
    """Recursively collect the paths of boolean claim leaves that are
    False.  Non-boolean leaves (counts, seconds, ratios) are context, not
    gates; every boolean in a claim dict is positively phrased ("pass",
    "ok", "..._stable") by convention, so False means the claim tripped."""
    out = []
    if isinstance(claim, dict):
        for k, v in claim.items():
            out += failed_claims(v, f"{prefix}.{k}" if prefix else str(k))
    elif isinstance(claim, bool) and not claim:
        out.append(prefix)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json-dir", default=None, metavar="DIR",
                    help="directory for all JSON artifacts (per-suite "
                         "results and the machine-readable BENCH_*.json "
                         "perf records); default: $BENCH_DIR or "
                         "experiments/bench")
    args = ap.parse_args(argv)
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    t0 = time.time()
    from . import (analysis_bench, autotune_bench, comm_bench,
                   comm_comp, common, detect_bench, faults_bench,
                   kernels_bench, lda_convergence, lm_consistency,
                   mf_convergence, pods_bench, psrun_bench, robustness,
                   staleness_profile, stragglers, sweep_bench,
                   theory_validation)
    if args.json_dir:
        common.set_results_dir(args.json_dir)

    claims, errors = {}, {}

    def suite(name, fn):
        """Run one suite; a crash is recorded (and fails the harness) but
        never silences the remaining suites' rows and artifacts.  Each
        BENCH_*.json the suite wrote gets ``meta.timing`` stamped (suite
        wall seconds + process peak RSS — RSS is monotonic process-wide,
        so it reads as "peak by the end of this suite")."""
        common.pop_written()
        t0 = time.perf_counter()
        try:
            claims[name] = fn()
        except Exception:
            errors[name] = traceback.format_exc()
            print(f"\n!! suite {name} crashed:\n{errors[name]}",
                  file=sys.stderr)
        finally:
            common.annotate_bench_meta(common.pop_written(), {
                "suite": name,
                "wall_s": round(time.perf_counter() - t0, 3),
                "peak_rss_mb": round(resource.getrusage(
                    resource.RUSAGE_SELF).ru_maxrss / 1024.0, 1),
            })

    print("name,us_per_call,derived")
    suite("C1_staleness_profile", lambda: staleness_profile.run()["claim_C1"])
    suite("C2_mf", lambda: mf_convergence.run()["claim_C2"])
    suite("C2_lda", lambda: lda_convergence.run()["claim_C2_lda"])
    suite("C6_comm_comp", lambda: comm_comp.run()["claim_C6"])
    suite("C3_robustness", lambda: robustness.run()["claim_C3"])
    suite("stragglers", lambda: stragglers.run()["claim"])
    suite("lm_consistency_pod", lambda: lm_consistency.run()["claim"])

    def _theory():
        theory = theory_validation.run()
        claims["C4_variance"] = theory["variance"]
        return theory["vap"]

    suite("C5_vap", _theory)

    def _sweep():
        sb = sweep_bench.run()
        return {"speedup": round(sb["speedup"], 1), "pass_3x": sb["pass_3x"]}

    suite("sweep_engine", _sweep)
    suite("autotune", lambda: autotune_bench.run()["claim"])
    suite("psrun_eager_beats_lazy", lambda: psrun_bench.run()["claim"])
    suite("pods_eager_beats_gated", lambda: pods_bench.run()["claim"])
    suite("comm_substrate", lambda: comm_bench.run()["claim"])
    suite("kernels", lambda: kernels_bench.run())
    suite("analysis", lambda: analysis_bench.run()["claim"])
    suite("detect_quality", lambda: detect_bench.run()["claim"])
    suite("wire_faults", lambda: faults_bench.run()["claim"])

    print("\n=== paper-fidelity claim summary ===")
    for k, v in claims.items():
        print(f"{k}: {v}")
    tripped = failed_claims(claims)
    status = 0
    if tripped:
        print(f"\nFAILED claims: {', '.join(tripped)}", file=sys.stderr)
        status = 1
    if errors:
        print(f"FAILED suites: {', '.join(errors)}", file=sys.stderr)
        status = 1
    print(f"\ntotal bench wall: {time.time()-t0:.1f}s")
    return status


if __name__ == "__main__":
    sys.exit(main())
