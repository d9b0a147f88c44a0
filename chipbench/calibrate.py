"""Readings that a cell's limits are set from, in one process on the chip.

    python3 chipbench/calibrate.py --workload <cell> --seeds 12 --faulted 3 --first-seed <n> [--seconds <s>]

For ``--seeds`` seeds it makes the sound run's readings: the cell's set-up
and a window of ``--seconds`` (one segment at 0), then the comparison with
the reference, every number printed whatever its limit; the window's loss
at each segment's end goes to standard error, for the cell's loss
threshold.  After the first seed's window it prints the segment program's
``memory_analysis()`` beside the chips' ``peak_bytes_in_use``.

For ``--faulted`` further seeds it runs the cell with the state handed on
unchanged between segments, and puts the reference itself in the
program's place: in bfloat16 (the control) and with each planted fault
(half of each worker's batch left out, worker 0's update doubled).  The
last line of standard output is a JSON summary: per
number, the largest sound reading (the lower reading of its limit) and the
smallest reading of the control and of each fault (the upper ones).
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def unchanged(segment):
    """The fault of a step that hands its state on unchanged."""
    return lambda state: (segment(state)[0], state)


def memory(segment, state, devices):
    """The window's segment program compiled for ``state``: its memory
    analysis on one chip, beside the peak of bytes in use so far."""
    import jax
    m = jax.jit(segment).lower(state).compile().memory_analysis()
    row = {"args": m.argument_size_in_bytes, "out": m.output_size_in_bytes,
           "alias": m.alias_size_in_bytes, "temp": m.temp_size_in_bytes}
    row["total"] = row["args"] + row["out"] - row["alias"] + row["temp"]
    row["peak_bytes_in_use"] = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices)
    print(json.dumps({"kind": "memory_analysis", **row}), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--faulted", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2**31 + 1000)
    ap.add_argument("--seconds", type=float, default=0.0)
    args = ap.parse_args(argv)

    import jax.numpy as jnp

    from chipbench import compare, run

    cell = run.load_cell(args.workload)
    devices = run.chips(cell.chips)
    run.enable_compile_cache()
    app_mod = run.load_module(run.HERE / "apps" / f"{cell.config['app']}.py")
    cell.limits = {name: math.inf for name in compare.NUMBERS}
    n_clocks = run.SETUP_SEGMENTS * cell.traffic["segment_clocks"]
    readings: dict = {"sound": []}
    seed = args.first_seed
    for i in range(args.seeds):
        res = run.run_cell(cell, seed, args.seconds, False, devices,
                           inspect=memory if i == 0 else None)
        row = {k: c["value"] for k, c in res["checks"].items()}
        row.update({k: m["value"] for k, m in res["metrics"].items()})
        print(json.dumps({"kind": "sound", "seed": seed, **row}), flush=True)
        readings["sound"].append(row)
        seed += 1
        gc.collect()
    kinds = {"control": dict(dtype=jnp.bfloat16),
             **{fault: dict(fault=fault) for fault in app_mod.FAULTS}}
    for _ in range(args.faulted):
        res = run.run_cell(cell, seed, 0.0, False, devices,
                           wrap_segment=unchanged)
        row = {k: c["value"] for k, c in res["checks"].items()}
        print(json.dumps({"kind": "unchanged", "seed": seed, **row}),
              flush=True)
        readings.setdefault("unchanged", []).append(row)
        gc.collect()
        ref = app_mod.reference(cell.config, cell.traffic, seed, n_clocks)
        for kind, kw in kinds.items():
            got = app_mod.reference(cell.config, cell.traffic, seed,
                                    n_clocks, **kw)
            row = compare.gaps(got, ref)
            print(json.dumps({"kind": kind, "seed": seed, **row}),
                  flush=True)
            readings.setdefault(kind, []).append(row)
            del got
        del ref
        seed += 1
        gc.collect()
    summary = {"workload": args.workload}
    for kind, rows in readings.items():
        pick = max if kind == "sound" else min
        summary[kind] = {n: pick(r[n] for r in rows)
                         for n in compare.NUMBERS}
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
