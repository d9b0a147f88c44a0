"""Per-stage device time of the PS clock step, from a profiler trace.

The clock step (``src/repro/psrun/runtime.py``) runs each of its stages
under ``jax.named_scope("psrun.<stage>")``: enforce, view, update, push,
record.  The scope lands in each HLO op's ``op_name``, which the profiler
keeps as the ``tf_op`` stat of the op's event metadata in the
``.xplane.pb``.  ``jax.profiler.ProfileData`` does not expose those stats,
so `load_op_scopes` reads the protobuf's wire format itself.

- `stage_times` charges each op's self time (`reduce.reduce_events`'s
  ``op_time``) to the first ``psrun.<stage>`` of its ``tf_op``, and ops
  with none (the loop's own plumbing, the ops outside the scan) to
  ``other``; the stages add up to the busy time.
- `idle_gaps` labels the device's idle gaps like `reduce.reduce_events`,
  with the program's host spans (``psrun.dispatch``) among the labels: the
  span that overlaps a gap most, the shortest (innermost) among equals.

The window, busy time and op times are `reduce.reduce_events`'s own.

    python3 chipbench/stages.py --workload <cell> --seed <n> --seconds <s>
        [--keep <dir>]

runs the cell as ``chipbench/run.py`` does, untraced, then profiles
``run.TRACE_SEGMENTS`` more segments of the same job and prints one JSON
line: ``stage_ms`` (device ms a clock per stage), the busy time a clock,
the longest idle gaps, and the host time in ``psrun.dispatch`` per
segment; ``--keep`` keeps the trace there, gzipped.
"""
from __future__ import annotations

import argparse
import gzip
import json
import re
import shutil
import struct
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import reduce  # noqa: E402

PREFIX = "psrun."                # the program's scopes and host spans
DISPATCH = "psrun.dispatch"


# ---- the XSpace protobuf, read by its wire format -------------------------
# XSpace.planes = 1; XPlane: name = 2, event_metadata = 4 (map<int64,
# XEventMetadata>), stat_metadata = 5 (map<int64, XStatMetadata>);
# XEventMetadata: name = 2, stats = 5; XStatMetadata: id = 1, name = 2;
# XStat: metadata_id = 1, str_value = 5, ref_value = 7.

def _varint(buf: bytes, i: int) -> tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf: bytes):
    """``(field number, value)`` of each field of one message: an int for
    varints and fixed-width fields, bytes for length-delimited ones."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 1:
            value, i = struct.unpack_from("<q", buf, i)[0], i + 8
        elif wire == 5:
            value, i = struct.unpack_from("<i", buf, i)[0], i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield field, value


def _map_values(buf: bytes, field: int):
    for f, entry in _fields(buf):
        if f == field:
            for k, v in _fields(entry):
                if k == 2:
                    yield v


def _read(path) -> bytes:
    opener = gzip.open if str(path).endswith(".gz") else open
    with opener(path, "rb") as f:
        return f.read()


def load_op_scopes(path) -> dict:
    """``{op event name: tf_op}`` over the TPU planes of a profiler
    ``.xplane.pb`` (or ``.xplane.pb.gz``); the event name is the HLO text
    that `reduce.load_xspace` keys the ops on."""
    scopes: dict = {}
    for f, plane in _fields(_read(path)):
        if f != 1:
            continue
        name = next((v for k, v in _fields(plane) if k == 2), b"")
        if not reduce.DEVICE_PLANE.match(name.decode()):
            continue
        stat_names = {}
        for meta in _map_values(plane, 5):
            m = dict(_fields(meta))
            stat_names[m.get(1, 0)] = m.get(2, b"").decode()
        tf_op = next((i for i, n in stat_names.items() if n == "tf_op"), None)
        for meta in _map_values(plane, 4):
            ev_name, op = "", ""
            for k, v in _fields(meta):
                if k == 2:
                    ev_name = v.decode()
                elif k == 5:
                    stat = dict(_fields(v))
                    if stat.get(1, 0) == tf_op:
                        op = (stat[5].decode() if 5 in stat
                              else stat_names.get(stat.get(7), ""))
            if ev_name:
                scopes[ev_name] = op
    return scopes


# ---- stages and gaps ------------------------------------------------------
STAGE = re.compile(rf"(?:^|/){re.escape(PREFIX)}(\w+)(?=[/:]|$)")


def stage_of(tf_op: str) -> str:
    """The first ``psrun.<stage>`` component of an op's ``tf_op``
    (``jit(run)/while/body/psrun.view/ring_view/pallas_call:``), or
    ``"other"``."""
    m = STAGE.search(tf_op)
    return m.group(1) if m else "other"


def stage_times(reduced: dict, scopes: dict) -> dict:
    """Seconds of device self time per stage, averaged over the devices:
    ``{stage: s}`` with ``other`` for ops in no stage.  A stage with no
    op is left out."""
    out: dict = {}
    for name, (_, secs) in reduced["op_time"].items():
        stage = stage_of(scopes.get(name, ""))
        out[stage] = out.get(stage, 0.0) + secs / reduced["n_devices"]
    return out


def stage_ms(reduced: dict, scopes: dict, clocks: int) -> dict:
    """``{"stage_ms.<stage>": device ms a clock}`` over ``clocks``."""
    return {f"stage_ms.{s}": 1e3 * secs / clocks
            for s, secs in stage_times(reduced, scopes).items()}


def _label(gap, host) -> str:
    best, name = (0, 0), "other"
    for n, s, d in host:
        overlap = min(gap[1], s + d) - max(gap[0], s)
        if overlap > 0 and (overlap, -d) > best:
            best, name = (overlap, -d), n
    return name


def _window(events: dict, spans) -> tuple[int, int]:
    """The traced window, as `reduce.reduce_events` takes it: the first
    harness span's start to the last one's end."""
    window = [h for h in events["host"] if h[0] in spans]
    if not window or not events["devices"]:
        raise ValueError("the trace holds no harness span or no device op")
    return (min(h[1] for h in window), max(h[1] + h[2] for h in window))


def idle_gaps(events: dict, spans) -> list:
    """The longest idle gaps on the devices inside the window of the
    harness's ``spans``, ``[[label, s], ...]``: labelled by the host span
    named in ``spans`` or starting with ``psrun.`` that overlaps the gap
    most, the shortest among equals."""
    w0, w1 = _window(events, spans)
    host = [h for h in events["host"]
            if h[0] in spans or h[0].startswith(PREFIX)]
    gaps = []
    for ops in events["devices"].values():
        cover = reduce.union([s, s + d] for _, s, d in ops
                             if s < w1 and s + d > w0)
        gaps += reduce._subtract([[w0, w1]], cover)
    gaps.sort(key=lambda g: g[0] - g[1])
    return [[_label(g, host), (g[1] - g[0]) / 1e9]
            for g in gaps[:reduce.TOP]]


def dispatch_s(events: dict, spans) -> list:
    """Host seconds of each ``psrun.dispatch`` span inside the window of
    the harness's ``spans``."""
    w0, w1 = _window(events, spans)
    return [d / 1e9 for n, s, d in sorted(events["host"], key=lambda h: h[1])
            if n == DISPATCH and s >= w0 and s + d <= w1]


def summary(path, clocks: int, spans) -> dict:
    """Everything this module reads from one trace of ``clocks`` clocks."""
    events = reduce.load_xspace(path)
    red = reduce.reduce_events(events, spans)
    return {"clocks": clocks,
            "stage_ms": stage_ms(red, load_op_scopes(path), clocks),
            "busy_ms_per_clock": 1e3 * red["busy_s"] / clocks,
            "window_s": red["window_s"], "busy_s": red["busy_s"],
            "idle_gaps": idle_gaps(events, spans),
            "dispatch_s": dispatch_s(events, spans)}


# ---- a traced run of a cell ----------------------------------------------
def traced_segments(n: int, out_dir: str, spans):
    """An ``inspect`` hook for `run.run_cell`: profiles ``n`` more
    segments of the job into ``out_dir``, each dispatch and read under the
    harness's span names (``spans[0]``, ``spans[1]``)."""
    def hook(segment, state, devices):
        import jax
        import numpy as np
        jax.profiler.start_trace(out_dir)
        for _ in range(n):
            with jax.profiler.TraceAnnotation(spans[0]):
                tr, state = segment(state)
            with jax.profiler.TraceAnnotation(spans[1]):
                np.asarray(tr.loss_ref)
                np.asarray(tr.staleness)
        jax.profiler.stop_trace()
    return hook


def run_traced(workload: str, seed: int, seconds: float, keep=None) -> dict:
    from chipbench import run
    cell = run.load_cell(workload)
    devices = run.chips(cell.chips)
    run.enable_compile_cache()
    trace_dir = tempfile.mkdtemp(prefix="chipbench-stages-")
    result = run.run_cell(
        cell, seed, seconds, False, devices,
        inspect=traced_segments(run.TRACE_SEGMENTS, trace_dir, run.SPANS))
    path = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))[-1]
    clocks = run.TRACE_SEGMENTS * cell.traffic["segment_clocks"]
    out = summary(path, clocks, run.SPANS)
    if keep:
        Path(keep).mkdir(parents=True, exist_ok=True)
        dest = Path(keep) / f"{workload}.xplane.pb.gz"
        with open(path, "rb") as src, gzip.open(dest, "wb") as dst:
            shutil.copyfileobj(src, dst)
        out["kept"] = str(dest)
    shutil.rmtree(trace_dir, ignore_errors=True)
    out.update(workload=workload, seed=seed, correct=result["correct"],
               metrics=result["metrics"])
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    out = run_traced(args.workload, args.seed, args.seconds, args.keep)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
