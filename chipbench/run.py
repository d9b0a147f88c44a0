"""Chip benchmark: one cell of ``BENCHMARK.json`` on the chips it asks for.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is made of is found by name: the cell's configuration
(``chipbench/configs/<config>.json``), its traffic
(``chipbench/traffic/<traffic>.json``), its limits
(``chipbench/cells/<workload>.json``), the app's constructor and plain reference
(``chipbench/apps/<app>.py``) and each per-layer metric's reader
(``chipbench/metrics/<metric>.py``).

A run, in one process that starts no other:

1. set-up: check for a TPU with the cell's chips (exit 3 and no result
   otherwise), turn on the compile cache (``JAX_COMPILATION_CACHE_DIR``
   where it is set, else ``<checkout>/.jax_cache``), build
   the app on the device from ``--seed``, and run the job's first two
   ``run_from`` segments (compiling their programs).  Those first clocks
   are the ones compared with the reference;
2. window: the same job runs on, segment after segment, each segment's
   ``Trace`` read back (loss, staleness), until ``--seconds`` have passed.
   A compile inside the window ends the run with an error.  With
   ``--trace 1`` the profiler records a few segments of it and the
   per-layer metrics are read from that trace;
3. once the window has closed and the peak memory is read, the program's
   state is freed and the plain reference runs the first clocks again;
   the gaps are printed beside their limits, on standard error and as the
   result line's last key.

The last line of standard output is the result:
``{"correct", "attempted", "failed", "metrics", "device", ["breakdown"],
"checks"}``.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()       # set-up is counted from here

import argparse  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
HERE = ROOT / "chipbench"
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

SPANS = ("segment_dispatch", "trace_read", "threshold_check")
# what a traffic file may set; anything else is refused, not ignored.  The
# program's other knobs keep their defaults (a worker reads its own writes,
# no persistent stragglers), which the reference assumes.
TRAFFIC_KEYS = frozenset({"model", "staleness", "window", "push_prob",
                          "straggler_prob", "segment_clocks"})
MODELS = ("bsp", "essp")
TRACE_SEGMENTS = 3       # a traced run profiles segments 1-3 of the window
# The first call of the runtime takes the freshly made state and compiles
# one program; every later call takes the state that program returns, laid
# out over the mesh, and compiles a second.  Set-up runs both, so the window
# compiles nothing, and the comparison covers both programs' clocks.
SETUP_SEGMENTS = 2


class NoChip(RuntimeError):
    """No TPU, or fewer chips than the cell asks for."""


class CompiledInWindow(RuntimeError):
    """Something compiled inside the measured window."""


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    loss_threshold: float
    end_to_end: list
    per_layer: list


def _load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(workload: str) -> Cell:
    bench = _load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json "
                         f"(have {sorted(cells)})")
    w = cells[workload]
    config = next(c for c in bench["configs"] if c["name"] == w["config"])
    own = _load_json(HERE / "cells" / f"{workload}.json")
    return Cell(
        name=workload, chips=w["chips"],
        config=_load_json(ROOT / config["file"]),
        traffic=_load_json(HERE / "traffic" / f"{w['traffic']}.json"),
        limits=own["limits"], loss_threshold=own["loss_threshold"],
        end_to_end=[m["name"] for m in bench["end_to_end"]
                    if _applies(m, workload)],
        per_layer=[m["name"] for m in bench["per_layer"]
                   if _applies(m, workload)])


def chips(n: int):
    """The first ``n`` TPU devices; raises `NoChip` off TPU or short."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform!r}")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} chips, JAX sees {len(devs)}")
    return devs[:n]


def enable_compile_cache() -> str:
    """JAX's persistent compile cache: the directory that
    ``JAX_COMPILATION_CACHE_DIR`` names, or else the fixed path
    ``<checkout>/.jax_cache``.  Every program is kept, however quickly it
    compiled."""
    import jax
    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or str(ROOT / ".jax_cache"))
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileLog:
    """JAX's own compile spans: tracing, lowering and backend compile (or
    loading the program from the persistent cache)."""

    def __init__(self):
        self.secs, self.events, self.cache_hits = 0.0, 0, 0

    def on_duration(self, event: str, duration_secs: float, **_):
        if event.startswith("/jax/core/compile/"):
            self.secs += duration_secs
            self.events += 1

    def on_event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def register(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self.on_duration)
        jax.monitoring.register_event_listener(self.on_event)


def consistency(traffic: dict):
    """The program's consistency config for a traffic mix of ``MODELS``;
    a key outside ``TRAFFIC_KEYS`` raises."""
    from repro.core.consistency import ConsistencyConfig
    unknown = sorted(set(traffic) - TRAFFIC_KEYS)
    if unknown or traffic["model"] not in MODELS:
        raise ValueError(f"traffic {traffic} is not supported: unknown "
                         f"keys {unknown}, models {MODELS}")
    knobs = {k: traffic[k] for k in
             ("model", "staleness", "push_prob", "straggler_prob",
              "window") if k in traffic}
    return ConsistencyConfig(**knobs)


def staleness_ok(staleness: np.ndarray, traffic: dict) -> bool:
    """The read rule's bound on every channel: BSP reads clock c-1, ESSP no
    older than c-s-1."""
    if traffic["model"] == "bsp":
        return bool(np.all(staleness == -1))
    return bool(np.all(staleness >= -traffic["staleness"] - 1))


@dataclass
class Segment:
    t_end: float
    bad: bool
    last_loss: float


def crossing(loss, before: float, threshold: float, start: float,
             length: float):
    """Seconds at which a segment's losses first reach ``threshold``, or
    None: the clock is found, the loss interpolated linearly between it and
    the clock before (``before`` for the segment's first clock), and the
    time linearly across the segment, ``start`` to ``start + length``."""
    below = np.flatnonzero(loss <= threshold)
    if not below.size:
        return None
    i = int(below[0])
    prev = float(loss[i - 1]) if i else before
    frac = 1.0 if prev <= loss[i] else \
        min(1.0, (prev - threshold) / (prev - float(loss[i])))
    return start + (i + frac) / len(loss) * length


def _table(state, d: int):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def table(base, uring, uclock):
        from repro.kernels.ref import RING_INVALID
        valid = (uclock > RING_INVALID).astype(uring.dtype)
        return (base + jnp.einsum("w,wqd->d", valid, uring,
                                  precision=jax.lax.Precision.HIGHEST))[:d]
    return table(state.base, state.uring, state.uclock)


def program_readings(app_mod, config: dict, traffic: dict, app, traces,
                     ring: dict, state) -> dict:
    """The readings of the program's first segments, on the host: ``ring``
    is read from the state after the first segment, the change from the
    state the next segment starts from."""
    out = {key: np.concatenate([np.asarray(getattr(t, key)) for t in traces])
           for key in ("loss_ref", "loss_view", "staleness", "forced",
                       "delivered", "intransit_inf")}
    missing = np.zeros_like(next(iter(ring.values())))
    out["ring"] = {c: ring.get(c, missing)
                   for c in app_mod.ring_clocks(traffic)}
    out["change"] = app_mod.leaf_norms(config,
                                       _table(state, app.dim) - app.x0)
    return out


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, devices,
             app_mod=None, wrap_segment=None, inspect=None) -> dict:
    """One run of ``cell``; returns the result line as a dict.

    ``app_mod`` defaults to the cell's app module; ``wrap_segment``, given
    the runtime's segment, returns the one the run drives.  A test hands in
    broken ones to see the comparison fail.  ``inspect``, where given, is
    called with the segment, the state the window ended with and the
    devices before the program is freed."""
    import jax

    from repro.launch.mesh import make_ps_mesh
    from repro.psrun import PSRuntime

    from chipbench import compare

    log = CompileLog()
    log.register()
    cfg, traffic = cell.config, cell.traffic
    app_mod = app_mod or load_module(HERE / "apps" / f"{cfg['app']}.py")
    mesh_shape = cfg["mesh"]
    if mesh_shape["data"] * mesh_shape["model"] != len(devices):
        raise ValueError(f"mesh {mesh_shape} does not use the cell's "
                         f"{len(devices)} chips")
    mesh = make_ps_mesh(devices=devices, **mesh_shape)
    K = traffic["segment_clocks"]
    cons = consistency(traffic)

    # ---- set-up: build, compile, run the job's first segments -----------
    t_start = time.perf_counter()
    app = app_mod.build_app(cfg)
    jax.block_until_ready(app.x0)
    t_app = time.perf_counter()
    fn = PSRuntime(mesh).run_fn(app, cons, K)
    segment = lambda st: fn.run_from(st, cons)  # noqa: E731
    if wrap_segment is not None:
        segment = wrap_segment(segment)
    state = fn.init_state(int(app_mod.seed32(seed)))
    firsts = []
    for i in range(SETUP_SEGMENTS):
        tr, state = segment(state)
        firsts.append(tr)
        if i == 0:
            ring = app_mod.ring_leaf_norms(cfg, state.uring, state.uclock)
    prog = program_readings(app_mod, cfg, traffic, app, firsts, ring, state)
    threshold = cell.loss_threshold
    del tr, firsts
    setup_s = time.perf_counter() - T_PROCESS
    compile_s, compile_events = log.secs, log.events
    print(f"set-up: {t_start - T_PROCESS!r} s to start (JAX, the chips), "
          f"{t_app - t_start!r} s making the data, "
          f"{T_PROCESS + setup_s - t_app!r} s in the first "
          f"{SETUP_SEGMENTS} segments; {compile_s!r} s of it compiling or "
          f"loading {log.cache_hits} programs from the cache",
          file=sys.stderr)

    # ---- window ----------------------------------------------------------
    n_trace = TRACE_SEGMENTS if trace else 0
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    traced: list = []              # first and end segment of the profile
    segments: list[Segment] = []
    hit_s = None
    last = float(prog["loss_ref"][-1])    # the loss of the clock before
    t0 = time.perf_counter()
    while True:
        if n_trace and len(segments) == 1:
            jax.profiler.start_trace(trace_dir)
            traced = [1]
        ts = time.perf_counter()
        with jax.profiler.TraceAnnotation("segment_dispatch"):
            tr, state = segment(state)
        with jax.profiler.TraceAnnotation("trace_read"):
            loss = np.asarray(tr.loss_ref)
            stale = np.asarray(tr.staleness)
        te = time.perf_counter()
        with jax.profiler.TraceAnnotation("threshold_check"):
            bad = not (np.all(np.isfinite(loss))
                       and staleness_ok(stale, traffic))
            if hit_s is None:
                hit_s = crossing(loss, last, threshold, ts - t0, te - ts)
            last = float(loss[-1])
        segments.append(Segment(te, bad, last))
        if len(traced) == 1 and len(segments) == 1 + n_trace:
            jax.profiler.stop_trace()
            traced.append(len(segments))
        if te - t0 >= seconds:
            break
    window_s = segments[-1].t_end - t0
    if len(traced) == 1:
        jax.profiler.stop_trace()
        traced.append(len(segments))
    if log.events != compile_events:
        raise CompiledInWindow(
            f"{log.events - compile_events} compile event(s) in the window")
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    attempted = len(segments)
    print(f"window: {attempted} segments of {K} clocks in {window_s!r} s; "
          f"set-up {setup_s!r} s; loss threshold {threshold!r} reached at "
          f"{hit_s!r} s; loss_ref at each segment's end "
          f"{[s.last_loss for s in segments]}", file=sys.stderr)
    failed = sum(s.bad for s in segments) + (hit_s is None)
    clocks = attempted * K
    work = app_mod.work_counts(cfg, traffic, mesh_shape)

    # ---- metrics ---------------------------------------------------------
    result = {"correct": False, "attempted": attempted, "failed": failed}
    if trace:
        from chipbench import reduce
        if not traced:
            raise ValueError("the window ran one segment: nothing was traced")
        n_traced = (traced[1] - traced[0]) * K
        path = sorted(glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb"))
        red = reduce.reduce_events(reduce.load_xspace(path[-1]), SPANS)
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = MetricContext(reduced=red, clocks=n_traced,
                            compile_s=compile_s, kind=dev.device_kind,
                            chips=len(devices), work=work)
        metrics = {}
        for name in cell.per_layer:
            value, unit = read_metric(name, ctx)
            if value is not None:
                metrics[name] = {"value": value, "unit": unit}
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        result["breakdown"] = {"device_ops": red["top_ops"],
                               "idle_gaps": red["idle_gaps"]}
    else:
        e2e = {"setup_s": (setup_s, "s"),
               "samples_per_s": (clocks * work["samples_per_clock"]
                                 / window_s, "samples/s"),
               "time_to_loss_s": (hit_s if hit_s is not None else window_s,
                                  "s")}
        metrics = {name: {"value": e2e[name][0], "unit": e2e[name][1]}
                   for name in cell.end_to_end}
    result["metrics"] = metrics
    result["device"] = device

    if inspect is not None:
        inspect(segment, state, devices)

    # ---- correct: free the program, run the reference --------------------
    del tr, state, segment, fn, app
    gc.collect()
    t_ref = time.perf_counter()
    ref = app_mod.reference(cfg, traffic, seed, SETUP_SEGMENTS * K)
    print(f"reference: {SETUP_SEGMENTS * K} clocks in "
          f"{time.perf_counter() - t_ref!r} s", file=sys.stderr)
    ok, checks = compare.judge(compare.gaps(prog, ref), cell.limits)
    result["correct"] = ok
    result["checks"] = checks
    return result


@dataclass
class MetricContext:
    """What a per-layer metric's reader may read."""
    reduced: dict          # chipbench.reduce.reduce_events of the trace
    clocks: int            # clocks run in the traced segments
    compile_s: float       # JAX's compile spans during set-up
    kind: str              # device_kind, for the peaks
    chips: int
    work: dict             # the app's work counts (chipbench.work)


def read_metric(name: str, ctx: MetricContext):
    """``(value, unit)`` from ``chipbench/metrics/<name>.py``; the value is
    None where the reader found nothing to read."""
    mod = load_module(HERE / "metrics" / f"{name}.py")
    return mod.read(ctx), mod.UNIT


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chipbench: the system under test (src/repro) is not in "
              f"{ROOT}; nothing was run", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    # the TPU runtime would log under a fixed /tmp path otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    cell = load_cell(args.workload)
    try:
        devices = chips(cell.chips)
    except NoChip as e:
        print(f"chipbench: {e}; nothing was run", file=sys.stderr)
        return 3
    enable_compile_cache()
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      devices)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
