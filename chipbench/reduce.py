"""From a profiler trace to device busy time, idle gaps and op times.

``load_xspace`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into
plain lists: per TPU, the device operations ``[name, start_ns, dur_ns]``
(the ``XLA Ops`` line), and the harness's own host spans.  Both are on the
profiler's one clock.  ``reduce_events`` turns those lists into numbers:

- the traced window runs from the first host span's start to the last one's
  end;
- busy time on a device is the union of its operations' intervals inside
  the window; idle is the rest;
- each idle gap is labelled by the host span that overlaps it most
  (``"other"`` if none does);
- an operation's self time is its duration less the part its nested
  operations cover, summed per name;
- a collective's exposed time is the part of it during which no other
  operation runs on that device.
"""
from __future__ import annotations

import gzip
import re

COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute"
    r"|send|recv", re.IGNORECASE)
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
TOP = 10
# "%fusion.202 = f32[6856768,100]{1,0:T(8,128)} fusion(...), kind=..." ->
# "fusion %fusion.202 f32[6856768,100]"
HLO = re.compile(r"^(%[\w.-]+) = (.*?) ([\w-]+)\(")


def short_name(op: str) -> str:
    """An XLA op's event name is its HLO text; keep the op, its name and
    its result's shape (``(...)`` for a tuple)."""
    m = HLO.match(op)
    if not m:
        return op
    shape = "(...)" if m.group(2).startswith("(") else \
        m.group(2).split("{")[0]
    return f"{m.group(3)} {m.group(1)} {shape}"


def load_xspace(path: str) -> dict:
    """``{"devices": {plane: [[name, start_ns, dur_ns], ...]}, "host":
    [[name, start_ns, dur_ns], ...]}`` from a profiler ``.xplane.pb``."""
    from jax.profiler import ProfileData
    if str(path).endswith(".gz"):
        with gzip.open(path, "rb") as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        pd = ProfileData.from_file(str(path))
    devices, host = {}, []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = [[ev.name, int(ev.start_ns), int(ev.duration_ns)]
                   for line in plane.lines if line.name == OPS_LINE
                   for ev in line.events]
            if ops:
                devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            host += [[ev.name, int(ev.start_ns), int(ev.duration_ns)]
                     for line in plane.lines for ev in line.events]
    return {"devices": devices, "host": host}


def union(intervals) -> list:
    """Sorted, merged ``[start, end)`` intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _length(intervals) -> int:
    return sum(b - a for a, b in intervals)


def _subtract(intervals, minus) -> list:
    """``intervals`` less ``minus`` (both merged and sorted)."""
    out, j = [], 0
    for a, b in intervals:
        while j < len(minus) and minus[j][1] <= a:
            j += 1
        k, cur = j, a
        while k < len(minus) and minus[k][0] < b:
            if minus[k][0] > cur:
                out.append([cur, minus[k][0]])
            cur = max(cur, minus[k][1])
            k += 1
        if cur < b:
            out.append([cur, b])
    return out


def _self_times(ops) -> dict:
    """Per name ``[count, self_ns]``; nested operations are charged to
    themselves, not to the operation that contains them."""
    acc: dict = {}
    stack: list = []           # [end, name, self_ns]

    def close(item):
        rec = acc.setdefault(item[1], [0, 0])
        rec[0] += 1
        rec[1] += item[2]

    for name, start, dur in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][0] <= start:
            close(stack.pop())
        if stack:
            stack[-1][2] -= min(dur, stack[-1][0] - start)
        stack.append([start + dur, name, dur])
    while stack:
        close(stack.pop())
    return acc


def reduce_events(events: dict, spans) -> dict:
    """Window, busy and idle time, op self times and exposed collectives
    from `load_xspace`'s lists; times in seconds, busy and exposed
    collective time averaged over the devices."""
    host = [h for h in events["host"] if h[0] in spans]
    if not host or not events["devices"]:
        raise ValueError("the trace holds no harness span or no device op")
    w0 = min(h[1] for h in host)
    w1 = max(h[1] + h[2] for h in host)
    n_dev = len(events["devices"])
    busy = exposed = 0
    op_time: dict = {}
    gaps = []
    for ops in events["devices"].values():
        inside = [[n, max(s, w0), min(s + d, w1) - max(s, w0)]
                  for n, s, d in ops if s < w1 and s + d > w0]
        cover = union([s, s + d] for _, s, d in inside)
        busy += _length(cover)
        gaps += _subtract([[w0, w1]], cover)
        coll = union([s, s + d] for n, s, d in inside if COLLECTIVE.search(n))
        comp = union([s, s + d] for n, s, d in inside
                     if not COLLECTIVE.search(n))
        exposed += _length(_subtract(coll, comp))
        for name, (count, ns) in _self_times(inside).items():
            rec = op_time.setdefault(name, [0, 0])
            rec[0] += count
            rec[1] += ns
    gaps.sort(key=lambda g: g[0] - g[1])
    by_short: dict = {}
    for name, (_, ns) in op_time.items():
        key = short_name(name)
        by_short[key] = by_short.get(key, 0) + ns
    top = sorted(by_short.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": busy / n_dev / 1e9,
        "collective_exposed_s": exposed / n_dev / 1e9,
        "n_devices": n_dev,
        "op_time": {n: [c, ns / 1e9] for n, (c, ns) in op_time.items()},
        "top_ops": [[n, ns / n_dev / 1e9] for n, ns in top],
        "idle_gaps": [[_label(g, host), (g[1] - g[0]) / 1e9]
                      for g in gaps[:TOP]],
    }


def _label(gap, host) -> str:
    best, name = 0, "other"
    for n, s, d in host:
        overlap = min(gap[1], s + d) - max(gap[0], s)
        if overlap > best:
            best, name = overlap, n
    return name


def kernel_calls(reduced: dict, pattern: str) -> tuple[int, float]:
    """``(calls, seconds)`` over all devices of the operations whose name
    matches ``pattern``."""
    rx = re.compile(pattern)
    calls = secs = 0
    for name, (count, s) in reduced["op_time"].items():
        if rx.search(name):
            calls += count
            secs += s
    return calls, secs
