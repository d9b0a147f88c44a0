"""Per-stage device time (``chipbench/stages.py``): the op scopes read from
a profiler trace's protobuf, the stage times and the idle-gap labels.

The reading is checked on hand-made events and a hand-encoded trace, where
every number can be worked out by hand, on the trace recorded before the
clock step named its stages (``essp3.xplane.pb.gz``: every op is
``other``), and on one recorded with the stage scopes in place
(``essp3.stages.xplane.pb.gz``), whose numbers are pinned.
"""
from __future__ import annotations

import gzip
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import reduce, stages  # noqa: E402

SPANS = ("segment_dispatch", "trace_read", "threshold_check")
DATA = Path(__file__).parent / "data"


# ---- a hand-encoded XSpace --------------------------------------------------
def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _field(num: int, value) -> bytes:
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(num << 3 | 2) + _varint(len(value)) + value


def _entry(field: int, key: int, msg: bytes) -> bytes:
    return _field(field, _field(1, key) + _field(2, msg))


def _plane(name: str, events: dict, stats: dict) -> bytes:
    """An XPlane with ``events`` ``{id: (name, stat or None)}``; a stat is
    ``(stat metadata id, str)`` or ``(stat metadata id, ref id)``."""
    body = _field(1, 7) + _field(2, name)
    for sid, sname in stats.items():
        body += _entry(5, sid, _field(1, sid) + _field(2, sname))
    for eid, (ename, stat) in events.items():
        msg = _field(1, eid) + _field(2, ename)
        if stat is not None:
            sid, value = stat
            msg += _field(5, _field(1, sid) + (
                _field(5, value) if isinstance(value, str)
                else _field(7, value)))
        body += _entry(4, eid, msg)
    return body


def test_load_op_scopes_reads_str_and_ref_stats(tmp_path):
    tpu = _plane("/device:TPU:0", {
        1: ("%fusion.1 = f32[8] fusion()", (3, "jit(run)/while/body/"
                                           "psrun.view/add:")),
        2: ("%copy.2 = f32[8] copy()", (3, 9)),      # tf_op by reference
        3: ("%constant.3 = f32[] constant(0)", None),
        4: ("%other.4 = f32[8] add()", (4, "not the tf_op")),
    }, {3: "tf_op", 4: "long_name", 9: "jit(run)/while:"})
    host = _plane("/host:CPU", {1: ("host event", (3, "jit(x)"))},
                  {3: "tf_op"})
    path = tmp_path / "t.xplane.pb.gz"
    with gzip.open(path, "wb") as f:
        f.write(_field(1, tpu) + _field(1, host) + _field(4, "hostname"))
    assert stages.load_op_scopes(path) == {
        "%fusion.1 = f32[8] fusion()": "jit(run)/while/body/psrun.view/add:",
        "%copy.2 = f32[8] copy()": "jit(run)/while:",
        "%constant.3 = f32[] constant(0)": "",
        "%other.4 = f32[8] add()": ""}


# ---- stages and gaps by hand ---------------------------------------------
@pytest.mark.parametrize("tf_op, stage", [
    ("jit(run)/while/body/psrun.view/ring_view/pallas_call:", "view"),
    ("jit(run)/while/body/psrun.record", "record"),
    ("jit(run)/while/body/psrun.push/psrun.view/x:", "push"),
    ("jit(run)/while:", "other"),
    ("jit(run)/while/body/not_psrun.view/x", "other"),
    ("", "other"),
])
def test_stage_of(tf_op, stage):
    assert stages.stage_of(tf_op) == stage


def test_stage_times_by_hand():
    # TPU:0: a loop [0,100) holding a view op [10,40) and a push op
    # [50,60); TPU:1: the view op [0,20) and an op with no scope [20,30)
    host = [["segment_dispatch", 0, 100]]
    events = {"devices": {
        "/device:TPU:0": [["while", 0, 100], ["view.1", 10, 30],
                          ["push.2", 50, 10]],
        "/device:TPU:1": [["view.1", 0, 20], ["bare.3", 20, 10]]},
        "host": host}
    scopes = {"while": "jit(run)/while:",
              "view.1": "jit(run)/while/body/psrun.view/dot:",
              "push.2": "jit(run)/while/body/psrun.push/add:"}
    red = reduce.reduce_events(events, SPANS)
    got = stages.stage_times(red, scopes)
    # view (30 + 20) / 2, push 10 / 2, other: the loop's own 60 and the
    # op without a scope 10, over two devices
    assert got == {"view": pytest.approx(25e-9), "push": pytest.approx(5e-9),
                   "other": pytest.approx(35e-9)}
    assert sum(got.values()) == pytest.approx(red["busy_s"])
    assert stages.stage_ms(red, scopes, clocks=5) == {
        "stage_ms.view": pytest.approx(5e-6),
        "stage_ms.push": pytest.approx(1e-6),
        "stage_ms.other": pytest.approx(7e-6)}


def test_idle_gaps_take_the_innermost_span():
    host = [["segment_dispatch", 0, 50], ["psrun.dispatch", 5, 40],
            ["trace_read", 50, 50], ["psrun.dispatch", 60, 10],
            ["unrelated", 0, 100], ["psrun.other", 92, 4]]
    dev = [["op", 10, 20], ["op", 40, 20], ["op", 75, 15]]
    events = {"devices": {"/device:TPU:0": dev}, "host": host}
    gaps = stages.idle_gaps(events, SPANS)
    # [0,10): dispatch span 5 vs segment_dispatch 10 -> segment_dispatch;
    # [30,40): both cover 10 -> the shorter psrun.dispatch;
    # [60,75): psrun.dispatch 10 vs trace_read 15 -> trace_read;
    # [90,100): trace_read 10 vs psrun.other 4 -> trace_read
    assert gaps == [["trace_read", pytest.approx(15e-9)],
                    ["segment_dispatch", pytest.approx(10e-9)],
                    ["psrun.dispatch", pytest.approx(10e-9)],
                    ["trace_read", pytest.approx(10e-9)]]
    assert stages.dispatch_s(events, SPANS) == [
        pytest.approx(40e-9), pytest.approx(10e-9)]


# ---- the trace recorded before the stages had names ----------------------
OLD = DATA / "essp3.xplane.pb.gz"


@pytest.fixture(scope="module")
def old_trace():
    events = reduce.load_xspace(OLD)
    return events, reduce.reduce_events(events, SPANS)


def test_every_device_op_has_its_metadata(old_trace):
    events, _ = old_trace
    scopes = stages.load_op_scopes(OLD)
    names = {n for n, _, _ in events["devices"]["/device:TPU:0"]}
    assert names <= set(scopes)
    assert sum(1 for n in names if scopes[n]) == 315
    assert len(names) == 384


def test_unnamed_program_is_all_other(old_trace):
    _, red = old_trace
    got = stages.stage_ms(red, stages.load_op_scopes(OLD), clocks=30)
    assert got == {"stage_ms.other": pytest.approx(103.5965748)}
    assert got["stage_ms.other"] == pytest.approx(1e3 * red["busy_s"] / 30)


# ---- a trace recorded with the stages named: mf-netflix.essp3, 3 x 10 ----
NAMED = DATA / "essp3.stages.xplane.pb.gz"


@pytest.fixture(scope="module")
def named_trace():
    events = reduce.load_xspace(NAMED)
    return events, reduce.reduce_events(events, SPANS)


def test_named_program_stage_times(named_trace):
    _, red = named_trace
    got = stages.stage_ms(red, stages.load_op_scopes(NAMED), clocks=30)
    assert got == {"stage_ms.enforce": pytest.approx(12.44043107),
                   "stage_ms.view": pytest.approx(15.23993117),
                   "stage_ms.update": pytest.approx(3.4779071),
                   "stage_ms.push": pytest.approx(1.21024773),
                   "stage_ms.record": pytest.approx(57.6319676),
                   "stage_ms.other": pytest.approx(13.5968745)}
    total = sum(got.values())
    assert total == pytest.approx(1e3 * red["busy_s"] / 30, rel=5e-3)
    assert total - got["stage_ms.other"] >= 0.8 * total


def test_named_program_gaps_and_dispatch(named_trace):
    events, red = named_trace
    # the runtime's spans change no label on this trace: each gap inside
    # a dispatch begins before the runtime's own span does
    assert stages.idle_gaps(events, SPANS) == red["idle_gaps"]
    assert stages.dispatch_s(events, SPANS) == [
        pytest.approx(0.003445209), pytest.approx(0.002921219),
        pytest.approx(0.00312621)]


def test_traced_segments_record_the_runtime_dispatch(tmp_path):
    """The hook profiles three segments of a small job on the CPU; each
    segment's dispatch holds the runtime's own ``psrun.dispatch`` span."""
    import jax

    from chipbench import run
    cell = run.load_cell("mf-netflix.essp3")
    cell.config = dict(cell.config, n_rows=128, n_cols=96, rank=16, batch=32,
                       density=0.2, mesh={"data": 1, "model": 1})
    cell.traffic = dict(cell.traffic, segment_clocks=4)
    res = run.run_cell(cell, 2**31 + 4099, 0.2, False, jax.devices()[:1],
                       inspect=stages.traced_segments(3, str(tmp_path),
                                                      SPANS))
    assert res["correct"]
    path, = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    host = reduce.load_xspace(path)["host"]
    outer = sorted(h for h in host if h[0] == "segment_dispatch")
    inner = sorted(h for h in host if h[0] == "psrun.dispatch")
    assert len(outer) == len(inner) == 3
    for (_, s0, d0), (_, s1, d1) in zip(outer, inner):
        assert s0 <= s1 and s1 + d1 <= s0 + d0
    events = {"devices": {"/device:TPU:0": [["op", 0, 1]]}, "host": host}
    assert len(stages.dispatch_s(events, SPANS)) == 3
