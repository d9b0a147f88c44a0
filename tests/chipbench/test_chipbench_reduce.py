"""The chip benchmark's yardstick: the trace reduction and the work counts.

The reduction is checked on hand-made event lists, where every number can
be worked out by hand, and on a profiler trace recorded on one TPU v5e from
``mf-netflix.essp3`` (three 10-clock segments), whose numbers are pinned.
The work counts are checked against hand counts at a small shape.
"""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import peaks, reduce, work  # noqa: E402

SPANS = ("segment_dispatch", "trace_read", "threshold_check")


def test_union_and_subtract():
    assert reduce.union([[5, 7], [0, 2], [1, 3], [7, 8]]) == [[0, 3], [5, 8]]
    assert reduce._subtract([[0, 10]], [[2, 3], [5, 7]]) == \
        [[0, 2], [3, 5], [7, 10]]
    assert reduce._subtract([[0, 4], [6, 9]], [[3, 7]]) == [[0, 3], [7, 9]]


def test_self_times_charge_nested_ops_to_themselves():
    ops = [["while", 0, 100], ["fusion", 10, 20], ["fusion", 40, 20],
           ["copy", 45, 5]]
    got = reduce._self_times(ops)
    assert got == {"while": [1, 60], "fusion": [2, 35], "copy": [1, 5]}


def _events():
    host = [["segment_dispatch", 0, 10], ["trace_read", 10, 80],
            ["threshold_check", 95, 5], ["unrelated", 0, 100]]
    dev0 = [["fusion.1", 12, 30], ["all-gather.2", 42, 18],
            ["custom-call.3", 70, 10], ["outside", 200, 10]]
    dev1 = [["fusion.1", 0, 50], ["all-gather.2", 50, 30]]
    return {"devices": {"/device:TPU:0": dev0, "/device:TPU:1": dev1},
            "host": host}


def test_reduce_events_by_hand():
    red = reduce.reduce_events(_events(), SPANS)
    assert red["window_s"] == pytest.approx(100e-9)
    # TPU:0 busy [12,60) + [70,80) = 58; TPU:1 busy [0,80) = 80
    assert red["busy_s"] == pytest.approx(69e-9)
    # exposed all-gather: TPU:0 [42,60) = 18, TPU:1 [50,80) = 30
    assert red["collective_exposed_s"] == pytest.approx(24e-9)
    assert red["op_time"]["fusion.1"] == [2, pytest.approx(80e-9)]
    assert "outside" not in red["op_time"]
    assert red["top_ops"][0] == ["fusion.1", pytest.approx(40e-9)]
    # gaps: TPU:0 [0,12) [60,70) [80,100); TPU:1 [80,100)
    assert [g[1] for g in red["idle_gaps"]] == pytest.approx(
        [20e-9, 20e-9, 12e-9, 10e-9])
    assert [g[0] for g in red["idle_gaps"]] == [
        "trace_read", "trace_read", "segment_dispatch", "trace_read"]
    assert reduce.kernel_calls(red, r"custom-call") == \
        (1, pytest.approx(10e-9))


def test_reduce_needs_spans_and_ops():
    with pytest.raises(ValueError, match="no harness span"):
        reduce.reduce_events({"devices": {}, "host": []}, SPANS)


def test_work_counts_by_hand():
    # W=2 slots, P=3 producers, R=2 readers, d=5 coordinates
    assert work.ring_view(2, 3, 2, 5) == {
        "flops": 2 * 2 * 2 * 3 * 5, "bytes": 4 * (30 + 5 + 10)}
    assert work.vap_suffix_norms(2, 3, 5) == {"flops": 60, "bytes": 120}
    # n=4 users, m=2 items, k=3, P=2 workers, B=2 ratings, N=6, W=2:
    # d = 18; view 2*2*2*2*18 = 288 flops, 4*(72+18+36) = 504 bytes;
    # update 2*2*8*3 = 96 flops, 2*(2*12 + 2*2*3*4 + 18*4) = 288 bytes;
    # fold 36 flops, 4*(36+36) = 288 bytes;
    # objectives 2*6*(2*3+3) = 108 flops, 2*(6*12 + 18*4) = 288 bytes.
    assert work.mf_clock(4, 2, 3, 2, 2, 6, 2) == {
        "flops": 288 + 96 + 36 + 108, "bytes": 504 + 288 + 288 + 288}


def test_peaks_and_least_time():
    assert peaks.peaks("TPU v5 lite")["hbm_bw"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("TPU v99")
    t, bound = peaks.least_seconds(197e12, 819e9 / 2, "TPU v5 lite")
    assert (t, bound) == (pytest.approx(1.0), "flops")
    t, bound = peaks.least_seconds(0, 819e9, "TPU v5 lite", chips=4)
    assert (t, bound) == (pytest.approx(0.25), "bytes")


# ---- a trace recorded on one TPU v5e: mf-netflix.essp3, 3 x 10 clocks ----
TRACE = Path(__file__).parent / "data" / "essp3.xplane.pb.gz"


@pytest.fixture(scope="module")
def recorded():
    return reduce.reduce_events(reduce.load_xspace(TRACE), SPANS)


def test_recorded_trace_window_busy_and_gaps(recorded):
    assert recorded["n_devices"] == 1
    assert recorded["window_s"] == pytest.approx(3.123549244)
    assert recorded["busy_s"] == pytest.approx(3.107897244)
    assert recorded["collective_exposed_s"] == 0
    assert recorded["idle_gaps"][:2] == [
        ["trace_read", pytest.approx(0.005383252)],
        ["trace_read", pytest.approx(0.005118696)]]
    assert recorded["top_ops"][:3] == [
        ["custom-call %closed_call.32 f32[8,5053824]",
         pytest.approx(0.383083562)],
        ["custom-call %closed_call.33 f32[6,8]", pytest.approx(0.373114435)],
        ["fusion %multiply_reduce_fusion.20 f32[6856768]",
         pytest.approx(0.279281372)]]


def test_recorded_trace_per_layer_metrics(recorded):
    from chipbench import run
    from chipbench.apps import matfact as mfa
    cell = run.load_cell("mf-netflix.essp3")
    config = dict(cell.config, n_rows=32768)      # the size it was taken at
    ctx = run.MetricContext(
        reduced=recorded, clocks=30, compile_s=60.6, kind="TPU v5 lite",
        chips=1, work=mfa.work_counts(config, cell.traffic, config["mesh"]))
    for kernel, seconds in (("ring_view_roofline", 0.383083562),
                            ("vap_suffix_norms_roofline", 0.373114435)):
        mod = run.load_module(run.HERE / "metrics" / f"{kernel}.py")
        assert reduce.kernel_calls(recorded, mod.NAME) == \
            (30, pytest.approx(seconds))
    got = {name: run.read_metric(name, ctx)[0] for name in
           ("idle_share", "mfu.clock", "ring_view_roofline",
            "vap_suffix_norms_roofline", "compile_s")}
    assert got == {"idle_share": pytest.approx(0.50109663),
                   "mfu.clock": pytest.approx(1.85993317),
                   "ring_view_roofline": pytest.approx(9.47148244),
                   "vap_suffix_norms_roofline": pytest.approx(7.93840653),
                   "compile_s": 60.6}
