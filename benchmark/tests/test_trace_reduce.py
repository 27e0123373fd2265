"""The reduction from a trace to busy time, idle share, heaviest operations
and labelled idle gaps: on a hand-made trace, and on a small trace recorded
on the chip (``fixtures/``)."""

import json
import os

import pytest

from benchmark import trace_reduce as tr

EPOCH_NS = 1_700_000_000 * 10 ** 9      # the host's clock at trace time 0


def ms(x):
    return x * 1e6                       # trace times are in ns


def hand_made():
    """Two chips.  Chip 0: ops at 10-30 ms and 20-50 ms (overlapping) and
    80-90 ms; chip 1: 10-20 ms.  Clock mark: trace 0 = EPOCH_NS."""
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [
                ["fusion.1", ms(10), ms(20)], ["fusion.2", ms(20), ms(30)],
                ["fusion.1", ms(80), ms(10)]]},
            {"name": "XLA Modules", "events": [["jit_f", ms(10), ms(80)]]}]},
        {"name": "/device:TPU:1", "lines": [
            {"name": "XLA Ops", "events": [["fusion.1", ms(10), ms(10)]]},
            {"name": "XLA Modules", "events": [["jit_f", ms(5), ms(20)]]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "bench", "events": [
                [f"{tr.CLOCK_MARK}{EPOCH_NS + int(ms(5))}", ms(5), 100.0],
                [f"{tr.CLOCK_MARK}{EPOCH_NS + int(ms(95))}", ms(95),
                 100.0]]}]}]}


def at(x_ms):
    return EPOCH_NS / 1e9 + x_ms / 1e3


def test_union_merges_overlaps():
    assert tr.union([[5, 6], [1, 3], [2, 4], [4, 4.5]]) == [[1, 4.5],
                                                            [5, 6]]
    assert tr.total(tr.clip([[1, 4], [5, 6]], 2, 5.5)) == pytest.approx(2.5)


def test_busy_is_a_union_per_device_averaged_over_devices():
    out = tr.reduce(hand_made(), at(0), at(100), [])
    assert out["devices"] == 2
    # chip 0 busy 10-50 and 80-90 = 50 ms; chip 1 busy 10 ms
    assert out["busy_s"] == pytest.approx(0.030)
    assert out["window_s"] == pytest.approx(0.100)
    assert out["idle_share"] == pytest.approx(0.70)
    # fusion.1: 20 + 10 + 10 ms over two chips; fusion.2: 30 ms
    # (ops are named under the program that was running then)
    assert out["device_ops"][0][0] == "jit_f/fusion.1"
    assert out["device_ops"][0][1] == pytest.approx(0.020)
    assert out["device_ops"][1] == ["jit_f/fusion.2", pytest.approx(0.015)]


def test_op_names_are_cut_to_name_array_and_kind():
    hlo = ("%fusion.293 = pred[65536]{0:T(1024)(128)(4,1)S(1)} fusion("
           "f32[65536]{0:T(1024)S(1)} %custom-call.4), kind=kLoop, "
           "calls=%fused_computation.754")
    assert tr.short_op(hlo) == "fusion.293 pred[65536] kLoop"
    assert tr.short_op("%fusion.95 = (f32[7,19]{1,0}, f32[7,19]{1,0}) "
                       "fusion(...), kind=kOutput") == \
        "fusion.95 f32[7,19] kOutput"
    assert tr.short_op("%custom-call = u32[]{:T(128)} custom-call(s64[] "
                       "%n)") == "custom-call u32[]"
    assert tr.short_op("fusion.1") == "fusion.1"


def test_gaps_are_labelled_by_the_most_specific_span():
    tree = {"name": "q", "kind": "query", "start": at(0), "end": at(92),
            "children": [
                {"name": "schedule", "kind": "phase", "start": at(0),
                 "end": at(9), "children": []},
                {"name": "execute", "kind": "phase", "start": at(9),
                 "end": at(92), "children": []},
                {"name": "stage-0", "kind": "stage", "start": at(45),
                 "end": at(85), "children": [
                     {"name": "t", "kind": "task", "start": at(45),
                      "end": at(85), "children": []}]}]}
    spans = tr.flatten_spans({"q1": [tree]})
    out = tr.reduce(hand_made(), at(0), at(100), spans)
    gaps = dict(out["idle_gaps"])
    # no chip runs anything in 0-10, 50-80 and 90-100 ms
    assert gaps["q1 schedule"] == pytest.approx(0.010, abs=1e-6)
    assert gaps["q1 stage-0"] == pytest.approx(0.030, abs=1e-6)
    assert gaps["no query in flight"] == pytest.approx(0.010, abs=1e-6)
    assert sum(gaps.values()) == pytest.approx(0.050, abs=1e-6)


def test_busy_within_whole_queries():
    out = tr.reduce(hand_made(), at(0), at(100), [])
    # 0-40 ms: chip 0 busy 30 ms, chip 1 busy 10 ms
    assert tr.busy_within(out, [[at(0), at(40)]]) == pytest.approx(
        0.020, abs=1e-6)
    assert tr.busy_within(out, [[at(0), at(40)], [at(30), at(85)]]) == \
        pytest.approx((50 - 10 + 5 + 10) / 2 / 1e3, abs=1e-6)


def test_the_window_clips_and_no_device_or_clock_reads_nothing():
    out = tr.reduce(hand_made(), at(25), at(85), [])
    assert out["busy_s"] == pytest.approx((25 + 5) / 2 / 1e3, abs=1e-6)
    no_device = {"planes": hand_made()["planes"][2:]}
    assert tr.reduce(no_device, at(0), at(100), []) is None
    no_clock = {"planes": hand_made()["planes"][:2]}
    assert tr.reduce(no_clock, at(0), at(100), []) is None


FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "chip_trace.json")


@pytest.mark.skipif(not os.path.exists(FIXTURE),
                    reason="no recorded trace committed")
def test_recorded_chip_trace_reduces_to_its_recorded_numbers():
    with open(FIXTURE) as f:
        fx = json.load(f)
    out = tr.reduce(fx["trace"], fx["start"], fx["end"],
                    [tuple(s) for s in fx["spans"]])
    assert out["devices"] == fx["expect"]["devices"]
    assert out["busy_s"] == pytest.approx(fx["expect"]["busy_s"])
    assert out["idle_share"] == pytest.approx(fx["expect"]["idle_share"])
    assert 0.0 < out["busy_s"] < out["window_s"]
    assert out["device_ops"] and out["idle_gaps"]
    assert out["device_ops"][0][0] == fx["expect"]["heaviest_op"]
    assert out["idle_gaps"][0][0] == fx["expect"]["longest_gap"]
