"""The trace reduction on a small recorded trace (hand-checkable)."""

import json
from pathlib import Path

import pytest

import trace_reduce as tr

PLANES = json.loads((Path(__file__).parent / "data" / "small_trace.json").read_text())


def test_busy_is_the_union_of_xla_ops_over_the_traced_span():
    red = tr.reduce_trace(PLANES)
    # ops: while 1000-5000 (contains two), copy 8000-9000 -> busy 5000 ns of
    # the 9000 ns from the first event (the host's, at 0, while the device
    # still idles) to the last one's end.
    assert red["chips"] == 1
    assert red["busy_s"] == pytest.approx(5000e-9)
    assert red["window_s"] == pytest.approx(9000e-9)
    assert red["idle_share"] == pytest.approx(4 / 9)


def test_operations_get_their_self_time_and_short_names():
    ops = dict(tr.reduce_trace(PLANES)["device_ops"])
    assert ops["convolution.2"] == pytest.approx(2000e-9)
    assert ops["fusion.1"] == pytest.approx(1500e-9)
    assert ops["while.7"] == pytest.approx(500e-9)  # 4000 less its body's 3500
    assert ops["copy.3"] == pytest.approx(1000e-9)
    assert all(" = " not in name for name in ops)


def test_the_longest_gap_is_named_by_what_the_host_was_doing():
    gaps = tr.reduce_trace(PLANES)["idle_gaps"]
    # the gap between the two operations, then the span's head, in which
    # the host dispatches the epoch and the device has not started
    assert gaps == [["host:PjitFunction(dynamic_slice)", pytest.approx(3000e-9)],
                    ["host:PjitFunction(packed_train_epoch)", pytest.approx(1000e-9)]]


def test_a_gap_no_host_event_covers_is_named_by_its_neighbours():
    planes = [p for p in PLANES if not p["name"].startswith("/host:")]
    (name, sec), = tr.reduce_trace(planes)["idle_gaps"]
    assert name == "between:convolution.2|copy.3" and sec == pytest.approx(3000e-9)


def test_a_trace_without_device_operations_reduces_to_nothing():
    assert tr.reduce_trace([PLANES[0]]) is None
    assert tr.reduce_trace([]) is None


def test_busy_is_averaged_over_chips():
    second = json.loads(json.dumps(PLANES[1]))
    second["name"] = "/device:TPU:1"
    second["lines"][1]["events"] = [["%copy.9 = f32[] copy()", 1000, 1000]]
    red = tr.reduce_trace(PLANES + [second])
    assert red["chips"] == 2
    assert red["busy_s"] == pytest.approx((5000e-9 + 1000e-9) / 2)
