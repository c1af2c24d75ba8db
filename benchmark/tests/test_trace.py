"""The trace reduction, on a trace recorded on the chip (two steps of
``fm8_criteo.train_fmb`` on a TPU v5 lite, PR 25: the head of its events as
``trace.dump_events`` keeps them) and on made-up events for what one chip
cannot show (collectives, two devices)."""

import json
import os

import pytest

from harness import trace

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def recorded():
    d = json.load(open(os.path.join(HERE, "recorded_trace.json")))
    return {"device": {p: [tuple(e) for e in ev] for p, ev in d["device"].items()},
            "host": [tuple(e) for e in d["host"]]}


def test_busy_union_window_and_idle_share(recorded):
    red = trace.reduce(recorded)
    (events,) = recorded["device"].values()
    total = sum(d for _, _, d in events)
    assert red["window_s"] == pytest.approx(2.214177656, rel=1e-9)
    assert red["busy_s"] == pytest.approx(2.214165402, rel=1e-9)
    assert red["busy_s"] <= red["window_s"] and red["busy_s"] <= total  # async copies overlap compute
    assert 1 - red["busy_s"] / red["window_s"] == pytest.approx(5.53e-6, rel=0.01)
    assert red["collective_s"] == 0.0 and red["collective_exposed_s"] == 0.0


def test_op_totals_name_the_op_with_its_shape(recorded):
    red = trace.reduce(recorded)
    ops = dict(red["device_ops"])
    assert len(red["device_ops"]) == 10
    assert list(ops)[:3] == ["fusion.5 f32[2555904,9]", "fusion.8 f32[67108864,9]", "fusion.7 f32[67108864,9]"]
    assert ops["fusion.5 f32[2555904,9]"] == pytest.approx(0.533295014, rel=1e-9)


def test_idle_gaps_are_named_by_the_host_span_that_covers_them(recorded):
    red = trace.reduce(recorded)
    assert red["idle_gaps"][0][0] == "np.asarray(jax.Array)"  # the loss fetch at a sync boundary
    assert sum(s for _, s in red["idle_gaps"]) == pytest.approx(red["window_s"] - red["busy_s"], rel=1e-6)


def test_collectives_and_their_exposed_part_average_over_devices():
    ev = {
        "device": {
            "d0": [("fusion.1", 0.0, 1.0), ("all-gather.2", 1.0, 0.5), ("fusion.3", 1.2, 0.1), ("fusion.4", 2.0, 1.0)],
            "d1": [("fusion.1", 0.0, 1.0), ("all-reduce.2", 1.0, 0.5), ("fusion.4", 2.0, 0.5)],
        },
        "host": [("sleep", 1.4, 0.7), ("outer", 1.0, 3.0)],
    }
    red = trace.reduce(ev)
    assert red["window_s"] == pytest.approx(3.0)
    assert red["busy_s"] == pytest.approx((2.5 + 2.0) / 2)
    assert red["collective_s"] == pytest.approx(0.5)
    assert red["collective_exposed_s"] == pytest.approx((0.4 + 0.5) / 2)  # fusion.3 hides 0.1 s on d0
    assert dict(red["device_ops"])["fusion.4"] == pytest.approx(0.75)
    assert red["idle_gaps"] == [("sleep", pytest.approx(0.5))]


def test_no_device_op_means_nothing_to_read():
    assert trace.reduce({"device": {}, "host": [("x", 0.0, 1.0)]}) is None
