"""The reduction from a profiler trace to the per-layer metrics: the busy
union, the attribution of device operations to layers through the compiled
program's HLO, and the roofline arithmetic — on a small trace recorded on a
TPU v5e (one chunk of grow-131k, trimmed) and on hand-made events.
"""

import gzip
import json
from pathlib import Path

import pytest

import reduce_trace
import run

DATA = Path(__file__).resolve().parent / "data"


def reader(name):
    return run.load_module(run.HERE / "metrics" / f"{name}.py")


def test_busy_is_the_union_of_intervals_per_device():
    ops = [
        ("/device:TPU:0", "a", 0, 10),
        ("/device:TPU:0", "b", 5, 10),  # overlaps a
        ("/device:TPU:0", "c", 30, 5),
        ("/device:TPU:1", "a", 0, 20),
    ]
    hlo = {"a": ("activity", None, "fusion", ""), "b": ("update", None, "fusion", ""),
           "c": ("activity", None, "fusion", "")}
    reduced = reduce_trace.reduce_events(ops, [], hlo, window_s=40e-9)
    assert reduced["busy_s"] == pytest.approx((20e-9 + 20e-9) / 2)
    idle = reader("device_idle_share").read(reduced, {})
    assert idle == pytest.approx(50.0)


def test_container_ops_are_not_counted_twice():
    ops = [("/device:TPU:0", "while.1", 0, 100), ("/device:TPU:0", "f", 10, 10)]
    hlo = {"while.1": ("other", None, "while", ""), "f": ("activity", None, "fusion", "")}
    reduced = reduce_trace.reduce_events(ops, [], hlo, window_s=100e-9)
    assert [e[0] for e in reduced["events"]] == ["f"]
    assert reduced["busy_s"] == pytest.approx(10e-9)


def test_idle_gaps_are_named_by_the_host_span_they_fall_in():
    ops = [("/device:TPU:0", "f", 0, 10), ("/device:TPU:0", "g", 50, 10)]
    host = [("dispatch chunk", 0, 5), ("wait for chunk", 5, 60)]
    hlo = {"f": ("activity", None, "fusion", ""), "g": ("activity", None, "fusion", "")}
    out = reduce_trace.breakdown(reduce_trace.reduce_events(ops, host, hlo, 70e-9))
    assert out["idle_gaps"][0][0] == "wait for chunk"
    assert out["idle_gaps"][0][1] == pytest.approx(40e-9)


def test_roofline_arithmetic():
    # 1 ms of msp_update over 100 steps of 131,072 neurons at 33 bytes each
    # and 819 GB/s: the bytes bound is 0.528 ms, a share of 52.8 %.
    reduced = {"events": [["k", 0.0, 1e-3, "activity", "msp_update"]]}
    ctx = dict(steps=100, neurons=131_072, device_kind="TPU v5 lite")
    share = reader("msp_update_roofline").read(reduced, ctx)
    assert share == pytest.approx(100 * 100 * 33 * 131_072 / 8.19e11 / 1e-3)
    assert reader("msp_update_roofline").read({"events": []}, ctx) is None


def test_unknown_device_is_an_error():
    reduced = {"events": [["k", 0.0, 1e-3, "activity", "msp_update"]]}
    with pytest.raises(KeyError):
        reader("msp_update_roofline").read(reduced, dict(steps=1, neurons=1,
                                                          device_kind="TPU v9"))


def recorded():
    with gzip.open(DATA / "grow-131k.trace.json.gz", "rt") as f:
        return json.load(f)


def test_recorded_chunk_reduces_to_its_layers():
    rec = recorded()
    hlo = reduce_trace.parse_hlo(rec["hlo"])
    reduced = reduce_trace.reduce_events(rec["ops"], rec["host"], hlo, rec["window_s"])
    layers = {e[3] for e in reduced["events"]}
    kernels = {e[4] for e in reduced["events"]} - {None}
    assert {"activity", "update"} <= layers
    assert "msp_update" in kernels
    # The kernel runs in the activity step, the M2L kernel in the update.
    for name, _, _, layer, kernel in reduced["events"]:
        if kernel == "msp_update":
            assert layer == "activity"
        if kernel == "m2l_pair":
            assert layer == "update"
    ctx = dict(steps=rec["steps"], updates=1, neurons=rec["neurons"], device_kind=rec["kind"])
    for name, want in rec["metrics"].items():
        assert reader(name).read(reduced, ctx) == pytest.approx(want, rel=1e-9), name
