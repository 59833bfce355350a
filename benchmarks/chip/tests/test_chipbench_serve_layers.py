"""The serving cell's host-side layer metrics: queue wait, batch
execution and engine dispatch from the program's counters, and the chip's
idle share under the spans that prepare and enqueue a batch."""

import sys
from pathlib import Path

import pytest

CHIP = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(CHIP))

import devtrace  # noqa: E402
import harness  # noqa: E402

COUNTERS = {"serve_claimed": 8, "serve_queue_wait_seconds": 0.04,
            "serve_batches": 2, "serve_exec_seconds": 0.03,
            "edm_launches": 4, "edm_dispatch_seconds": 0.002}


def _reader(name):
    return harness.load_module(CHIP / "metrics" / f"{name}.py")


def _ctx(counters=None, trace=None):
    return {"trace": trace,
            "window": harness.WindowReading(counters=dict(counters or {}))}


@pytest.mark.parametrize("name, value", [
    ("queue_wait_ms.serve", 5.0),   # 40 ms over 8 claimed requests
    ("exec_ms.serve", 15.0),        # 30 ms over 2 batches
    ("dispatch_ms.serve", 0.5),     # 2 ms over 4 launches
])
def test_counter_ratio(name, value):
    assert _reader(name).read(_ctx(COUNTERS)) == pytest.approx(value)


@pytest.mark.parametrize("name", ["queue_wait_ms.serve", "exec_ms.serve",
                                  "dispatch_ms.serve"])
@pytest.mark.parametrize("drop", ["serve_claimed", "serve_exec_seconds",
                                  "edm_dispatch_seconds"])
def test_counter_ratio_is_none_without_its_counters(name, drop):
    """A program that keeps the denominator but not the new clock (the
    parent of this metric) gives nothing, and neither does an empty
    window."""
    counters = {k: v for k, v in COUNTERS.items() if k != drop}
    needs = {"queue_wait_ms.serve": "serve_claimed",
             "exec_ms.serve": "serve_exec_seconds",
             "dispatch_ms.serve": "edm_dispatch_seconds"}[name]
    got = _reader(name).read(_ctx(counters))
    assert (got is None) == (drop == needs)
    assert _reader(name).read(_ctx({})) is None


def _trace(host, chips=1):
    # Per chip: busy 0-10, 30-40 and 70-100 ns of a 100 ns window, so
    # the idle gaps are 10-30 (20 ns) and 40-70 (30 ns).
    ops = [("k", 0, 10), ("k", 30, 40), ("k", 70, 100)]
    devices = {f"/device:TPU:{i}": list(ops) for i in range(chips)}
    return devtrace.Trace((0, 100), devices,
                          [("bench.window", 0, 100)] + host)


@pytest.mark.parametrize("chips", [1, 4])
def test_dispatch_idle_counts_gaps_under_launch_and_ccm_batch(chips):
    host = [("serve.batch", 5, 75), ("session.ccm_batch", 8, 35),
            ("engine.launch", 12, 28), ("engine.land", 36, 72)]
    tr = _trace(host, chips)
    labels = sorted(label for label, _, _ in tr.idle_gaps())
    assert labels == ["engine.land"] * chips + ["engine.launch"] * chips
    got = _reader("dispatch_idle_pct.serve").read(_ctx(trace=tr))
    assert got == pytest.approx(20.0)  # 20 of 100 ns, per chip


def test_dispatch_idle_leaves_out_other_spans():
    host = [("serve.batch", 5, 75), ("session.ccm_batch", 38, 74)]
    tr = _trace(host)
    got = _reader("dispatch_idle_pct.serve").read(_ctx(trace=tr))
    assert got == pytest.approx(30.0)  # only the 40-70 gap
    tr = _trace([("serve.batch", 5, 75), ("engine.launch", 80, 90)])
    assert _reader("dispatch_idle_pct.serve").read(_ctx(trace=tr)) == 0.0


def test_dispatch_idle_is_none_without_a_trace_or_its_spans():
    read = _reader("dispatch_idle_pct.serve").read
    assert read(_ctx(trace=None)) is None
    assert read(_ctx(trace=_trace([("serve.batch", 5, 75)]))) is None
