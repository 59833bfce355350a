"""The trace reduction, on a small recorded chip trace and by hand."""

import sys
import types
from pathlib import Path

import pytest

CHIP = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(CHIP))

import devtrace  # noqa: E402
import harness  # noqa: E402

RECORDED = Path(__file__).with_name("recorded_f1_trace.json")

# HLO texts of kernel ops as a TPU v5 lite trace names them (shortened).
KNN_BATCH_HLO = (
    "%_call.1 = (f32[1,29475,11]{2,1,0:T(8,128)S(1)}, s32[1,29475,11]) "
    "custom-call(f32[1,29568,10] %bitcast.51, f32[1,10,29696] "
    '%concatenate.1), custom_call_target="tpu_custom_call", '
    "frontend_attributes={kernel_metadata={}}")
MULTI_E_HLO = KNN_BATCH_HLO.replace("%_call.1", "%_call.6")
LOOKUP_HLO = ('%lookup_rho.2 = f32[8,8] custom-call(f32[29484,8] %bitcast), '
              'custom_call_target="tpu_custom_call"')
FUSION_HLO = ("%fusion.355 = pred[33180] fusion(pred[1580,22] %slice), "
              "kind=kLoop, calls=%fused_computation.12")


@pytest.fixture(scope="module")
def recorded():
    return devtrace.load(str(RECORDED))


def test_recorded_window_is_busy_with_knn_batch(recorded):
    assert recorded.window_s() == pytest.approx(0.4)
    assert recorded.busy_s() == pytest.approx(0.384490646, abs=1e-9)
    assert recorded.kernel_s(["knn_batch"]) == pytest.approx(0.377966805,
                                                             abs=1e-9)
    assert recorded.kernel_s(["lookup_rho"]) == pytest.approx(0.003747348,
                                                              abs=1e-9)
    top = recorded.breakdown()["device_ops"][0]
    assert top[0] == "kernel:knn_batch"


def test_recorded_knn_launches_take_what_a_probe_measured(recorded):
    # One knn_batch launch per library at L = 29484, E = 10: 74.5 ms in a
    # separate probe of the same kernel (TPU v5 lite). The recorded
    # window clips its first and last launch.
    ops = [e - s for _, s, e in next(iter(recorded.devices.values()))]
    keys = [k for k, _, _ in next(iter(recorded.devices.values()))]
    full = [d for k, d in zip(keys, ops) if k == "kernel:knn_batch"][1:-1]
    assert full and all(74e6 < d < 76e6 for d in full)


def test_recorded_idle_gaps_add_up_to_the_idle_time(recorded):
    gaps = recorded.idle_gaps()
    idle = sum(e - s for _, s, e in gaps) / 1e9
    assert idle == pytest.approx(recorded.window_s() - recorded.busy_s(),
                                 abs=1e-9)
    assert {label for label, _, _ in gaps} <= {"bench.window",
                                               "session.xmap"}


def test_union_and_self_time_of_nested_ops():
    ops = [("loop", 0, 100), ("a", 10, 30), ("b", 20, 40), ("c", 150, 160)]
    assert devtrace.union_ns([(s, e) for _, s, e in ops]) == 110
    nested = [("while", 0, 100), ("k", 10, 30), ("f", 40, 50)]
    assert devtrace.self_times(nested) == {"while": 70, "k": 20, "f": 10}


@pytest.mark.parametrize("hlo, module, key", [
    (KNN_BATCH_HLO, "jit__group_step", "kernel:knn_batch"),
    (MULTI_E_HLO, "jit_panel_master", "kernel:knn_multi_e"),
    (LOOKUP_HLO, "jit__master_group_step", "kernel:lookup_rho"),
    (FUSION_HLO, "jit_rho_curves_from_master",
     "jit_rho_curves_from_master/fusion"),
    (KNN_BATCH_HLO, "jit_something_else", "kernel:jit_something_else_call"),
])
def test_op_key(hlo, module, key):
    assert devtrace.op_key(hlo, module) == key


def test_idle_gap_takes_the_innermost_open_span():
    tr = devtrace.Trace((0, 100), {"/device:TPU:0": [("k", 0, 40),
                                                     ("k", 60, 100)]},
                        [("bench.window", 0, 100),
                         ("session.xmap", 30, 70)])
    assert tr.idle_gaps() == [("session.xmap", 40, 60)]
    assert tr.busy_s() == pytest.approx(80e-9)


def _reader(name):
    return harness.load_module(CHIP / "metrics" / f"{name}.py")


def test_metric_readers_on_the_recorded_trace(recorded):
    work = [{"op": "knn", "libs": 5, "E": 10, "Lp": 29475}]
    outcome = types.SimpleNamespace(work=work, attempted=1)
    ctx = {"trace": recorded, "outcome": outcome,
           "window": harness.WindowReading(compiles=0)}
    idle = _reader("device_idle_pct.xmap").read(ctx)
    assert idle == pytest.approx(100 * (1 - 0.384490646 / 0.4))
    gops = _reader("knn_gop_per_s").read(ctx)
    assert gops == pytest.approx(3 * 10 * 29475**2 * 5 / 0.377966805 / 1e9)
    assert _reader("lookup_gop_per_s").read(ctx) is None  # no lookup work
    assert _reader("compiles_in_window.xmap").read(ctx) == 0


def test_readers_return_nothing_without_a_trace():
    outcome = types.SimpleNamespace(work=[], attempted=0)
    ctx = {"trace": None, "outcome": outcome,
           "window": harness.WindowReading()}
    for name in ("device_idle_pct.serve", "knn_gop_per_s",
                 "lookup_gop_per_s", "launches_per_call",
                 "batch_occupancy.serve"):
        assert _reader(name).read(ctx) is None
