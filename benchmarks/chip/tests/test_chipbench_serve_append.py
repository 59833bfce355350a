"""Serve-and-append cell: a sound run is correct and compiles nothing in
its window; a corrupted append, an answer from the pre-append panel and
the lower-precision control are not correct (CPU, tiny size)."""

import numpy as np
import pytest

import tiny  # noqa: F401  (sets up the import paths)

import harness  # noqa: E402
import job_serve_append  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402
from repro.edm import EDM  # noqa: E402

CELL = "fish1_serve_append"
COUNTS = harness.load_module(tiny.CHIP / "metrics" / "append_gop_per_s.py")


def tiny_run(trace=0, seconds=1.5):
    """A CPU run of the cell: 6 × 240, live at capacity 384 (1.25 · 248
    to 128s), an 8-sample append every 0.5 s (one in set-up, one in the
    warm-up, three in the window)."""
    config, mix = tiny.shrink(CELL)
    config = dict(config, capacity=384)
    mix.update(append_first_s=0.25, append_every_s=0.5, drain_s=30)
    result, compared = run.run_cell(tiny.ROOT, CELL, tiny.SEED, seconds,
                                    trace, config=config, mix=mix,
                                    find_chips=tiny.cpu_devices)
    return result, {c.name: c for c in compared}


def corrupted_append(monkeypatch):
    """One sample of every appended delta altered where it lands."""
    real = EDM.append

    def append(self, delta):
        delta = np.array(delta, np.float32)
        delta[0, 0] += np.float32(0.25)
        return real(self, delta)

    monkeypatch.setattr(EDM, "append", append)
    return "append_readback_mismatch"


def stale_answers(monkeypatch):
    """Requests answered on the panel as registered, before any append."""
    real = EDM.ccm_batch

    def ccm_batch(self, pairs, *, E):
        if not hasattr(self, "_registered"):
            self._registered = EDM(np.asarray(self.data.panel), self.config)
        if self._registered.data.L == self.data.L:
            return real(self, pairs, E=E)
        return real(self._registered, pairs, E=E)

    monkeypatch.setattr(EDM, "ccm_batch", ccm_batch)
    return "rho_max_abs_diff"


def control(monkeypatch):
    """The reference in the program's place on the current panel, its
    neighbour search in bfloat16 (the precision below float32)."""
    import jax.numpy as jnp

    def ccm_batch(self, pairs, *, E):
        X = jnp.asarray(self.data.panel)
        c = self.config
        libs = np.asarray([p[0] for p in pairs])
        r = np.asarray(reference.skill(X[libs], X, E=int(E), tau=c.tau,
                                       Tp=c.Tp_cross, dtype=jnp.bfloat16))
        return r[np.arange(len(pairs)), [p[1] for p in pairs]]

    monkeypatch.setattr(EDM, "ccm_batch", ccm_batch)
    return "rho_max_abs_diff"


def test_sound_run_is_correct_and_compiles_nothing_in_its_window():
    result, compared = tiny_run(trace=1)
    assert result["correct"], compared
    assert result["failed"] == 0 and result["attempted"] > 10
    run_line = result["run"]
    assert run_line["appends_in_window"] == 3
    assert run_line["capacity"] == 384
    assert run_line["capacity_regrows_in_window"] == 0
    assert len(run_line["checked_versions"]) == 3
    layers = result["metrics"]
    assert layers["compiles_in_window.serve"]["value"] == 0
    assert layers["append_ms.serve"]["value"] > 0
    assert compared["append_readback_mismatch"].value == 0


@pytest.mark.parametrize("fault", [corrupted_append, stale_answers,
                                   control])
def test_fault_is_not_correct(fault, monkeypatch):
    failing = fault(monkeypatch)
    result, compared = tiny_run()
    assert not result["correct"], compared
    assert not compared[failing].ok, compared


def test_append_count_sums_old_rows_and_slab_per_level():
    """The old rows add no operations (their squares are carried): the
    count is the slab's alone, level by level."""
    w = {"op": "knn_append", "series": 2, "E_max": 2, "tau": 1, "dt": 3,
         "L_old": 10, "k": 4}
    level = [3 * 1 * 3 * 13, 3 * 2 * 3 * 12]
    assert COUNTS.append_ops(w) == 2 * sum(level)
    assert COUNTS.append_ops({"op": "knn"}) == 0


def test_append_time_is_the_whole_program_once():
    """The kernel and the program's other ops, nested or not, each
    second counted once; other programs' ops left out."""
    import devtrace

    ops = [("kernel:knn_append", 0, 40), ("jit_panel_master_append_sq/"
                                          "fusion", 30, 70),
           ("jit_panel_master_append_sq/copy", 100, 110),
           ("kernel:knn_append_fold", 120, 150),
           ("kernel:lookup_rho", 200, 300)]
    trace = devtrace.Trace((0, 1000), {"/device:TPU:0": ops}, [])
    assert COUNTS.append_device_s(trace) == 110 / 1e9


def test_control_readings_fail_the_limit():
    """``control_append.py`` at the tiny size: the bfloat16 reference
    on the checked versions reads above the cell's ρ limit."""
    import control_append

    config, mix = tiny.shrink(CELL)
    mix.update(append_first_s=0.25, append_every_s=0.5)
    nums, checked = control_append.readings(config, mix, tiny.SEED, 1.5)
    assert len(checked) == 3
    assert nums["rho_max_abs_diff"] > mix["limits"]["rho_max_abs_diff"]


def test_append_clock_and_samples():
    assert list(job_serve_append.append_times(0.5, 1.0, 10.0)) == [
        0.5 + i for i in range(10)]
    assert len(job_serve_append.append_times(0.5, 1.0, 0.3)) == 0
    mix = {"append_first_s": 0.5, "append_every_s": 1.0, "warmup_s": 3,
           "append_dt": 8}
    assert job_serve_append.appended_samples(mix, 10.0) == 8 * (1 + 3 + 10)
