"""Batch cells: a sound run is correct; each planted fault and the
lower-precision control are not (CPU, tiny size)."""

import numpy as np
import pytest

import tiny  # noqa: F401  (sets up the import paths)

import job_batch  # noqa: E402
import reference  # noqa: E402
from repro.edm import EDM  # noqa: E402

CELLS = ["f1_xmap_e10", "fish1_edim_xmap"]


def _wrap_xmap(monkeypatch, change):
    real = EDM.xmap

    def xmap(self, *a, **kw):
        return change(real(self, *a, **kw))

    monkeypatch.setattr(EDM, "xmap", xmap)


def altered(monkeypatch):
    """An answer altered where it is produced."""
    _wrap_xmap(monkeypatch, lambda rho: rho + np.float32(1e-2))


def half_left_out(monkeypatch):
    """Half of the library batch left out of the matrix."""
    def change(rho):
        rho = rho.copy()
        rho[::2] = 0.0
        return rho
    _wrap_xmap(monkeypatch, change)


def state_unchanged(monkeypatch):
    """A call that returns the previous call's state (a stale matrix)."""
    last = {}

    def change(rho):
        prev = last.get("rho", rho)
        last["rho"] = rho
        return prev
    _wrap_xmap(monkeypatch, change)


def control(monkeypatch):
    """The reference in the program's place, its neighbour search in
    bfloat16 (the precision below the configuration's float32)."""
    import jax.numpy as jnp

    def one_call(panel, cfg, steps):
        X = jnp.asarray(panel)
        out = {}
        if "optimal_E" in steps:
            rc = reference.rho_curves(X, E_max=cfg.E_max, tau=cfg.tau,
                                      Tp=cfg.Tp, dtype=jnp.bfloat16)
            out["E_opt"] = (np.argmax(rc, axis=1) + 1).astype(np.int32)
            out["rho_E"] = rc
        else:
            out["E_opt"] = np.full(panel.shape[0], cfg.E, np.int32)
        rho = np.zeros((panel.shape[0],) * 2, np.float32)
        for E in sorted(set(out["E_opt"].tolist())):
            tgt = np.flatnonzero(out["E_opt"] == E)
            r = np.asarray(reference.skill(X, X, E=E, tau=cfg.tau,
                                           Tp=cfg.Tp_cross,
                                           dtype=jnp.bfloat16))
            rho[:, tgt] = r[:, tgt]
        out["rho"] = rho
        return out

    monkeypatch.setattr(job_batch, "one_call", one_call)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    result, compared = tiny.tiny_run(cell)
    assert result["correct"], compared
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert result["metrics"]["pairs_per_s"]["value"] > 0
    assert result["metrics"]["setup_s"]["value"] > 0
    assert list(result)[:5] == ["correct", "attempted", "failed", "metrics",
                                "device"]


@pytest.mark.parametrize("fault", [altered, half_left_out, state_unchanged,
                                   control])
@pytest.mark.parametrize("cell", CELLS)
def test_fault_is_not_correct(cell, fault, monkeypatch):
    fault(monkeypatch)
    result, compared = tiny.tiny_run(cell)
    assert not result["correct"], compared
