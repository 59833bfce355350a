"""Serve cell: a sound run is correct; each planted fault and the
lower-precision control are not (CPU, tiny size)."""

import numpy as np
import pytest

import tiny  # noqa: F401  (sets up the import paths)

import reference  # noqa: E402
from repro.edm import EDM  # noqa: E402

CELL = "fish1_serve_ccm"


def _wrap(monkeypatch, change):
    real = EDM.ccm_batch

    def ccm_batch(self, pairs, *, E):
        return change(real(self, pairs, E=E), pairs)

    monkeypatch.setattr(EDM, "ccm_batch", ccm_batch)


def altered(monkeypatch):
    """An answer altered where it is produced."""
    _wrap(monkeypatch, lambda rho, pairs: rho + np.float32(1e-2))


def half_left_out(monkeypatch):
    """Half of a coalesced batch left unanswered (NaN)."""
    def change(rho, pairs):
        rho = np.array(rho, np.float32)
        rho[::2] = np.nan
        return rho
    _wrap(monkeypatch, change)


def fails(monkeypatch):
    """Requests that raise instead of answering."""
    def change(rho, pairs):
        if pairs[0][1] % 2:
            raise RuntimeError("planted launch failure")
        return rho
    _wrap(monkeypatch, change)


def control(monkeypatch):
    """The reference in the program's place, its neighbour search in
    bfloat16 (the precision below the configuration's float32)."""
    import jax.numpy as jnp

    def ccm_batch(self, pairs, *, E):
        X = jnp.asarray(self.data.panel)
        c = self.config
        libs = np.asarray([p[0] for p in pairs])
        r = np.asarray(reference.skill(X[libs], X, E=int(E), tau=c.tau,
                                       Tp=c.Tp_cross, dtype=jnp.bfloat16))
        return r[np.arange(len(pairs)), [p[1] for p in pairs]]

    monkeypatch.setattr(EDM, "ccm_batch", ccm_batch)


def test_sound_run_is_correct():
    result, compared = tiny.tiny_run(CELL, seconds=1.5)
    assert result["correct"], compared
    assert result["failed"] == 0 and result["attempted"] > 10
    assert result["metrics"]["ccm_p95_ms"]["value"] > 0


@pytest.mark.parametrize("fault", [altered, half_left_out, fails, control])
def test_fault_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    result, compared = tiny.tiny_run(CELL, seconds=1.5)
    assert not result["correct"], compared
