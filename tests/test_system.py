"""End-to-end behaviour of the paper's system.

The full kEDM pipeline — per-series optimal-E, all-kNN with the fused
kernels (interpret mode), batched grouped lookups, fused-ρ CCM — run as
one workflow on a synthetic causal system, validating the paper's
qualitative claims end-to-end rather than per-module.
"""

import os
import subprocess
import sys

import numpy as np
import jax.numpy as jnp
import pytest

from repro import core
from repro.data import timeseries as ts
from repro.kernels import ops


def test_full_pipeline_kernel_path_matches_ref():
    """The whole pipeline through the Pallas kernels (interpret mode)
    reproduces the ref-path CCM skills: the portability contract."""
    x, y = ts.coupled_logistic(400, b_xy=0.0, b_yx=0.3, seed=8)
    E, tau, k = 3, 1, 4
    off = (E - 1) * tau
    xs = jnp.asarray(x)
    Y = jnp.asarray(np.stack([y, x]))

    rhos = {}
    for impl in ("ref", "interpret"):
        D = ops.pairwise_distances(xs, E=E, tau=tau, impl=impl)
        d, i = ops.topk_select(D, k=k, impl=impl)
        w = ops.make_weights(d)
        rhos[impl] = np.asarray(
            ops.lookup_rho(Y, i, w, offset=off, impl=impl))
    np.testing.assert_allclose(rhos["ref"], rhos["interpret"],
                               rtol=1e-4, atol=1e-4)
    assert rhos["ref"][1] > 0.95  # self-map sanity (library vs itself)


def test_end_to_end_causal_discovery():
    """optimal-E → grouped CCM → direction recovery, one shot."""
    x, y = ts.coupled_logistic(700, b_xy=0.0, b_yx=0.32, seed=4)
    panel = jnp.asarray(np.stack([x, y]))
    E_opt, _ = core.optimal_E_batch(panel, E_max=4)
    E_opt = np.maximum(np.asarray(E_opt), 2)
    rho = core.ccm_matrix(panel, E_opt)
    # x forces y ⇒ cross-mapping x from y's manifold (rho[1,0]) beats
    # the reverse (rho[0,1])
    assert rho[1, 0] > rho[0, 1] + 0.1, rho
    assert rho[1, 0] > 0.85


def test_tp_horizon_pipeline():
    """Tp-ahead cross-map prediction stays causal and consistent."""
    x, y = ts.coupled_logistic(500, b_xy=0.0, b_yx=0.3, seed=2)
    r0 = float(core.cross_map(jnp.asarray(y), jnp.asarray(x), E=2, Tp=0))
    r2 = float(core.cross_map(jnp.asarray(y), jnp.asarray(x), E=2, Tp=2))
    assert r0 > 0.8
    assert r2 < r0 + 0.05  # horizon can't *help*


@pytest.mark.parametrize("module", [
    "repro.edm", "repro.serving", "repro.distributed", "repro.kernels.ops"])
def test_import_starts_no_backend(module):
    """Importing the package must not claim a device: a parent process
    that imports it may still hand the chip to a child (one process per
    chip). A module-level jnp constant would start the backend."""
    prog = (f"import {module}\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge.backends_are_initialized()\n"
            "print('NO_BACKEND')")
    env = dict(os.environ, PYTHONPATH=os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src")))
    out = subprocess.run([sys.executable, "-c", prog], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "NO_BACKEND" in out.stdout
