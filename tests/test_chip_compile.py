"""Compile the EDM main path for a described TPU v5e (no chip needed).

Interpret mode runs kernel bodies on the CPU and never checks what the
TPU compiler (Mosaic) enforces: (8, 128) block tiling, tile-aligned lane
slices, scalar reads from SMEM, scoped-VMEM budgets, device memory.
These tests lower and compile every main-path kernel, and the whole
batched CCM engine step, for one chip of a described ``v5e:2x2``
topology at kEDM's Table 1 series lengths: L = 1600 (Fish1_Normo) and
L = 29484 (F1). Nothing runs; a refusal here is a refusal on the chip.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and under xdist
every worker imports this file.
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import pytest

from repro.core import ccm
from repro.kernels import ref
from repro.kernels.knn_append import _master_append
from repro.kernels.knn_batch import _call as knn_batch_call
from repro.kernels.knn_multi_e import _call as multi_e_call
from repro.kernels.lookup import lookup_rho
from repro.kernels.smap_gram import _call as smap_gram_call
from repro.kernels.topk import topk_select_sizes

HBM_BYTES = 16 * 10**9  # one TPU v5e chip
E_MAX = 20  # EDMConfig default sweep depth
K_MASTER = E_MAX + 2  # session master width: E_max + 1 + slack 1
LENGTHS = [1600, 29484]  # kEDM Table 1: Fish1_Normo, F1


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("libtpu (the TPU compiler) is not installed")
    # Any other error (a locked or refused TPU library) fails the tests.
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # A compile for a described chip is written to the persistent cache
    # but can never be read back without one: keep the cache out of it.
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_ok(lowered, *, kernels=1):
    compiled = lowered.compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= kernels, "kernel not lowered"
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total <= HBM_BYTES, f"{total / 1e9:.2f} GB exceeds one v5e"
    return compiled


@pytest.mark.parametrize("L", LENGTHS)
def test_knn_batch_compiles(one_chip, L):
    E = E_MAX
    B = 8 if L < 4096 else 2
    Lp = L - (E - 1)
    _compile_ok(knn_batch_call.lower(
        _spec(one_chip, (B, L)), E=E, tau=1, k=E + 1, mx=Lp - 1,
        exclude_self=True, block=(128, 1024), interpret=False))


@pytest.mark.parametrize("L", LENGTHS)
def test_knn_multi_e_master_compiles(one_chip, L):
    ks = ref.multi_e_ks(E_MAX, K_MASTER)
    mxs = ref.multi_e_max_idx(L, E_MAX, 1, None)
    _compile_ok(multi_e_call.lower(
        _spec(one_chip, (L,)), E_max=E_MAX, tau=1, ks=ks, mxs=mxs,
        exclude_self=True, block=(128, 1024), interpret=False))


@pytest.mark.parametrize("L", LENGTHS)
def test_lookup_rho_compiles(one_chip, L):
    E = 10
    N = 154 if L < 4096 else 256
    Lp = L - (E - 1)
    _compile_ok(lookup_rho.lower(
        _spec(one_chip, (N, L)), _spec(one_chip, (Lp, E + 1), jnp.int32),
        _spec(one_chip, (Lp, E + 1)), offset=E - 1, block=(128, 128),
        interpret=False))


@pytest.mark.parametrize("L", LENGTHS)
def test_smap_gram_compiles(one_chip, L):
    _compile_ok(smap_gram_call.lower(
        _spec(one_chip, (L,)), _spec(one_chip, (1, L)), E=6, tau=1, Tp=1,
        thetas=(0.0, 0.1, 0.3, 0.5, 1.0, 2.0, 4.0, 8.0), exclude_self=True,
        block=(128, 1024), interpret=False))


@pytest.mark.parametrize("L", LENGTHS)
def test_topk_select_sizes_compiles(one_chip, L):
    E = 10
    Lp = L - (E - 1)
    caps = tuple(int(c) for c in (Lp // 8, Lp // 4, Lp // 2, Lp - 1))
    _compile_ok(topk_select_sizes.lower(
        _spec(one_chip, (Lp, Lp)), k=E + 1, max_idxs=caps,
        exclude_self=True, block=(8, 512), interpret=False))


@pytest.mark.parametrize("L", LENGTHS)
def test_master_append_compiles(one_chip, L):
    dt = 8
    L_old = L - dt
    _compile_ok(_master_append.lower(
        _spec(one_chip, (L,)), _spec(one_chip, (E_MAX, L_old, K_MASTER)),
        _spec(one_chip, (E_MAX, L_old, K_MASTER), jnp.int32), dt=dt,
        E_max=E_MAX, tau=1, block=128, interpret=False),
        kernels=2 * E_MAX)


@pytest.mark.parametrize("L", LENGTHS)
def test_ccm_group_step_compiles(one_chip, L):
    """The one-shot ``xmap`` engine launch: batched kNN + per-series ρ."""
    E = 10
    B, Nt = (22, 154) if L < 4096 else (1, 256)
    _compile_ok(ccm._group_step.lower(
        _spec(one_chip, (B, L)), _spec(one_chip, (Nt, L)), E=E, tau=1, Tp=0,
        k=E + 1, impl="pallas"), kernels=2)
