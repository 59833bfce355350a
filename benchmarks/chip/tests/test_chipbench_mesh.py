"""The four-chip cell on four virtual CPU devices (tiny size): a sound
run of the sharded path is correct, an altered one is not."""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

CHIP = Path(__file__).resolve().parents[1]
ROOT = CHIP.parents[1]
CELL = "f1_xmap_e10_4chip"

PROG = textwrap.dedent(f"""
    import json, sys
    sys.path.insert(0, {str(CHIP / "tests")!r})
    import numpy as np
    import tiny
    from repro.edm import EDM
    import jax
    assert len(jax.devices()) == 4
    def run():
        result, compared = tiny.tiny_run({CELL!r})
        result["compared"] = {{k: c.value for k, c in compared.items()}}
        return result
    sound = run()
    real = EDM.xmap
    EDM.xmap = lambda self, *a, **kw: real(self, *a, **kw) + np.float32(1e-2)
    print(json.dumps({{"sound": sound, "altered": run()}}))
""")


@pytest.fixture(scope="module")
def runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", PROG], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_sound_sharded_run_is_correct(runs):
    sound = runs["sound"]
    assert sound["correct"], sound["compared"]
    assert sound["attempted"] >= 1 and sound["failed"] == 0
    assert sound["metrics"]["pairs_per_s"]["value"] > 0
    assert sound["run"]["compiles_in_window"] == 0


def test_altered_sharded_run_is_not_correct(runs):
    assert not runs["altered"]["correct"], runs["altered"]["compared"]
