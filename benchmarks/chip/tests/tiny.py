"""Drive a whole cell on the CPU at a tiny size, the chip check skipped.

The cell's own job, window, check and result line run as on the chip;
only its configuration and mix are shrunk (few short series, a window
of a second or two) and the program's kernels resolve to their jnp
path, as they do on any CPU.
"""

import sys
from pathlib import Path

CHIP = Path(__file__).resolve().parents[1]
ROOT = CHIP.parents[1]
for p in (str(CHIP), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import harness  # noqa: E402
import run  # noqa: E402

SEED = 2**33 + 12345  # larger than 32 signed bits hold


def shrink(cell: str):
    spec = harness.Spec(ROOT)
    entry = spec.cell(cell)
    config = dict(spec.config(entry["config"]), N=6, L=240)
    if config["edm"]["E"]:
        config["edm"] = dict(config["edm"], E=3)
    config["edm"] = dict(config["edm"], E_max=6)
    mix = dict(spec.mix(entry["traffic"]))
    if mix["job"] == "batch":
        mix.update(panels=2, warmup_panels=min(2, mix["warmup_panels"]),
                   check_calls=2, check_rows=6,
                   check_series=6 if mix["check_series"] else 0)
    else:
        mix.update(max_batch=6, warmup_s=0.3, rate_per_s=25,
                   check_requests=20)
    return config, mix


def cpu_devices(want, peaks):
    import jax

    return jax.devices()[:1]


def tiny_run(cell: str, seconds: float = 1.0, seed: int = SEED):
    """(result dict, compared numbers) of a CPU run of ``cell``."""
    config, mix = shrink(cell)
    result, compared = run.run_cell(ROOT, cell, seed, seconds, 0,
                                    config=config, mix=mix,
                                    find_chips=cpu_devices)
    return result, {c.name: c for c in compared}
