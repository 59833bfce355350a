"""Run one cell of the chip benchmark and print its result line.

From the root of a checkout, on a machine that holds the cell's chips:

    python3 benchmarks/chip/run.py --workload f1_xmap_e10 --seed 7 \\
        --seconds 10 --trace 0

The cell, its configuration and its traffic mix are found by name from
``BENCHMARK.json`` (see ``harness.py``). The run builds its panels from
``--seed``, warms up every shape the window will use (that is
``setup_s``), measures for ``--seconds``, and then checks a seeded
sample of what the window produced against the plain reference
(``reference.py``). ``--trace 0`` reports the cell's end-to-end metrics;
``--trace 1`` records the window with the profiler and reports its
per-layer metrics, with the device's busy time and a breakdown.

The last line on stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` when traced),
then ``compared``: each number the check compared with its limit, which
are also the last lines on stderr. Without a TPU, or with fewer chips
than the cell needs, it exits 2 and prints no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import harness  # noqa: E402


class Context:
    """What a job gets: its files, seed, window and the set-up clock."""

    def __init__(self, config, mix, seed, seconds, window, devices):
        self.config, self.mix = config, mix
        self.seed, self.seconds = seed, seconds
        self.window = window
        self.devices = devices
        self.setup_s = None

    def setup_done(self):
        self.setup_s = time.perf_counter() - T_START

    def memory_peak(self):
        return harness.memory_peak_bytes(self.devices)


def per_layer(spec, cell_name, outcome, config, mix):
    """The cell's per-layer metrics, each from its own reader file."""
    ctx = {"config": config, "mix": mix, "outcome": outcome,
           "window": outcome.window, "trace": outcome.window.trace}
    metrics = {}
    for m in spec.metrics_for("per_layer", cell_name):
        value = spec.reader(m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return metrics


def run_cell(root, workload, seed, seconds, trace=0, trace_dir=None, *,
             config=None, mix=None, find_chips=harness.find_chips):
    """Run one cell from the checkout at ``root``; returns the result
    dict and the compared numbers. ``config`` and ``mix`` replace the
    cell's files (tests run tiny ones), ``find_chips`` the chip check."""
    spec = harness.Spec(root)
    cell = spec.cell(workload)
    config = config or spec.config(cell["config"])
    mix = mix or spec.mix(cell["traffic"])
    job = spec.job(mix)
    peaks = harness.load_json(HERE / "peaks.json")["devices"]

    from repro import compile_cache

    cache_dir = compile_cache.enable()
    import jax

    # Cache every program, also the sub-second ones, so that a second
    # run in the same checkout compiles nothing.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = find_chips(cell["chips"], peaks)[:cell["chips"]]
    clock = harness.CompileClock()
    window = harness.Window(clock, trace=bool(trace), trace_dir=trace_dir)
    ctx = Context(config, mix, seed, seconds, window, devices)
    outcome = job.run(ctx)

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": outcome.memory_peak_bytes}
    result = {"correct": outcome.correct, "attempted": outcome.attempted,
              "failed": outcome.failed}
    if trace:
        tr = outcome.window.trace
        result["metrics"] = per_layer(spec, workload, outcome, config, mix)
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s()
        result["device"] = device
        result["breakdown"] = tr.breakdown()
    else:
        e2e = dict(outcome.end_to_end, setup_s=ctx.setup_s)
        result["metrics"] = {
            m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
            for m in spec.metrics_for("end_to_end", workload)}
        result["device"] = device
    result["run"] = dict(
        outcome.extra, window_s=outcome.window.seconds,
        compiles_in_window=outcome.window.compiles, setup_s=ctx.setup_s,
        setup_compile_s=clock.s - outcome.window.compile_s,
        compile_cache=(os.path.relpath(cache_dir, root)
                       if cache_dir.startswith(str(root)) else "env"))
    return result, outcome.compared


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the traced window's profile here")
    args = ap.parse_args(argv)

    root = Path.cwd()
    if not (root / "src" / "repro").is_dir():
        print(f"run.py: {root} holds no program (src/repro); run it from "
              f"the root of a checkout", file=sys.stderr)
        return 2
    sys.path.insert(0, str(root / "src"))
    try:
        result, compared = run_cell(root, args.workload, args.seed,
                                    args.seconds, args.trace, args.trace_dir)
    except harness.NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    print("run " + json.dumps(result["run"]), file=sys.stderr, flush=True)
    harness.emit(result, compared)
    return 0


if __name__ == "__main__":
    sys.exit(main())
