"""Find the knee of a serve cell: p50/p95 and backlog at several rates.

    python3 benchmarks/chip/sweep.py --workload fish1_serve_ccm \\
        --seed 11 --seconds 10 --rates 20,50,100,200

One process: the cell's server is set up and warmed once (as in a run),
then the cell's open loop is driven at each rate in turn for
``--seconds``. Per rate it prints one JSON line: requests, p50 and p95
latency (ms, from when each request was due), the mean requests per
executed batch, how many requests were still open when the schedule
ended, and the p95 of the last fifth of the schedule against the first
(a growing backlog shows as a last fifth far slower than the first).
The knee is the highest rate with no growing backlog; the cell's rate
is set below it, by hand, in its mix.
"""

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import harness  # noqa: E402
import job_serve  # noqa: E402


class _Ctx:
    def __init__(self, config, mix, seed):
        self.config, self.mix, self.seed = config, mix, seed


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()
    root = Path.cwd()
    sys.path.insert(0, str(root / "src"))
    spec = harness.Spec(root)
    cell = spec.cell(args.workload)
    config, mix = spec.config(cell["config"]), spec.mix(cell["traffic"])
    from repro import compile_cache, telemetry

    compile_cache.enable()
    import jax
    import numpy as np

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        harness.find_chips(cell["chips"],
                           harness.load_json(HERE / "peaks.json")["devices"])
    except harness.NoChip as e:
        print(f"sweep.py: {e}", file=sys.stderr)
        return 2
    t0 = time.perf_counter()
    srv, _, E_opt = job_serve.start(_Ctx(config, mix, args.seed))
    print(json.dumps({"setup_s": time.perf_counter() - t0,
                      "E_hist": np.bincount(E_opt).tolist()}), flush=True)
    try:
        for k, rate in enumerate(float(r) for r in args.rates.split(",")):
            rng = np.random.default_rng([args.seed, 100 + k])
            plan = job_serve.schedule(rng, rate, args.seconds,
                                      config["N"], E_opt)
            c0 = harness.counters()
            due, sub, done, _ = job_serve.drive(srv, mix["op"], *plan)
            c1 = harness.counters()
            lat = (done - due) * 1e3
            n = len(due)
            fifth = max(1, n // 5)
            batches = c1.get("serve_batches", 0) - c0.get("serve_batches", 0)
            print(json.dumps({
                "rate_per_s": rate, "requests": n,
                "p50_ms": float(np.nanmedian(lat)),
                "p95_ms": job_serve.p95(lat),
                "failed": int(np.isnan(done).sum()),
                "req_per_batch": n / batches if batches else None,
                "open_at_schedule_end": int(np.sum(done > due[-1])),
                "p95_first_fifth_ms": job_serve.p95(lat[:fifth]),
                "p95_last_fifth_ms": job_serve.p95(lat[-fifth:]),
                "generator_late_max_ms": float((sub - due).max() * 1e3)}),
                flush=True)
    finally:
        srv.close()
        telemetry.disable()
    return 0


if __name__ == "__main__":
    sys.exit(main())
