"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows and, per module, writes a
machine-readable ``BENCH_<name>.json`` artifact (same rows) to the current
directory so the perf trajectory is diffable across PRs:
  bench_knn      → paper Figs. 2–3 (all-kNN breakdown vs E)
  bench_lookup   → paper Figs. 4–5 (batched lookups, fused ρ)
  bench_ccm      → paper Table 1 (pairwise CCM, dataset-shaped)
  bench_roofline → paper Figs. 6–9 (arithmetic intensity / roofline)
  bench_esweep   → ISSUE 1 (seed per-E optimal-E sweep vs multi-E engine)
  bench_smap     → ISSUE 2 (seed per-query S-Map lstsq vs batched engine)
  bench_edm      → ISSUE 3 (session facade overhead; cached-kNN CCM reuse)
  bench_serve    → ISSUE 8 (serving: append-merge vs rebuild, batching)
"""

from __future__ import annotations

import json
import sys

from benchmarks import common
from repro import compile_cache


def main() -> None:
    compile_cache.enable()
    from benchmarks import (
        bench_ccm,
        bench_edm,
        bench_esweep,
        bench_knn,
        bench_lookup,
        bench_roofline,
        bench_serve,
        bench_smap,
    )

    mods = {
        "knn": bench_knn,
        "lookup": bench_lookup,
        "ccm": bench_ccm,
        "roofline": bench_roofline,
        "esweep": bench_esweep,
        "smap": bench_smap,
        "edm": bench_edm,
        "serve": bench_serve,
    }
    only = sys.argv[1] if len(sys.argv) > 1 else None
    print("name,us_per_call,derived")
    for name, mod in mods.items():
        if only and only != name:
            continue
        common.drain_rows()
        mod.run()
        artifact = f"BENCH_{name}.json"
        with open(artifact, "w") as f:
            json.dump({"bench": name, "rows": common.drain_rows()}, f,
                      indent=2)
            f.write("\n")


if __name__ == "__main__":
    main()
