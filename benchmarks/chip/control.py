"""Readings of the control: the reference in the program's place, one
precision down, at a cell's own size and check.

    python3 benchmarks/chip/control.py --workload f1_xmap_e10 \\
        --seeds 11,12,13

The cell's configuration states float32. The control is the plain
reference (``reference.py``) with its neighbour search in bfloat16 —
the step a faster kernel would be tempted by — answering exactly what
the cell's check compares: for a batch cell the sampled library rows
(and ρ(E) series) of ``check_calls`` calls on the seed's panels, for the
serve cell ``check_requests`` requests of the seed's schedule. Each
seed prints one JSON line with every compared number, the control's
reading against the float32 reference beside the mix's limit. The
control has to fail at least one of them on every seed; its smallest
reading over the seeds is the upper end a limit is set below. The
benchmark's own runs never run this.
"""

import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import harness  # noqa: E402


def batch_readings(config, mix, seed):
    import jax.numpy as jnp
    import numpy as np

    import job_batch
    import reference

    s = config["edm"]
    N = config["N"]
    panels = job_batch.make_panels(config, mix, seed)
    rng = np.random.default_rng([seed % 2**64, 1])
    worst: dict = {}
    for p in range(min(mix["check_calls"], len(panels))):
        X = jnp.asarray(panels[p])
        sample = job_batch.draw_sample(rng, N, mix,
                                       "optimal_E" in mix["steps"])
        rows = sample["rows"]
        out = {"rho": np.full((N, N), np.nan, np.float32)}
        if "optimal_E" in mix["steps"]:
            # The control's own optimal E for every series, from its
            # bfloat16 ρ(E); the sampled curves are what is compared.
            rc = reference.rho_curves(X, E_max=s["E_max"], tau=s["tau"],
                                      Tp=s["Tp"], dtype=jnp.bfloat16)
            out["E_opt"] = (np.argmax(rc, axis=1) + 1).astype(np.int32)
            out["rho_E"] = rc
        else:
            out["E_opt"] = np.full(N, s["E"], np.int32)
        for E in sorted({int(e) for e in out["E_opt"]}):
            tgt = np.flatnonzero(out["E_opt"] == E)
            r = np.asarray(reference.skill(X[rows], X, E=E, tau=s["tau"],
                                           Tp=s["Tp_cross"],
                                           dtype=jnp.bfloat16))
            out["rho"][np.ix_(rows, tgt)] = r[:, tgt]
        ref = job_batch.reference_answers(panels[p], out, sample, config)
        for k, v in job_batch.numbers(out, ref, sample).items():
            worst[k] = max(worst.get(k, 0.0), v)
    return worst


def serve_readings(config, mix, seed, seconds):
    import jax.numpy as jnp
    import numpy as np

    import datagen
    import job_serve
    import reference

    s = config["edm"]
    N, L = config["N"], config["L"]
    panel = datagen.forced_network_panels(1, N, L,
                                          seed=mix["panel_seed"])[0]
    rc = reference.rho_curves(jnp.asarray(panel), E_max=s["E_max"],
                              tau=s["tau"], Tp=s["Tp"])
    E_opt = (np.argmax(rc, axis=1) + 1).astype(np.int32)
    rng = np.random.default_rng([seed % 2**64, 3])
    due, libs, tgts, Es = job_serve.schedule(rng, mix["rate_per_s"],
                                             seconds, N, E_opt)
    pick = np.sort(rng.choice(len(due), min(len(due),
                                            mix["check_requests"]),
                              replace=False))
    args = (panel, libs[pick], tgts[pick], Es[pick], config)
    got = job_serve.reference_answers(*args, dtype=jnp.bfloat16)
    want = job_serve.reference_answers(*args)
    return job_serve.numbers(got, want)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0,
                    help="window length whose requests a serve check samples")
    args = ap.parse_args()
    root = Path.cwd()
    sys.path.insert(0, str(root / "src"))
    spec = harness.Spec(root)
    cell = spec.cell(args.workload)
    config, mix = spec.config(cell["config"]), spec.mix(cell["traffic"])
    from repro import compile_cache

    compile_cache.enable()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        harness.find_chips(cell["chips"],
                           harness.load_json(HERE / "peaks.json")["devices"])
    except harness.NoChip as e:
        print(f"control.py: {e}", file=sys.stderr)
        return 2
    for seed in (int(x) for x in args.seeds.split(",")):
        if mix["job"] == "batch":
            nums = batch_readings(config, mix, seed)
        else:
            nums = serve_readings(config, mix, seed, args.seconds)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": nums, "limits": mix["limits"],
                          "fails": [k for k, v in nums.items()
                                    if not v <= mix["limits"][k]]}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
