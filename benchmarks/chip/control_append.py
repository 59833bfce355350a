"""Readings of the control on ``fish1_serve_append``: the reference in
the program's place, one precision down, at the cell's own size and
check.

    python3 benchmarks/chip/control_append.py --seeds 11,12,13

``control.py`` reads a serve cell on its registered panel; this cell
checks answers from three library versions. So, per seed, the window's
schedule of requests is drawn as the job draws it, each request takes
the version the append clock gives it (the appends due before it), the
checked sample is drawn by ``job_serve_append.pick_checked``, and each
checked answer is the reference with its neighbour search in bfloat16
on the panel prefix of its version, compared with the float32
reference there. Each seed prints one JSON line, the reading beside
the mix's limit. The control has to fail on every seed. The
benchmark's own runs never run this.
"""

import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import harness  # noqa: E402


def readings(config, mix, seed, seconds):
    import jax.numpy as jnp
    import numpy as np

    import datagen
    import job_serve
    import job_serve_append as jsa
    import reference

    s = config["edm"]
    N, L, dt = config["N"], config["L"], mix["append_dt"]
    full = datagen.forced_network_panels(
        1, N, L + jsa.appended_samples(mix, seconds),
        seed=mix["panel_seed"])[0]
    rc = reference.rho_curves(jnp.asarray(full[:, :L]), E_max=s["E_max"],
                              tau=s["tau"], Tp=s["Tp"])
    E_opt = (np.argmax(np.asarray(rc), axis=1) + 1).astype(np.int32)
    v_first = 1 + len(jsa.append_times(mix["append_first_s"],
                                       mix["append_every_s"],
                                       mix["warmup_s"]))
    rng = np.random.default_rng([seed % 2**64, 3])
    due, libs, tgts, Es = job_serve.schedule(rng, mix["rate_per_s"],
                                             seconds, N, E_opt)
    t_app = jsa.append_times(mix["append_first_s"], mix["append_every_s"],
                             seconds)
    versions = v_first + np.searchsorted(t_app, due, side="right")
    pick, checked = jsa.pick_checked(rng, np.arange(len(due)), versions,
                                     v_first, v_first + len(t_app),
                                     mix["check_requests"])
    got = np.zeros(len(pick), np.float32)
    want = np.zeros(len(pick), np.float32)
    for v in checked:
        sel = versions[pick] == v
        args = (full[:, :L + v * dt], libs[pick][sel], tgts[pick][sel],
                Es[pick][sel], config)
        got[sel] = job_serve.reference_answers(*args, dtype=jnp.bfloat16)
        want[sel] = job_serve.reference_answers(*args)
    return job_serve.numbers(got, want), checked


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0,
                    help="window length whose requests the check samples")
    args = ap.parse_args()
    root = Path.cwd()
    sys.path.insert(0, str(root / "src"))
    spec = harness.Spec(root)
    cell = spec.cell("fish1_serve_append")
    config, mix = spec.config(cell["config"]), spec.mix(cell["traffic"])
    from repro import compile_cache

    compile_cache.enable()
    try:
        harness.find_chips(cell["chips"],
                           harness.load_json(HERE / "peaks.json")["devices"])
    except harness.NoChip as e:
        print(f"control_append.py: {e}", file=sys.stderr)
        return 2
    for seed in (int(x) for x in args.seeds.split(",")):
        nums, checked = readings(config, mix, seed, args.seconds)
        print(json.dumps({"workload": "fish1_serve_append", "seed": seed,
                          "versions": checked, "control": nums,
                          "limits": mix["limits"],
                          "fails": [k for k, v in nums.items()
                                    if not v <= mix["limits"][k]]}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
