"""Batch job: whole EDM calls on seeded panels, back to back.

Mix parameters (``mixes/<traffic>.json`` with ``"job": "batch"``):

* ``steps``: the session methods of one call, in order: ``["xmap"]``,
  or ``["optimal_E", "xmap"]`` (kEDM's ``edim`` then ``xmap``);
* ``panels``: distinct panels, cycled call by call; each call binds a
  fresh ``EDM`` session, so nothing is reused by value. They are made
  from ``--seed``, or, where the mix gives a ``panel_seed``, from that:
  a per-target-E xmap compiles one program per E-group size, which the
  data decides, so panels drawn per run would change the work (and what
  set-up compiles) from seed to seed; ``--seed`` then only orders the
  panels and draws the check;
* ``warmup_panels``: panels called once each in set-up (every shape the
  window will meet; the E-grouping of a per-target-E xmap depends on
  the data, so such a mix warms every panel);
* ``check_calls``, ``check_rows``, ``check_series``: how much of the
  window's output the reference recomputes: up to ``check_calls`` calls
  drawn from the seed, in each ``check_rows`` library rows of ρ (every
  target) and, with ``optimal_E``, ``check_series`` series' ρ(E);
* ``limits``: the limit of each compared number.

The window runs whole cycles over the panels until ``--seconds`` have
passed, so every seed does the same work (panels differ in cost where
their E-grouping differs). End to end: ``pairs_per_s``, the cross-map
skills of the window's calls (N² per ``xmap``) over its wall time.
"""

from __future__ import annotations

import time

import numpy as np

import datagen
import harness
import reference


def session_config(config: dict, mesh=None):
    from repro.edm import EDMConfig

    s = config["edm"]
    return EDMConfig(E=s.get("E"), E_max=s["E_max"], tau=s["tau"],
                     Tp=s["Tp"], Tp_cross=s["Tp_cross"], mesh=mesh)


def make_mesh(config: dict):
    layout = config.get("mesh")
    if not layout:
        return None
    from repro.distributed import make_ccm_mesh

    return make_ccm_mesh(tuple(layout["shape"]), tuple(layout["axes"]))


def call_work(config: dict, steps, E_opt) -> list[dict]:
    """The algorithm's work in one call, from shapes (for op counts)."""
    s = config["edm"]
    N, L, tau = config["N"], config["L"], s["tau"]
    work = []
    if "optimal_E" in steps:
        work.append({"op": "knn_multi_e", "series": N, "E_max": s["E_max"],
                     "L": L, "tau": tau})
        for E in range(1, s["E_max"] + 1):
            Lp = L - (E - 1) * tau
            work.append({"op": "lookup", "libs": N, "targets": 1, "k": E + 1,
                         "rows": Lp - s["Tp"]})
    groups = np.bincount(np.asarray(E_opt), minlength=1)
    for E in np.flatnonzero(groups):
        Lp = L - (int(E) - 1) * tau
        if "optimal_E" not in steps:
            work.append({"op": "knn", "libs": N, "E": int(E), "Lp": Lp})
        work.append({"op": "lookup", "libs": N, "targets": int(groups[E]),
                     "k": int(E) + 1, "rows": Lp - s["Tp_cross"]})
    return work


def one_call(panel, cfg, steps) -> dict:
    from repro.edm import EDM

    sess = EDM(panel, cfg)
    out = {}
    if "optimal_E" in steps:
        out["E_opt"], out["rho_E"] = sess.optimal_E()
    out["rho"] = sess.xmap()
    if "E_opt" not in out:
        out["E_opt"] = np.full(panel.shape[0], cfg.E, np.int32)
    return out


def reference_answers(panel, out, sample, config, dtype=None) -> dict:
    """The reference's ρ rows (and ρ(E)) for one call's sampled part.

    ``out`` is what the program answered: its ρ rows are recomputed at
    the program's own per-target E (E_opt), which the ρ(E) check
    judges separately.
    """
    import jax.numpy as jnp

    dtype = jnp.float32 if dtype is None else dtype
    s = config["edm"]
    X = jnp.asarray(panel)
    rows = sample["rows"]
    E_opt = np.asarray(out["E_opt"])
    ref = {"rho": np.zeros((len(rows), panel.shape[0]), np.float32)}
    for E in sorted({int(e) for e in E_opt}):
        tgt = np.flatnonzero(E_opt == E)
        r = np.asarray(reference.skill(X[rows], X, E=E, tau=s["tau"],
                                       Tp=s["Tp_cross"], dtype=dtype))
        ref["rho"][:, tgt] = r[:, tgt]
    if "rho_E" in out:
        ref["rho_E"] = reference.rho_curves(
            X[sample["series"]], E_max=s["E_max"], tau=s["tau"], Tp=s["Tp"],
            dtype=dtype)
    return ref


def numbers(out, ref, sample) -> dict:
    """The compared numbers of one call: program (or control) ``out``
    against the reference ``ref`` on the sampled rows and series."""
    got = np.asarray(out["rho"])[sample["rows"]]
    nums = {"rho_max_abs_diff": float(np.max(np.abs(got - ref["rho"])))}
    if "rho_E" in ref:
        ser = sample["series"]
        curve = np.asarray(out["rho_E"])[ser]
        nums["rho_E_max_abs_diff"] = float(
            np.max(np.abs(curve - ref["rho_E"])))
        # How far the program's E_opt falls short of the reference's best
        # ρ(E): 0 where they agree, small where two E's nearly tie.
        E_prog = np.asarray(out["E_opt"])[ser]
        at = ref["rho_E"][np.arange(len(ser)), E_prog - 1]
        nums["E_opt_gap"] = float(np.max(ref["rho_E"].max(axis=1) - at))
    # A NaN anywhere fails the comparison.
    return {k: (v if np.isfinite(v) else float("inf"))
            for k, v in nums.items()}


def draw_sample(rng, N: int, mix: dict, has_curves: bool) -> dict:
    sample = {"rows": np.sort(rng.choice(N, mix["check_rows"],
                                         replace=False))}
    if has_curves:
        sample["series"] = np.sort(rng.choice(N, mix["check_series"],
                                              replace=False))
    return sample


def make_panels(config: dict, mix: dict, seed: int) -> np.ndarray:
    """The run's panels, in the order its calls take them."""
    panels = datagen.forced_network_panels(
        mix["panels"], config["N"], config["L"],
        seed=mix.get("panel_seed", seed))
    if "panel_seed" in mix:
        order = np.random.default_rng([seed % 2**64, 4]).permutation(
            len(panels))
        panels = panels[order]
    return panels


def run(ctx) -> harness.Outcome:
    config, mix = ctx.config, ctx.mix
    steps = tuple(mix["steps"])
    N = config["N"]
    panels = make_panels(config, mix, ctx.seed)
    cfg = session_config(config, make_mesh(config))
    for p in range(mix["warmup_panels"]):
        one_call(panels[p], cfg, steps)
    ctx.setup_done()

    outs = []
    with ctx.window.measure() as reading:
        t0 = time.perf_counter()
        p = 0
        while True:
            outs.append((p, one_call(panels[p], cfg, steps)))
            p = (p + 1) % len(panels)
            if p == 0 and time.perf_counter() - t0 >= ctx.seconds:
                break
    mem = ctx.memory_peak()

    pairs = N * N * len(outs)
    work = [w for _, o in outs for w in call_work(config, steps, o["E_opt"])]
    rng = np.random.default_rng([ctx.seed % 2**64, 1])
    picked = np.sort(rng.choice(len(outs), min(len(outs), mix["check_calls"]),
                                replace=False))
    worst: dict = {}
    for c in picked:
        p, out = outs[c]
        sample = draw_sample(rng, N, mix, "rho_E" in out)
        ref = reference_answers(panels[p], out, sample, config)
        for k, v in numbers(out, ref, sample).items():
            worst[k] = max(worst.get(k, 0.0), v)
    compared = [harness.Compared(k, v, float(mix["limits"][k]))
                for k, v in worst.items()]
    return harness.Outcome(
        attempted=len(outs), failed=0,
        end_to_end={"pairs_per_s": pairs / reading.seconds},
        compared=compared, work=work, window=reading,
        memory_peak_bytes=mem, extra={"checked_calls": len(picked)})
