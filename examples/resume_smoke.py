"""Resume smoke: run → SIGTERM → resume → assert bit-identical parity.

The CI job for the fault-tolerance layer (docs/ARCHITECTURE.md "Fault
tolerance and resumable runs"): a child process runs a journaled
``EDM.xmap(run_dir=...)`` and is SIGTERM'd mid-run (the child's engine
launches are wrapped to self-deliver the signal after a fixed tile —
deterministic fault injection, no timing races), the parent asserts the
preemption ABI (exit code ``PREEMPTED_EXIT`` = 17, journal status
"preempted"), then a second child resumes and the parent asserts:

* the resumed matrix is **bit-identical** to an uninterrupted run;
* no journaled tile was recomputed (engine launch count = remaining
  tiles only).

Run: ``PYTHONPATH=src python examples/resume_smoke.py [out_dir]``

With ``out_dir``, the run dirs land at ``<out_dir>/run`` and
``<out_dir>/fresh`` instead of tempdirs — CI passes one so it can
upload ``<out_dir>/run/telemetry/events.jsonl`` as a build artifact.
The smoke also asserts the telemetry span log exists, is schema-valid,
and records both attempts of the interrupted run.
"""

import json
import os
import subprocess
import sys
import tempfile

CHILD = """
import os, signal, sys
import numpy as np, jax.numpy as jnp
from repro import compile_cache
from repro.core import ccm
from repro.data import timeseries as ts
from repro.edm import EDM, EDMConfig

mode, run_dir = sys.argv[1], sys.argv[2]
compile_cache.enable()
panel, _ = ts.forced_network_panel(8, 260, seed=21)
cfg = EDMConfig(E=3, batch_libs=2)   # 4 tiles of 2 library rows

orig = ccm._group_step
n = {"launches": 0}
def wrapped(*a, **k):
    n["launches"] += 1
    if mode == "kill" and n["launches"] == 2:
        os.kill(os.getpid(), signal.SIGTERM)   # preempt mid-run
    return orig(*a, **k)
ccm._group_step = wrapped

rho = EDM(jnp.asarray(panel), cfg).xmap(run_dir=run_dir)
np.save(os.path.join(run_dir, mode + ".npy"), rho)
print("LAUNCHES=" + str(n["launches"]))
"""


def _child(mode: str, run_dir: str) -> subprocess.CompletedProcess:
    env = dict(os.environ)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.path.join(root, "src")
    return subprocess.run([sys.executable, "-c", CHILD, mode, run_dir],
                          env=env, capture_output=True, text=True,
                          timeout=600)


def main() -> None:
    # Only the children compute, one at a time: each owns the device. The
    # parent imports the package for its constants and schema checks but
    # never starts a JAX backend (importing ``repro`` starts none).
    from repro.edm import PREEMPTED_EXIT

    if len(sys.argv) > 1:
        base = os.path.abspath(sys.argv[1])
        run = os.path.join(base, "run")
        fresh = os.path.join(base, "fresh")
        os.makedirs(run, exist_ok=True)
        os.makedirs(fresh, exist_ok=True)
    else:
        run = tempfile.mkdtemp(prefix="resume_smoke_")
        fresh = tempfile.mkdtemp(prefix="resume_smoke_ref_")

    kill = _child("kill", run)
    assert kill.returncode == PREEMPTED_EXIT, (
        f"expected exit {PREEMPTED_EXIT}, got {kill.returncode}:\n"
        f"{kill.stderr}")
    with open(os.path.join(run, "report.json")) as f:
        report = json.load(f)
    assert report["status"] == "preempted", report
    done = report["rows_done"]
    assert 0 < done < 8, report
    print(f"preempted cleanly: exit {kill.returncode}, "
          f"{done}/8 rows journaled")

    resume = _child("resume", run)
    assert resume.returncode == 0, resume.stderr
    launches = int(resume.stdout.strip().split("LAUNCHES=")[1])
    assert launches == 4 - done // 2, (
        f"resume recomputed journaled tiles: {launches} launches for "
        f"{8 - done} remaining rows")

    ref = _child("fresh", fresh)
    assert ref.returncode == 0, ref.stderr

    import numpy as np
    a = np.load(os.path.join(run, "resume.npy"))
    b = np.load(os.path.join(fresh, "fresh.npy"))
    assert np.array_equal(a, b), "resumed run is not bit-identical"
    print(f"resumed with {launches} launches (4 fresh), bit-identical")

    from repro.telemetry.schema import validate_events_file
    log = os.path.join(run, "telemetry", "events.jsonl")
    assert os.path.exists(log), "journaled run wrote no telemetry log"
    errs = validate_events_file(log)
    assert not errs, "telemetry log fails schema:\n" + "\n".join(errs)
    with open(log) as f:
        names = [json.loads(line)["name"] for line in f]
    assert "run.start" in names and "run.resume" in names, names
    print(f"telemetry log schema-valid ({len(names)} events, "
          f"both attempts recorded)")
    print("RESUME_SMOKE_OK")


if __name__ == "__main__":
    main()
