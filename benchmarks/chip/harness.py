"""Shared pieces of the chip benchmark.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, found by the name ``BENCHMARK.json`` gives
it:

* ``configs/<config>.json``: the deployment (``file`` in the config's
  entry);
* ``mixes/<traffic>.json``: the traffic's parameters; its ``job`` names
  the general driver ``job_<job>.py`` that reads them;
* ``metrics/<metric>.py``: one per-layer metric, a ``read(ctx)`` that
  returns a number, or ``None`` where the run gave it nothing to read.

This module holds what every job shares: the measured window (host
clock, backend compiles, program counters and, in a traced run, the
profiler), the chip check and the result line.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib
import importlib.util
import json
import math
import sys
import tempfile
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent


# ------------------------------------------------------------ the files


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    """Import a Python file by path (metric names hold dots)."""
    if not path.is_file():
        raise FileNotFoundError(f"no such benchmark file: {path}")
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Spec:
    """``BENCHMARK.json`` and the files it names, looked up by name."""

    def __init__(self, root: Path):
        self.root = Path(root)
        self.data = load_json(self.root / "BENCHMARK.json")

    def _named(self, key: str, name: str) -> dict:
        for entry in self.data[key]:
            if entry["name"] == name:
                return entry
        raise KeyError(f"{key} has no entry named {name!r}")

    def cell(self, name: str) -> dict:
        return self._named("workloads", name)

    def config(self, name: str) -> dict:
        return load_json(self.root / self._named("configs", name)["file"])

    @staticmethod
    def mix(traffic: str) -> dict:
        return load_json(HERE / "mixes" / f"{traffic}.json")

    @staticmethod
    def job(mix: dict):
        if not (HERE / f"job_{mix['job']}.py").is_file():
            raise FileNotFoundError(f"no driver job_{mix['job']}.py")
        return importlib.import_module(f"job_{mix['job']}")

    @staticmethod
    def reader(metric: str):
        return load_module(HERE / "metrics" / f"{metric}.py")

    def metrics_for(self, kind: str, cell: str) -> list[dict]:
        """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
        return [m for m in self.data[kind]
                if cell in m.get("workloads", [cell])]


# ------------------------------------------------------------ the chip


def find_chips(want: int, peaks: dict):
    """The devices of this run; raises ``NoChip`` unless there are at
    least ``want`` TPU chips of a kind the peak table knows."""
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:  # no backend could start
        raise NoChip(f"JAX found no device: {e}") from e
    plat = devices[0].platform
    if plat != "tpu" or len(devices) < want:
        raise NoChip(f"cell needs {want} TPU chip(s); JAX found "
                     f"{len(devices)} {plat!r} device(s)")
    kind = devices[0].device_kind
    if kind not in peaks:
        raise NoChip(f"device kind {kind!r} is not in peaks.json")
    return devices


class NoChip(RuntimeError):
    pass


class CompileClock:
    """Backend (XLA + Mosaic) compiles, from JAX's monitoring events.

    Copied from the bring-up smoke test: tracing and lowering events
    nest and would double count, so only backend compiles are taken.
    """

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.s = 0.0
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.s += duration
            self.n += 1


def counters() -> dict:
    """The program's counters, by name."""
    from repro import telemetry

    return {k: v for k, v in telemetry.metrics_snapshot().items()
            if isinstance(v, (int, float))}


# ------------------------------------------------------------ the window


@dataclasses.dataclass
class WindowReading:
    seconds: float = 0.0
    compiles: int = 0
    compile_s: float = 0.0
    counters: dict = dataclasses.field(default_factory=dict)
    trace: object = None  # trace.Trace of the window, traced runs only


class Window:
    """The measured window: host clock, compiles and counter deltas.

    In a traced run (``trace=True``) the profiler records the window and
    the program's telemetry spans are bridged into it, so host spans and
    device operations share one clock; the window itself is the host
    span ``bench.window``.
    """

    SPAN = "bench.window"

    def __init__(self, clock: CompileClock, *, trace: bool,
                 trace_dir: str | None = None):
        self.clock = clock
        self.trace = trace
        self.trace_dir = trace_dir
        self.reading = WindowReading()

    @contextlib.contextmanager
    def measure(self):
        import jax

        r = self.reading
        tmp = None
        if self.trace:
            from repro import telemetry

            telemetry.enable()
            telemetry.enable_xla_trace(True)
            tmp = self.trace_dir or tempfile.mkdtemp(prefix="chipbench-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0  # host spans, not every call
            jax.profiler.start_trace(tmp, profiler_options=opts)
        c0, n0, k0 = self.clock.s, self.clock.n, counters()
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(self.SPAN):
                yield r
        finally:
            r.seconds = time.perf_counter() - t0
            r.compiles = self.clock.n - n0
            r.compile_s = self.clock.s - c0
            k1 = counters()
            r.counters = {k: v - k0.get(k, 0) for k, v in k1.items()}
            if self.trace:
                jax.profiler.stop_trace()
                from repro import telemetry

                telemetry.enable_xla_trace(False)
                telemetry.disable()
        if self.trace:
            import devtrace

            r.trace = devtrace.Trace.from_dir(tmp, window_span=self.SPAN)
            if self.trace_dir is None:
                import shutil

                shutil.rmtree(tmp, ignore_errors=True)


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest chip, as the backend reports it."""
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in devices]
    return max(peaks)


# ------------------------------------------------------------ the outcome


@dataclasses.dataclass
class Compared:
    """One number the check compares, with its limit (value ≤ limit)."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


@dataclasses.dataclass
class Outcome:
    """What a job hands back to the harness."""

    attempted: int
    failed: int
    end_to_end: dict  # metric name -> value
    compared: list  # of Compared
    work: list  # algorithm work of the window (see metrics' op counts)
    window: WindowReading
    memory_peak_bytes: int
    extra: dict = dataclasses.field(default_factory=dict)

    @property
    def correct(self) -> bool:
        return self.failed == 0 and all(c.ok for c in self.compared)


def emit(result: dict, compared: list) -> None:
    """The compared numbers as the last lines on stderr, then the
    result as the last line on stdout (``compared`` last in it)."""
    for c in compared:
        print(f"compared {c.name} = {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAIL'}", file=sys.stderr, flush=True)
    result["compared"] = {c.name: {"value": c.value, "limit": c.limit}
                          for c in compared}
    print(json.dumps(result), flush=True)
