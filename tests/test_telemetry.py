"""Unified telemetry layer (ISSUE 7): span stack + sinks, the metrics
registry and its Prometheus export, artifact schema validation, the
configurable straggler threshold, resume lineage in run reports, and the
end-to-end acceptance path (journaled xmap → JSONL span log + metrics in
report.json + run inspector)."""

import json
import os
import signal

import numpy as np
import jax.numpy as jnp
import pytest

from repro import telemetry
from repro.core import ccm
from repro.data import timeseries as ts
from repro.distributed.fault import StragglerMonitor
from repro.edm import EDM, EDMConfig, PREEMPTED_EXIT, run_key
from repro.edm import inspect as edm_inspect
from repro.telemetry import schema


def _panel(n=6, steps=220, seed=3):
    panel, _ = ts.forced_network_panel(n, steps, seed=seed)
    return jnp.asarray(panel)


# ------------------------------------------------------- spans + sinks


def test_span_disabled_is_shared_noop():
    """The default path: no sinks, not enabled → the SAME no-op object
    every call (no per-call allocation), and events vanish silently.
    Doubles as the suite's sink-hygiene guard: a failure here means an
    earlier test leaked a sink (e.g. a MatrixRunner never closed)."""
    assert not telemetry.active(), \
        f"leaked sinks: {telemetry._sinks} enabled: {telemetry._enabled}"
    s1, s2 = telemetry.span("a", x=1), telemetry.span("b")
    assert s1 is s2
    with s1:
        assert telemetry.current_span_path() == ""
    telemetry.event("nobody.listening", x=1)  # must not raise


def test_span_nesting_builds_paths_and_durations():
    with telemetry.record() as rec:
        with telemetry.span("outer", a=1) as sp:
            assert telemetry.current_span_path() == "outer"
            with telemetry.span("inner"):
                assert telemetry.current_span_path() == "outer/inner"
                telemetry.event("tick", n=3)
            sp.annotate(b=2)
        assert telemetry.current_span_path() == ""
    inner, outer = rec.spans("inner")[0], rec.spans("outer")[0]
    assert inner["path"] == "outer/inner" and outer["path"] == "outer"
    assert inner["dur_s"] >= 0 and outer["dur_s"] >= inner["dur_s"]
    assert outer["attrs"] == {"a": 1, "b": 2}
    ev = rec.events_named("tick")[0]
    assert ev["path"] == "outer/inner" and ev["attrs"] == {"n": 3}
    # every record is schema-valid as emitted
    for e in rec.events:
        assert schema.validate_event(e) == []


def _devtrace():
    """The chip benchmark's trace reduction (it reads the bridged spans)."""
    import importlib.util
    import pathlib
    path = (pathlib.Path(__file__).resolve().parents[1] / "benchmarks"
            / "chip" / "devtrace.py")
    spec = importlib.util.spec_from_file_location("devtrace", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_xla_bridge_names_nested_spans_by_bare_name(monkeypatch):
    """Every span reaches the profiler under its bare name (nested ones
    too, attributes left out), which the trace reduction's span pattern
    accepts; sinks still get the full path."""
    import jax

    names = []

    class RecordingAnnotation:
        def __init__(self, name, **kwargs):
            names.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", RecordingAnnotation)
    telemetry.enable_xla_trace(True)
    try:
        with telemetry.record() as rec:
            EDM(_panel(), EDMConfig(E=3, batch_libs=2)).xmap()
    finally:
        telemetry.enable_xla_trace(False)
    assert {"session.xmap", "engine.drive", "engine.launch",
            "engine.land"} <= set(names)
    pattern = _devtrace().TELEMETRY_SPAN
    assert all(pattern.match(n) for n in names), names
    assert names.count("engine.launch") == names.count("engine.land") == 3
    land = rec.spans("engine.land")[0]
    assert land["path"] == "session.xmap/engine.drive/engine.land"
    assert land["attrs"] == {"a": 0, "b": 2}


def test_enable_activates_without_sinks():
    telemetry.enable()
    try:
        assert telemetry.active()
        assert telemetry.span("x") is not telemetry.span("x")
    finally:
        telemetry.disable()
    assert not telemetry.active()


def test_recorder_counter_deltas_ignore_prior_history():
    telemetry.counter("t_prior").inc(7)
    with telemetry.record() as rec:
        telemetry.counter("t_prior").inc(2)
        telemetry.counter("t_fresh").inc()
    assert rec.counter_delta("t_prior") == 2
    assert rec.counter_delta("t_fresh") == 1
    assert rec.counter_delta("t_never_touched") == 0


def test_jsonl_sink_writes_schema_valid_lines(tmp_path):
    path = tmp_path / "sub" / "events.jsonl"  # parent dir auto-created
    sink = telemetry.JsonlSink(str(path))
    telemetry.add_sink(sink)
    try:
        with telemetry.span("s", shape=(3, 4)):
            telemetry.event("e", arr=np.float32(1.5))  # non-JSON type
    finally:
        telemetry.remove_sink(sink)
        sink.close()
    assert schema.validate_events_file(str(path)) == []
    lines = [json.loads(line) for line in path.read_text().splitlines()]
    assert [ev["name"] for ev in lines] == ["e", "s"]  # event, then span end
    assert lines[0]["attrs"]["arr"] == 1.5


# ------------------------------------------------------------- metrics


def test_metric_registry_kinds_and_type_guard():
    c = telemetry.counter("t_kinds_c")
    c.inc()
    c.inc(3)
    assert c.value == 4 and telemetry.counter("t_kinds_c") is c
    g = telemetry.gauge("t_kinds_g")
    g.set(2)
    g.set(7.5)
    assert g.value == 7.5
    h = telemetry.histogram("t_kinds_h", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 99.0):
        h.observe(v)
    assert h.counts == [1, 1, 1] and h.count == 3
    assert h.sum == pytest.approx(99.55)
    with pytest.raises(TypeError):
        telemetry.gauge("t_kinds_c")  # already a Counter


def test_render_prom_format():
    telemetry.counter("t_prom_total").inc(5)
    telemetry.gauge("t_prom_g").set(2.5)
    h = telemetry.histogram("t_prom_h", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(99.0)
    prom = telemetry.render_prom()
    assert "# TYPE t_prom_total counter\nt_prom_total 5" in prom
    assert "# TYPE t_prom_g gauge\nt_prom_g 2.5" in prom
    # histogram buckets are CUMULATIVE and end at +Inf == _count
    assert 't_prom_h_bucket{le="0.1"} 1' in prom
    assert 't_prom_h_bucket{le="1"} 2' in prom
    assert 't_prom_h_bucket{le="+Inf"} 3' in prom
    assert "t_prom_h_count 3" in prom
    snap = telemetry.metrics_snapshot()
    assert snap["t_prom_total"] == 5
    assert snap["t_prom_h"]["count"] == 3


# ----------------------------------------------------- schema validation


def test_schema_rejects_malformed_records():
    assert schema.validate_event({"type": "event", "name": "x",
                                  "ts": 1.0}) == []
    assert schema.validate_event({"type": "span", "name": "x", "ts": 1.0,
                                  "dur_s": 0.1, "path": "a/x"}) == []
    assert schema.validate_event([1, 2])  # not an object
    assert schema.validate_event({"type": "bogus", "name": "x", "ts": 0})
    assert schema.validate_event({"type": "event", "name": "", "ts": 0})
    assert schema.validate_event({"type": "span", "name": "x", "ts": 0,
                                  "dur_s": -1, "path": "x"})
    assert schema.validate_event({"type": "event", "name": "x", "ts": 0,
                                  "attrs": [1]})


def test_schema_bench_and_cli(tmp_path, capsys):
    good = {"bench": "ccm", "rows": [
        {"name": "r", "us_per_call": 12.5, "derived": "8pairs_per_s"}]}
    assert schema.validate_bench(good) == []
    assert schema.validate_bench({"bench": "", "rows": []})
    assert schema.validate_bench({"bench": "b", "rows": [
        {"name": "r", "us_per_call": 0}]})
    bench = tmp_path / "BENCH_x.json"
    bench.write_text(json.dumps(good))
    events = tmp_path / "events.jsonl"
    events.write_text(json.dumps(
        {"type": "event", "name": "e", "ts": 1.0}) + "\n")
    assert schema.main([str(bench), str(events)]) == 0
    assert "schema OK: 2 artifact(s)" in capsys.readouterr().out
    bad = tmp_path / "bad.jsonl"
    bad.write_text('{"type": "nope"}\nnot json\n')
    assert schema.main([str(bad)]) == 1
    assert schema.main([]) == 2


# ------------------------------------------------- straggler threshold


def test_straggler_monitor_synthetic_clock_and_threshold():
    """Deterministic regression: replay a timing sequence through an
    injected clock — six nominal 1s launches then a 4× outlier. The
    outlier flips the flag at threshold 3, not at threshold 8, and the
    flag publishes both the counter and the straggler.flag event."""
    t = {"now": 0.0}

    def clock():
        return t["now"]

    def replay(mon):
        for step in range(6):
            mon.start()
            t["now"] += 1.0
            assert mon.stop(step) is False
        mon.start()
        t["now"] += 4.0
        return mon.stop(6)

    with telemetry.record() as rec:
        mon = StragglerMonitor(threshold=3.0, window=10, clock=clock)
        assert replay(mon) is True
    assert rec.counter_delta("edm_stragglers_flagged") == 1
    ev = rec.events_named("straggler.flag")[0]["attrs"]
    assert ev["step"] == 6 and ev["threshold"] == 3.0
    assert ev["seconds"] == pytest.approx(4.0)
    assert mon.report()["flagged"][0]["rolling_median_s"] == 1.0

    lax = StragglerMonitor(threshold=8.0, window=10, clock=clock)
    assert replay(lax) is False
    assert lax.report()["flagged"] == []


def test_straggler_threshold_config_validation_and_keying():
    with pytest.raises(ValueError):
        EDMConfig(straggler_threshold=0.0)
    with pytest.raises(ValueError):
        StragglerMonitor(threshold=-1.0)
    # a perf-only knob: changing it must NOT change the resume key
    X = np.asarray(_panel())
    sig = ("xmap", "simplex", None, ((3, 6),))
    assert run_key(X, EDMConfig(E=3, straggler_threshold=9.0), sig) \
        == run_key(X, EDMConfig(E=3), sig)


# --------------------------------------- end-to-end acceptance (ISSUE 7)


def test_e2e_journaled_run_produces_all_telemetry_artifacts(tmp_path):
    """The acceptance path in one test: a journaled xmap emits the JSONL
    span log, folds Prometheus metrics (pairs, launch and dispatch-time
    counters) into report.json, counts every pair exactly once, and the
    run inspector renders the result from artifacts alone."""
    X = _panel()
    run = tmp_path / "run"
    cfg = EDMConfig(E=3, batch_libs=2, straggler_threshold=5.0)
    with telemetry.record() as rec:
        got = EDM(X, cfg).xmap(run_dir=str(run))
    assert got.shape == (6, 6)
    assert rec.counter_delta("edm_pairs_total") == 36
    assert rec.counter_delta("edm_runs_started") == 1
    assert rec.spans("session.xmap") and rec.spans("engine.drive")
    assert rec.events_named("run.start") and rec.events_named("run.complete")

    log = run / "telemetry" / "events.jsonl"
    assert log.exists()
    assert schema.validate_events_file(str(log)) == []
    names = [json.loads(line)["name"]
             for line in log.read_text().splitlines()]
    assert "run.start" in names and "run.complete" in names
    assert "engine.drive" in names  # spans land in the on-disk log too

    rep = json.loads((run / "report.json").read_text())
    assert rep["status"] == "complete"
    assert rep["rows_done"] == rep["rows_total"] == 6
    assert rep["pairs_done"] == 36 and rep["pairs_per_s"] > 0
    assert rep["tiles_committed"] == 3  # ceil(6/2)
    assert rep["stragglers"]["threshold"] == 5.0  # config threaded through
    prom = rep["metrics_prom"]
    assert "edm_pairs_total" in prom
    assert "# TYPE edm_launches counter" in prom
    assert "# TYPE edm_dispatch_seconds counter" in prom
    assert rec.counter_delta("edm_launches") == 3  # ceil(6/2)
    assert rec.counter_delta("edm_dispatch_seconds") > 0
    assert "edm_launch_latency_seconds" not in prom

    info = edm_inspect.inspect_run(str(run))
    assert info["status"] == "complete"
    assert info["rows_done"] == 6
    assert info["pairs_per_s"] == rep["pairs_per_s"]
    assert info["heartbeat_age_s"] is not None
    text = edm_inspect.format_summary(info)
    assert "status: complete" in text and "rows: 6/6" in text
    assert "run.complete" in text
    assert edm_inspect.main([str(run)]) == 0
    assert edm_inspect.main([str(tmp_path / "nope")]) == 2


def test_inspector_tolerates_partial_run_dir(tmp_path):
    info = edm_inspect.inspect_run(str(tmp_path))
    assert info["status"] is None and info["rows_done"] is None
    assert "no run.json" in edm_inspect.format_summary(info)


def test_resume_lineage_in_manifest_and_report(tmp_path, monkeypatch):
    """Kill → resume: the manifest accumulates one attempt record per
    process, the final report names the prior attempt's run_id, keeps
    cumulative wall time across attempts, and the telemetry log holds
    both lifecycle events."""
    X = _panel()
    cfg = EDMConfig(E=3, batch_libs=2)
    ref = EDM(X, cfg).xmap()
    run = tmp_path / "run"
    orig = ccm._group_step
    n = {"launches": 0}

    def sigterm_mid_run(*a, **k):
        n["launches"] += 1
        if n["launches"] == 2:
            os.kill(os.getpid(), signal.SIGTERM)
        return orig(*a, **k)

    monkeypatch.setattr(ccm, "_group_step", sigterm_mid_run)
    with pytest.raises(SystemExit) as exc:
        EDM(X, cfg).xmap(run_dir=str(run))
    assert exc.value.code == PREEMPTED_EXIT
    manifest = json.loads((run / "run.json").read_text())
    assert len(manifest["attempts"]) == 1
    first = manifest["attempts"][0]
    assert first["status"] == "preempted" and first["rows_resumed"] == 0
    rep1 = json.loads((run / "report.json").read_text())
    assert rep1["status"] == "preempted" and rep1["prior_run_ids"] == []

    monkeypatch.setattr(ccm, "_group_step", orig)
    got = EDM(X, cfg).xmap(run_dir=str(run))
    np.testing.assert_array_equal(ref, got)
    manifest = json.loads((run / "run.json").read_text())
    assert len(manifest["attempts"]) == 2
    assert manifest["attempts"][0] == first  # history is append-only
    second = manifest["attempts"][1]
    assert second["status"] == "complete"
    assert second["run_id"] != first["run_id"]
    assert second["rows_resumed"] == rep1["rows_done"] > 0

    rep = json.loads((run / "report.json").read_text())
    assert rep["status"] == "complete"
    assert rep["prior_run_ids"] == [first["run_id"]]
    assert rep["run_id"] == second["run_id"]
    assert rep["rows_resumed"] + rep["rows_this_attempt"] == 6
    assert rep["cumulative_elapsed_s"] >= rep["elapsed_s"]
    assert rep["cumulative_elapsed_s"] == pytest.approx(
        first["elapsed_s"] + rep["elapsed_s"], abs=1e-6)

    names = [json.loads(line)["name"] for line in
             (run / "telemetry" / "events.jsonl").read_text().splitlines()]
    assert "run.start" in names and "run.resume" in names
    # the inspector surfaces the lineage
    text = edm_inspect.format_summary(edm_inspect.inspect_run(str(run)))
    assert "attempts: 2" in text
