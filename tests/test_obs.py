"""Unified telemetry tier (PR-8): tracer, metrics, flight recorder.

Covers the observability contracts that CI leans on:

  * Perfetto export is schema-valid Chrome trace JSON — only X/M/i/C
    phases, complete spans carry ts+dur, every (pid, tid) that appears
    in an event has process_name/thread_name metadata, and the pid
    scheme (host=1, PS=10, worker w=100+w) gives one track per worker;
  * the exported timeline reconstructs the wire ledger EXACTLY —
    ok + lost + dup wire spans == ``trace.comm``, fault instants match
    the fault ledger record for record (the export-side twin of
    ``faults.validate``);
  * exports are deterministic at a fixed seed (byte-identical event
    streams), and telemetry is semantics-free: the scheduler emits the
    same Trace with the whole tier on as with it off;
  * metrics are a shared no-op when disabled and real instruments when
    enabled (pow2 histogram buckets, label scoping, jax-tracer skip);
  * the flight recorder is a bounded ring and dumps on a forged
    fault ledger (``faults.validate``) and on scheduler exceptions;
  * every BENCH row gets a ``run_id``/``schema_version`` stamp, and
    ``bench_delta`` tolerates (but announces) rows gaining columns.
"""
import dataclasses
import glob
import importlib.util
import json
import os
import re

import numpy as np
import pytest

from repro import cluster
from repro.cluster import faults
from repro.obs import export as obs_export
from repro.obs import flight as obs_flight
from repro.obs import metrics as obs_metrics
from repro.obs import runinfo, state
from repro.obs import trace as obs_trace

N = 8


@pytest.fixture(autouse=True)
def _clean_obs():
    """Every test starts and ends with the tier fully off and empty."""
    state.disable()
    obs_trace.reset()
    obs_metrics.reset()
    obs_flight.reset()
    yield
    state.disable()
    obs_trace.reset()
    obs_metrics.reset()
    obs_flight.reset()


def _demo_trace(seed=0, rounds=4):
    return obs_export.build_trace(protocol="sync_ps", n=N, rounds=rounds,
                                  p_drop=0.1, crash=True, quorum=6,
                                  seed=seed)


# ---------------------------------------------------------------------------
# Perfetto schema validity + track-per-worker invariants
# ---------------------------------------------------------------------------


def test_export_is_schema_valid_chrome_trace(tmp_path):
    tr = _demo_trace()
    out = tmp_path / "timeline.json"
    obs_export.export_trace(tr, str(out))
    doc = json.loads(out.read_text())

    assert set(doc) >= {"traceEvents", "displayTimeUnit", "metadata"}
    events = doc["traceEvents"]
    assert events, "empty timeline"
    assert {e["ph"] for e in events} <= {"X", "M", "i", "C"}
    for e in events:
        if e["ph"] == "X":       # complete spans: ts + non-negative dur
            assert e["ts"] >= 0 and e["dur"] >= 0
        if e["ph"] == "i":       # instants carry an explicit scope
            assert e["s"] == "t"
    # file-level identity stamp for artifact cross-referencing
    assert doc["metadata"]["schema_version"] == runinfo.SCHEMA_VERSION
    assert doc["metadata"]["counts"]["wire_spans"] == len(tr.comm)


def test_every_track_is_named_and_pids_follow_the_scheme(tmp_path):
    tr = _demo_trace()
    out = tmp_path / "timeline.json"
    obs_export.export_trace(tr, str(out))
    events = json.loads(out.read_text())["traceEvents"]

    named_pids = {e["pid"] for e in events
                  if e["ph"] == "M" and e["name"] == "process_name"}
    named_tracks = {(e["pid"], e["tid"]) for e in events
                    if e["ph"] == "M" and e["name"] == "thread_name"}
    for e in events:
        if e["ph"] in ("X", "i"):
            assert e["pid"] in named_pids
            assert (e["pid"], e["tid"]) in named_tracks

    # pid scheme: server = 10, worker w = 100 + w — one track per worker
    data_pids = {e["pid"] for e in events if e["ph"] in ("X", "i")}
    worker_pids = {p for p in data_pids if p >= 100}
    assert worker_pids == {100 + w for w in range(N)}
    assert 10 in data_pids     # the PS track (barriers, shortfalls)


def test_worker_uplink_spans_live_on_the_sender_track(tmp_path):
    tr = _demo_trace()
    tracer = obs_trace.timeline_from_trace(tr)
    ps = tr.n_workers
    uplinks = [d for d in tr.comm if d.dst == ps]
    up_spans = [e for e in tracer.events()
                if e["ph"] == "X" and e["cat"].startswith("wire,uplink")]
    assert len(up_spans) == len(uplinks)
    for e in up_spans:
        assert e["pid"] == 100 + e["args"]["src"]


# ---------------------------------------------------------------------------
# Ledger reconstruction: ok + lost + dup == comm, fault instants exact
# ---------------------------------------------------------------------------


def test_timeline_counts_match_ledgers_exactly():
    tr = _demo_trace()
    tally = faults.validate(tr)
    tracer = obs_trace.timeline_from_trace(tr)
    counts = obs_export.verify_timeline(tr, tracer)   # asserts internally

    by = counts["wire_by_status"]
    assert by["ok"] + by["lost"] + by["dup"] == len(tr.comm)
    assert by["ok"] == tally["delivered"]
    assert by["lost"] == tally["dropped"]
    assert by["dup"] == tally["duplicated"]
    assert counts["quorum_spans"] == tally["timed_out"]
    # the demo scenario actually exercises the faulty paths
    assert by["lost"] > 0 and counts["quorum_spans"] > 0
    assert tally["rejoins"] >= 1


def test_verify_timeline_catches_a_missing_span():
    tr = _demo_trace()
    tracer = obs_trace.timeline_from_trace(tr)
    dropped = tracer._events.pop()    # forge: lose one rendered event
    with pytest.raises(AssertionError, match="timeline/ledger mismatch"):
        obs_export.verify_timeline(tr, tracer)
    tracer._events.append(dropped)
    obs_export.verify_timeline(tr, tracer)


def test_live_compute_spans_do_not_disturb_the_accounting():
    # live scheduler tracing adds cat="sim,compute" rows to the SAME
    # tracer; verify_timeline must still balance (it tallies only the
    # wire,/event,/fault, categories)
    state.enable(trace=True, metrics=False, flight=False)
    live = obs_trace.tracer()
    tr = _demo_trace()
    assert any(e["cat"] == "sim,compute" for e in live.events())
    obs_trace.timeline_from_trace(tr, into=live)
    obs_export.verify_timeline(tr, live)


# ---------------------------------------------------------------------------
# Determinism + zero-semantics-impact
# ---------------------------------------------------------------------------


def test_export_is_deterministic_at_fixed_seed(tmp_path):
    docs = []
    for i in range(2):
        obs_trace.reset()
        out = tmp_path / f"t{i}.json"
        obs_export.export_trace(_demo_trace(seed=3), str(out))
        docs.append(json.loads(out.read_text()))
    assert docs[0]["traceEvents"] == docs[1]["traceEvents"]


def test_telemetry_never_changes_the_schedule():
    off = _demo_trace()
    state.enable()
    on = _demo_trace()
    assert on.makespan == off.makespan
    assert len(on.comm) == len(off.comm)
    assert on.events == off.events
    assert on.faults.summary() == off.faults.summary()


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------


def test_metrics_are_a_shared_noop_when_disabled():
    c = obs_metrics.counter("x.count")
    g = obs_metrics.gauge("x.gauge")
    assert c is g                       # the single shared null object
    c.inc(5)
    g.set(1.0)
    assert obs_metrics.registry().snapshot() == {}


def test_metrics_record_when_enabled_and_labels_scope_names():
    state.enable(trace=False, metrics=True, flight=False)
    obs_metrics.counter("wire.msgs", protocol="sync_ps").inc()
    obs_metrics.counter("wire.msgs", protocol="sync_ps").inc(2)
    obs_metrics.counter("wire.msgs", protocol="dsgd").inc()
    snap = obs_metrics.registry().snapshot()
    assert snap["wire.msgs[protocol=sync_ps]"]["value"] == 3
    assert snap["wire.msgs[protocol=dsgd]"]["value"] == 1


def test_histogram_pow2_buckets():
    state.enable(trace=False, metrics=True, flight=False)
    h = obs_metrics.histogram("lag")
    for v in (0.5, 1.0, 3.0, 7.9, 8.0, 100.0, 0.0, -2.0):
        h.observe(v)
    s = h.snapshot()
    assert s["count"] == 8 and s["zero"] == 1 and s["neg"] == 1
    # (0,1] -> bucket 0; (2,4] -> 2; (4,8] -> 3; (64,128] -> 7
    assert s["pow2_buckets"] == {"0": 2, "2": 1, "3": 2, "7": 1}
    assert s["min"] == -2.0 and s["max"] == 100.0


def test_observe_array_skips_jax_tracers_and_flattens_numpy():
    state.enable(trace=False, metrics=True, flight=False)

    class Tracer:                       # duck-typed jax.core.Tracer
        def ravel(self):                # pragma: no cover - must not run
            raise AssertionError("tracer was observed")

    obs_metrics.observe_array("q.range", Tracer())
    assert "q.range" not in obs_metrics.registry().snapshot()
    obs_metrics.observe_array("q.range", np.arange(6.0).reshape(2, 3))
    assert obs_metrics.registry().snapshot()["q.range"]["count"] == 6


def test_scheduler_fills_the_registry():
    state.enable(trace=False, metrics=True, flight=False)
    _demo_trace()
    snap = obs_metrics.registry().snapshot()
    assert snap["cluster.traces[protocol=sync_ps]"]["value"] >= 1
    assert snap["faults.quorum_cuts"]["value"] > 0
    assert any(k.startswith("cluster.wire_msgs[") for k in snap)


# ---------------------------------------------------------------------------
# Flight recorder
# ---------------------------------------------------------------------------


def test_flight_recorder_is_a_bounded_ring():
    state.enable(trace=False, metrics=False, flight=True)
    rec = obs_flight.recorder()
    rec.set_capacity(8)
    try:
        for i in range(20):
            obs_flight.record("tick", i=i)
        evs = rec.snapshot()
        assert len(evs) == 8
        assert [e["i"] for e in evs] == list(range(12, 20))
    finally:
        rec.set_capacity(obs_flight.DEFAULT_CAPACITY)


def test_forged_ledger_dumps_the_flight_buffer(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_OBS_DIR", str(tmp_path))
    state.enable(trace=False, metrics=False, flight=True)
    tr = _demo_trace()
    # forge: the ledger loses a drop record, so it no longer matches wire
    led = dataclasses.replace(tr.faults, drops=tr.faults.drops[:-1])
    tr = dataclasses.replace(tr, faults=led)
    with pytest.raises(AssertionError):
        faults.validate(tr)
    dump = tmp_path / "flight_faults_validate.json"
    assert dump.exists()
    payload = json.loads(dump.read_text())
    assert "AssertionError" in payload["reason"]
    assert payload["run_id"] == runinfo.run_id()
    # the buffer holds the events leading up to the failure, in order
    seqs = [e["seq"] for e in payload["events"]]
    assert seqs == sorted(seqs)
    assert payload["events"][-1]["kind"] == "faults.validate_failed"


def test_guarded_dumps_on_uncaught_exception(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_OBS_DIR", str(tmp_path))
    state.enable(trace=False, metrics=False, flight=True)

    @obs_flight.guarded("unit.boom")
    def boom():
        obs_flight.record("about.to.fail")
        raise ValueError("kaboom")

    with pytest.raises(ValueError, match="kaboom"):
        boom()
    payload = json.loads((tmp_path / "flight_unit_boom.json").read_text())
    assert payload["reason"] == "ValueError: kaboom"
    assert payload["events"][-1]["kind"] == "about.to.fail"


def test_flight_disabled_writes_nothing(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_OBS_DIR", str(tmp_path))
    obs_flight.record("never")
    assert obs_flight.dump_on_failure("scope", "reason") is None
    assert list(tmp_path.iterdir()) == []


def test_kernel_annotation_is_transparent():
    @obs_flight.kernel_annotation("unit.kernel")
    def f(x, y=1):
        return x + y

    assert f(2) == 3                    # tier off: named_scope wraps it
    state.enable(trace=True, metrics=False, flight=False)
    assert f(2, y=3) == 5               # tier on: the same scope
    assert f.__name__ == "f"            # wraps() keeps jit-able identity


def test_kernel_names_reach_the_lowered_hlo_with_obs_off():
    import jax
    import jax.numpy as jnp
    from repro.kernels.quant import ops

    assert not state.enabled("trace")
    x = jnp.linspace(-1.0, 1.0, 4096)
    lowered = jax.jit(lambda v, k: ops.qdq_flat(v, k)).lower(
        x, jax.random.PRNGKey(0))
    hlo = lowered.as_text(dialect="hlo", debug_info=True)
    assert "quant.qdq_flat" in hlo
    assert "_qdq_flat_impl" in hlo      # the jitted name stays as it was


def test_span_lands_on_the_profiler_trace_around_its_device_work(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.tanh(x @ x.T).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()             # compiled outside the trace
    hlo_names = set(re.findall(r"%([\w.\-]+) = ",
                               f.lower(x).compile().as_text()))

    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs_trace.span("unit.span"):   # obs tracing is off
            f(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    planes = jax.profiler.ProfileData.from_file(path).planes
    host = [p for p in planes if p.name == "/host:CPU"]
    events = [ev for p in host for line in p.lines for ev in line.events]
    spans = [ev for ev in events if ev.name == "unit.span"]
    assert len(spans) == 1
    lo = spans[0].start_ns
    hi = lo + spans[0].duration_ns
    work = [ev for ev in events if ev.name in hlo_names]
    assert work, "the jitted call's operations are not in the trace"
    for ev in work:
        assert lo <= ev.start_ns and ev.start_ns + ev.duration_ns <= hi
    assert obs_trace.tracer().n_events == 0   # the JSON stays off


# ---------------------------------------------------------------------------
# run_id stamping + bench_delta schema tolerance
# ---------------------------------------------------------------------------


def test_stamp_rows_adds_run_identity():
    rows = [{"op": "a", "us": 1.0}, {"op": "b", "us": 2.0}]
    out = runinfo.stamp_rows(rows, seed=7)
    assert out is rows                  # in-place, like the benches use it
    for r in rows:
        assert r["run_id"] == runinfo.run_id(7)
        assert r["run_id"].endswith("-s7")
        assert r["schema_version"] == runinfo.SCHEMA_VERSION


def _load_bench_delta():
    here = os.path.dirname(__file__)
    path = os.path.join(here, os.pardir, "benchmarks", "bench_delta.py")
    spec = importlib.util.spec_from_file_location("bench_delta", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_bench_delta_tolerates_rows_gaining_stamped_columns():
    bd = _load_bench_delta()
    base = {"q/sync_ps": {"workload": "q", "protocol": "sync_ps",
                          "makespan_s": 10.0}}
    fresh = {"q/sync_ps": {"workload": "q", "protocol": "sync_ps",
                           "makespan_s": 10.0, "run_id": "abc-s0",
                           "schema_version": 2, "stale_p99": 4.0}}
    # the new columns never gate...
    assert bd.compare(base, fresh, threshold=1.0001) == []
    # ...but their appearance is announced, and schema_version/run_id
    # are identity stamps, not metrics
    assert bd.schema_drift(base, fresh) == (["stale_p99"], [])
    # a real regression in a shared metric still trips
    fresh["q/sync_ps"]["makespan_s"] = 30.0
    assert len(bd.compare(base, fresh, threshold=2.0)) == 1
