"""Unit tests for `repro.obs` — the flight recorder subsystem.

Covers the streaming metrics (deterministic RNG-free reservoir thinning),
the span tracer (wall + virtual clocks, span ids and parents, profiler
annotations, compile-delta events), the JSONL schema validator, and the
sinks (digest-stable JSONL, console summary).  End-to-end replay invariance lives in
``tests/test_obs_invariance.py``.
"""
import glob
import json

import pytest

from repro.obs import (
    NULL_RECORDER,
    FlightRecorder,
    MetricsRegistry,
    ObsSpec,
    Summary,
    console_summary,
    file_sha256,
    validate_record,
    validate_trace_lines,
    write_jsonl,
)


# --------------------------------------------------------------------------- #
# ObsSpec
# --------------------------------------------------------------------------- #

def test_obs_spec_defaults_off():
    spec = ObsSpec()
    assert not spec.enabled
    assert spec.trace_path


@pytest.mark.parametrize("bad", [
    dict(trace_path=""),
    dict(sample_cap=4),
    dict(profile_dir=""),
])
def test_obs_spec_validates(bad):
    with pytest.raises(ValueError):
        ObsSpec(enabled=True, **bad)


# --------------------------------------------------------------------------- #
# Summary / MetricsRegistry
# --------------------------------------------------------------------------- #

def test_summary_exact_aggregates():
    s = Summary(cap=64)
    for v in [3.0, 1.0, 2.0]:
        s.observe(v)
    snap = s.snapshot()
    assert snap["count"] == 3
    assert snap["sum"] == 6.0
    assert snap["mean"] == 2.0
    assert snap["min"] == 1.0 and snap["max"] == 3.0
    assert snap["p50"] == 2.0


def test_summary_thinning_is_bounded_and_deterministic():
    a, b = Summary(cap=32), Summary(cap=32)
    for i in range(10_000):
        a.observe(float(i))
        b.observe(float(i))
    assert len(a._samples) < 32
    assert a.snapshot() == b.snapshot()        # no RNG anywhere
    assert a.count == 10_000
    assert a.min == 0.0 and a.max == 9999.0
    # the systematic reservoir still spans the stream
    assert a.quantile(0.5) == pytest.approx(5000, rel=0.1)


def test_registry_counters_gauges_summaries():
    m = MetricsRegistry(sample_cap=64)
    m.inc("blocks")
    m.inc("blocks", 2.0)
    m.set_gauge("bytes", 7.0)
    m.set_gauge("bytes", 9.0)
    m.observe("lat", 5.0)
    snap = m.snapshot()
    assert snap["counters"]["blocks"] == 3.0
    assert snap["gauges"]["bytes"] == 9.0
    assert snap["summaries"]["lat"]["count"] == 1


# --------------------------------------------------------------------------- #
# FlightRecorder / NullRecorder
# --------------------------------------------------------------------------- #

def test_span_records_wall_and_virtual_time():
    vt = [10.0]
    rec = FlightRecorder(ObsSpec(enabled=True), clock=lambda: vt[0])
    with rec.span("round.total", round=3) as sp:
        vt[0] = 12.5
        sp.set(arrived=8)
    (r,) = rec.records
    assert r["kind"] == "span" and r["name"] == "round.total"
    assert r["round"] == 3
    assert r["dur_us"] >= 0
    assert r["vt"] == 12.5
    assert r["attrs"]["vt_dur"] == 2.5
    assert r["attrs"]["arrived"] == 8
    # the span also feeds the ms summary under its own name
    assert rec.metrics.summaries["round.total"].count == 1


def test_span_ids_and_parents_follow_nesting():
    rec = FlightRecorder(ObsSpec(enabled=True))
    with rec.span("round.total", round=0):
        with rec.span("round.schedule", round=0):
            pass
        with rec.span("round.chain", round=0):
            with rec.span("chain.pack", cat="chain", round=0):
                pass
    with rec.span("round.total", round=1):
        pass
    by_name = {}
    for r in rec.records:
        by_name.setdefault(r["name"], []).append(r)
    total0, total1 = by_name["round.total"]
    assert (total0["id"], total0["parent"]) == (0, None)
    assert by_name["round.schedule"][0]["parent"] == 0
    assert by_name["round.chain"][0]["parent"] == 0
    assert by_name["chain.pack"][0]["parent"] == \
        by_name["round.chain"][0]["id"]
    assert total1["parent"] is None
    assert sorted(r["id"] for r in rec.records) == list(range(5))
    for r in rec.records:
        validate_record(r)


def _host_annotations(log_dir) -> list:
    from jax.profiler import ProfileData
    (path,) = glob.glob(str(log_dir / "**" / "*.xplane.pb"), recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(("round.", "chain.")):
                        out.append((ev.name, ev.start_ns, ev.end_ns,
                                    dict(ev.stats)))
    return out


@pytest.mark.parametrize("live", [True, False])
def test_spans_are_profiler_annotations(tmp_path, live):
    """A live recorder's spans show on the profiler's host plane by name,
    each inside its parent and carrying its id and parent; the shared no-op
    recorder leaves no annotation."""
    import jax
    rec = FlightRecorder(ObsSpec(enabled=True)) if live else NULL_RECORDER
    jax.profiler.start_trace(str(tmp_path))
    try:
        with rec.span("round.total", round=0):
            with rec.span("round.chain", round=0):
                with rec.span("chain.pack", cat="chain", round=0):
                    jax.numpy.ones(4).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    events = {name: (s, e, stats)
              for name, s, e, stats in _host_annotations(tmp_path)}
    if not live:
        assert events == {}
        return
    ids = {r["name"]: (r["id"], r["parent"]) for r in rec.records}
    assert set(events) == set(ids)
    for name, (s, e, stats) in events.items():
        span_id, parent = ids[name]
        assert stats["id"] == span_id
        assert stats.get("parent") == parent
    for child, parent in (("round.chain", "round.total"),
                          ("chain.pack", "round.chain")):
        assert events[parent][0] <= events[child][0]
        assert events[child][1] <= events[parent][1]


def test_compile_delta_emits_events_once_per_growth():
    rec = FlightRecorder(ObsSpec(enabled=True))
    rec.compile_delta({"sync_step": 1, "eval": 0}, round_idx=0)
    rec.compile_delta({"sync_step": 1, "eval": 1}, round_idx=1)
    rec.compile_delta({"sync_step": 1, "eval": 1}, round_idx=2)
    events = [r for r in rec.records if r["kind"] == "event"]
    assert [(e["attrs"]["entry"], e["round"]) for e in events] == \
        [("sync_step", 0), ("eval", 1)]
    assert rec.metrics.counters["compiles"] == 2


def test_ready_returns_value_unchanged():
    rec = FlightRecorder(ObsSpec(enabled=True, block_until_ready=True))
    assert rec.ready(41) == 41
    assert NULL_RECORDER.ready("x") == "x"


def test_null_recorder_is_inert():
    with NULL_RECORDER.span("anything", round=1) as sp:
        sp.set(a=1)
    NULL_RECORDER.event("e")
    NULL_RECORDER.point("p", 1.0)
    NULL_RECORDER.inc("c")
    NULL_RECORDER.set_gauge("g", 2.0)
    NULL_RECORDER.observe("o", 3.0)
    NULL_RECORDER.compile_delta({"x": 5})
    assert not NULL_RECORDER.enabled


def test_timing_summary_reads_round_metrics():
    rec = FlightRecorder(ObsSpec(enabled=True))
    for ms in (10.0, 12.0, 11.0):
        rec.metrics.observe("round.total", ms)
        rec.metrics.observe("round.chain", ms / 10)
    rec.inc("compiles", 4)
    t = rec.timing_summary()
    assert t["rounds"] == 3
    assert t["compiles"] == 4
    assert t["round_ms_p50"] == 11.0
    assert t["chain_overhead_pct"] == 10.0


# --------------------------------------------------------------------------- #
# schema
# --------------------------------------------------------------------------- #

def test_validate_record_accepts_each_kind():
    for rec in [
        {"kind": "meta", "schema": 2},
        {"kind": "span", "name": "a", "cat": "round", "round": 1, "id": 0,
         "parent": None, "ts_us": 0.0, "dur_us": 1.0, "vt": None},
        {"kind": "span", "name": "b", "cat": "round", "round": 1, "id": 3,
         "parent": 0, "ts_us": 0.0, "dur_us": 1.0, "vt": None},
        {"kind": "event", "name": "compile", "round": None, "ts_us": 2.0},
        {"kind": "point", "name": "p", "round": 0, "value": 1.5},
        {"kind": "summary", "name": "s", "count": 1, "sum": 1.0, "mean": 1.0,
         "min": 1.0, "max": 1.0, "p50": 1.0, "p90": 1.0, "p99": 1.0},
        {"kind": "counter", "name": "c", "value": 2.0},
        {"kind": "gauge", "name": "g", "value": 3.0},
    ]:
        validate_record(rec)


@pytest.mark.parametrize("bad", [
    {"name": "missing-kind"},
    {"kind": "nope"},
    {"kind": "span", "name": "a"},                       # missing fields
    {"kind": "counter", "name": "c", "value": "high"},   # non-numeric
    {"kind": "counter", "name": "c", "value": True},     # bool is not a number
    {"kind": "point", "name": 7, "round": 0, "value": 1.0},
    {"kind": "meta", "schema": 1},                       # old schema
    {"kind": "span", "name": "a", "cat": "c", "round": None,   # no id
     "ts_us": 0.0, "dur_us": 1.0, "vt": None},
    {"kind": "span", "name": "a", "cat": "c", "round": None, "id": 2,
     "parent": 2, "ts_us": 0.0, "dur_us": 1.0, "vt": None},  # own parent
    {"kind": "span", "name": "a", "cat": "c", "round": None, "id": -1,
     "parent": None, "ts_us": 0.0, "dur_us": 1.0, "vt": None},
])
def test_validate_record_rejects(bad):
    with pytest.raises(ValueError):
        validate_record(bad)


def _span_line(span_id: int, parent: int | None) -> str:
    return json.dumps({"kind": "span", "name": "a", "cat": "c",
                       "round": None, "id": span_id, "parent": parent,
                       "ts_us": 0.0, "dur_us": 1.0, "vt": None})


def test_validate_trace_lines_requires_meta_header():
    meta = json.dumps({"kind": "meta", "schema": 2})
    span = _span_line(0, None)
    counts = validate_trace_lines([meta, span])
    assert counts == {"meta": 1, "span": 1}
    with pytest.raises(ValueError):
        validate_trace_lines([span, meta])               # meta must come first
    with pytest.raises(ValueError):
        validate_trace_lines([meta, meta])               # exactly one meta


@pytest.mark.parametrize("spans, ok", [
    ([(1, 0), (0, None)], True),          # a child is written before its parent
    ([(0, None), (2, 1)], False),         # parent 1 names no span
    ([(0, None), (0, None)], False),      # duplicate id
])
def test_validate_trace_lines_checks_span_parents(spans, ok):
    lines = [json.dumps({"kind": "meta", "schema": 2})] + \
        [_span_line(i, p) for i, p in spans]
    if ok:
        assert validate_trace_lines(lines) == {"meta": 1, "span": len(spans)}
    else:
        with pytest.raises(ValueError):
            validate_trace_lines(lines)


# --------------------------------------------------------------------------- #
# sinks
# --------------------------------------------------------------------------- #

def _recorder_with_traffic() -> FlightRecorder:
    rec = FlightRecorder(ObsSpec(enabled=True))
    with rec.span("round.total", round=0):
        with rec.span("chain.pack", cat="chain", round=0) as sp:
            sp.set(n_tx=3)
    rec.event("compile", round=0, entry="sync_step", n=1)
    rec.inc("chain.blocks")
    rec.set_gauge("arena.bytes", 1024.0)
    return rec


def test_write_jsonl_digest_matches_file_and_schema(tmp_path):
    rec = _recorder_with_traffic()
    path = str(tmp_path / "t.jsonl")
    digest = write_jsonl(path, {"seed": 0}, rec.records, rec.metrics)
    assert digest == file_sha256(path)
    lines = open(path).read().splitlines()
    counts = validate_trace_lines(lines)
    assert counts["span"] == 2 and counts["meta"] == 1
    # byte-determinism: same records -> same file -> same digest
    path2 = str(tmp_path / "t2.jsonl")
    assert write_jsonl(path2, {"seed": 0}, rec.records, rec.metrics) == digest


def test_console_summary_mentions_phases_and_counters():
    rec = _recorder_with_traffic()
    text = console_summary(rec.metrics, title="t")
    assert "round.total" in text and "chain.pack" in text
    assert "chain.blocks=1" in text
    assert "arena.bytes=1024" in text
    assert "100.0%" in text                              # round.total share


# --------------------------------------------------------------------------- #
# spec integration
# --------------------------------------------------------------------------- #

def test_experiment_spec_obs_roundtrip_and_digest_exclusion():
    import repro.api as api
    on = api.ExperimentSpec(obs=api.ObsSpec(enabled=True,
                                            trace_path="x.jsonl"))
    off = api.ExperimentSpec()
    # observability is out-of-band: traced and untraced runs share the
    # replay recipe, so the config digest must ignore the obs section
    assert on.config_digest() == off.config_digest()
    back = api.ExperimentSpec.from_json(on.to_json())
    assert back.obs == on.obs
    assert back == on
