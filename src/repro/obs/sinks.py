"""Trace sinks: JSONL file (digest-stamped) and console summary.

The JSONL sink is the canonical artifact: every record the flight recorder
captured, one JSON object per line (schema: `repro.obs.schema`), written
with sorted keys and compact separators so the file — and therefore its
sha256, which `repro.api.run` stamps into the manifest — is deterministic
given the same records.

For a timeline, ``ObsSpec.profile_dir`` wraps the run in
``jax.profiler.trace``: every recorder span is also a profiler annotation,
so the profile shows the spans beside XLA's device ops in one Perfetto view.
"""
from __future__ import annotations

import hashlib
import json
from typing import Any, Mapping

from repro.obs.metrics import MetricsRegistry
from repro.obs.schema import SCHEMA_VERSION


def _dumps(obj: Mapping[str, Any]) -> str:
    return json.dumps(obj, sort_keys=True, separators=(",", ":"))


def write_jsonl(path: str, meta: Mapping[str, Any], records: list[dict],
                metrics: MetricsRegistry) -> str:
    """Write the trace file and return its sha256 hexdigest.

    Layout: one ``meta`` header, every span/event/point record in emission
    order, then the end-of-run ``summary``/``counter``/``gauge`` records
    from the metrics registry.
    """
    h = hashlib.sha256()
    snap = metrics.snapshot()
    with open(path, "w") as f:
        def emit(obj: Mapping[str, Any]) -> None:
            line = _dumps(obj) + "\n"
            f.write(line)
            h.update(line.encode())

        emit({"kind": "meta", "schema": SCHEMA_VERSION, **meta})
        for rec in records:
            emit(rec)
        for name, body in snap["summaries"].items():
            emit({"kind": "summary", "name": name, **body})
        for name, value in sorted(snap["counters"].items()):
            emit({"kind": "counter", "name": name, "value": value})
        for name, value in sorted(snap["gauges"].items()):
            emit({"kind": "gauge", "name": name, "value": value})
    return h.hexdigest()


def file_sha256(path: str) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as f:
        for chunk in iter(lambda: f.read(1 << 20), b""):
            h.update(chunk)
    return h.hexdigest()


def console_summary(metrics: MetricsRegistry, *, title: str = "trace") -> str:
    """The ``--trace`` table: per-phase latency breakdown with share of the
    round total, then counters and gauges."""
    snap = metrics.snapshot()
    summaries = snap["summaries"]
    total_key = ("round.total" if "round.total" in summaries
                 else "flush.total" if "flush.total" in summaries else None)
    total_sum = summaries[total_key]["sum"] if total_key else None

    lines = [f"=== {title} ===",
             f"{'phase':<28}{'count':>7}{'mean_ms':>10}{'p50_ms':>10}"
             f"{'p99_ms':>10}{'total_s':>10}{'share':>8}"]
    for name, s in summaries.items():
        # share of round time is only meaningful for phase (span) summaries —
        # ledger.* / async.* series are token amounts and weights, not ms
        is_phase = name.startswith(("round.", "flush.", "chain."))
        share = (f"{100.0 * s['sum'] / total_sum:6.1f}%"
                 if total_sum and is_phase else f"{'':>7}")
        lines.append(f"{name:<28}{s['count']:>7}{s['mean']:>10.3f}"
                     f"{s['p50']:>10.3f}{s['p99']:>10.3f}"
                     f"{s['sum'] / 1e3:>10.3f}{share:>8}")
    if snap["counters"]:
        lines.append("counters: " + "  ".join(
            f"{k}={v:g}" for k, v in sorted(snap["counters"].items())))
    if snap["gauges"]:
        lines.append("gauges:   " + "  ".join(
            f"{k}={v:g}" for k, v in sorted(snap["gauges"].items())))
    return "\n".join(lines)
