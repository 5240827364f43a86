"""`repro.obs` — flight recorder: structured tracing + metrics for BFLN runs.

Low-overhead, always-out-of-band observability across the round engine, the
event-driven simulator, the blockchain layer, and the experiment runner:

    spec = ExperimentSpec(obs=ObsSpec(enabled=True, trace_path="run.jsonl"))
    result = run(spec)            # manifest carries the trace file's sha256
    print(result.summary())       # ... | round p50=82.1ms chain=7% compiles=4

The recorder captures wall-clock *and* sim virtual-clock spans per round
phase (sample, gather, donated step, digests, chain, eval, async flush),
explicit compile events from `RoundEngine.cache_sizes()` deltas, and a
metrics registry of per-round counters/gauges with streaming p50/p99
summaries.  Sinks: a schema-validated JSONL trace (digest stamped into the
run manifest) and a console summary table; every span is also a
``jax.profiler`` annotation, so a profile (``ObsSpec.profile_dir``) shows
the spans beside the device ops.

Hard invariant: tracing on vs. off leaves event logs, block hashes, ledger
balances and final accuracy bit-identical — observability may time and
count, never perturb (pinned by ``tests/test_obs_invariance.py``).
"""
from repro.obs.metrics import MetricsRegistry, Summary  # noqa: F401
from repro.obs.names import (  # noqa: F401
    ALL_NAMES,
    COUNTER_NAMES,
    DYNAMIC_PREFIXES,
    EVENT_NAMES,
    GAUGE_NAMES,
    SERIES_NAMES,
    SPAN_NAMES,
)
from repro.obs.recorder import (  # noqa: F401
    NULL_RECORDER,
    FlightRecorder,
    NullRecorder,
)
from repro.obs.schema import (  # noqa: F401
    SCHEMA_VERSION,
    validate_record,
    validate_trace_lines,
)
from repro.obs.sinks import (  # noqa: F401
    console_summary,
    file_sha256,
    write_jsonl,
)
from repro.obs.spec import ObsSpec  # noqa: F401
