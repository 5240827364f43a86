"""Canonical observability name registry — the single source of truth for
every span / event / counter / gauge / series name the flight recorder is
allowed to see.

``docs/TRACE_SCHEMA.md`` documents these names for humans; this module is
the machine-checked form.  The ``trace-schema`` rule of ``repro.analysis``
cross-checks three ways and fails CI on drift:

    1. every literal name passed to a recorder method anywhere in
       ``src/repro`` must be registered here (per method: ``span`` ->
       :data:`SPAN_NAMES`, ``event`` -> :data:`EVENT_NAMES`, ``inc`` ->
       :data:`COUNTER_NAMES`, ``set_gauge`` -> :data:`GAUGE_NAMES`,
       ``observe``/``point`` -> :data:`SERIES_NAMES`);
    2. every name registered here must appear in ``docs/TRACE_SCHEMA.md``;
    3. every dotted metric name mentioned in ``docs/TRACE_SCHEMA.md`` must
       resolve against this registry.

Dynamic name families (f-strings with a literal prefix, e.g.
``f"family.{name}"``) are registered as prefixes in
:data:`DYNAMIC_PREFIXES`; the schema doc spells them ``family.<name>``.
None is registered today.

This module is imported by the static analyzer, which must run without jax —
keep it dependency-free.
"""
from __future__ import annotations

# --- spans: timed phases (recorder.span) --------------------------------- #
SPAN_NAMES = frozenset({
    # sync round phases (cat "round")
    "round.total", "round.sample", "round.schedule", "round.wait",
    "round.gather", "round.step", "round.digests", "round.chain",
    "round.scatter", "round.record", "round.eval", "round.retry",
    # async FedBuff flush phases and the event loop (cat "flush")
    "flush.total", "flush.gather", "flush.prepare", "flush.step",
    "flush.chain", "flush.merge", "flush.record", "flush.eval",
    "async.dispatch",
    # blockchain phases (cat "chain")
    "chain.pack", "chain.validate", "chain.verify", "chain.digests",
    "chain.commit", "chain.consensus", "chain.rewards",
    # checkpoint / run lifecycle
    "ckpt.save", "ckpt.restore", "run.final_eval",
    # serving tier (cat "serve", repro.serve)
    "serve.snapshot", "serve.verify", "serve.pack", "serve.batch",
    "serve.readback", "serve.flush",
})

# --- events: point-in-time markers (recorder.event) ----------------------- #
FAULT_EVENT_NAMES = frozenset({
    "fault.crash", "fault.producer_fail", "fault.producer_failover",
    "fault.block_quarantined", "fault.commit_dropped", "fault.commit_delayed",
    "fault.commit_delivered_late", "fault.ckpt_corrupted",
    "fault.ckpt_truncated",
})
EVENT_NAMES = frozenset({"compile"}) | FAULT_EVENT_NAMES

# --- counters: monotone totals (recorder.inc) ----------------------------- #
COUNTER_NAMES = frozenset({
    "compiles", "rounds.empty", "chain.blocks", "chain.tx",
    "ckpt.saved", "ckpt.restored", "fault.retry", "fault.retry_recovered",
    "serve.requests", "serve.rejected", "serve.batches", "serve.releases",
    "serve.verifications",
    # calls of the round engine's compiled entries from the round loop
    "engine.sync_step", "engine.async_step", "engine.async_merge",
}) | (FAULT_EVENT_NAMES - {"fault.commit_delivered_late"})

# --- gauges: last-written values (recorder.set_gauge) --------------------- #
GAUGE_NAMES = frozenset({
    "arena.bytes", "arena.per_device_bytes", "engine.cohort_bytes",
    "ckpt.bytes", "run.final_accuracy", "run.n_blocks",
    "serve.bank_bytes", "serve.queue_depth",
})

# --- series: per-round observations (recorder.observe / recorder.point) --- #
SERIES_NAMES = frozenset({
    "async.staleness", "async.staleness_weight", "async.staleness_mean",
    "ledger.paid", "ledger.fees", "ledger.burned",
    "serve.latency", "serve.batch_size",
})

# Dynamic families: a recorder call may build its name with an f-string as
# long as the literal prefix is registered here (schema doc: `<...>` suffix).
DYNAMIC_PREFIXES: tuple[str, ...] = ()

# recorder method -> the name set it is checked against
METHOD_NAME_SETS = {
    "span": SPAN_NAMES,
    "event": EVENT_NAMES,
    "inc": COUNTER_NAMES,
    "set_gauge": GAUGE_NAMES,
    "observe": SERIES_NAMES,
    "point": SERIES_NAMES,
}

ALL_NAMES = (SPAN_NAMES | EVENT_NAMES | COUNTER_NAMES | GAUGE_NAMES
             | SERIES_NAMES)


def is_registered(name: str, allowed: frozenset | None = None) -> bool:
    """True if ``name`` (a literal, or an f-string literal prefix ending in
    ``.``) is covered by the registry — exact match or dynamic prefix."""
    pool = ALL_NAMES if allowed is None else allowed
    if name in pool:
        return True
    return any(name.startswith(p) or p.startswith(name)
               for p in DYNAMIC_PREFIXES)
