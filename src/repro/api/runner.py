"""`run(spec) -> ExperimentResult` — the one way to run an experiment.

Builds the population from ``spec.data``, drives the event-driven simulator
(every strategy goes through the fused, arena-backed round engine unless
``spec.engine=False``), and returns the report together with a *manifest*:
a flat, JSON-able record stamped with the spec's ``config_digest`` so any
result can be traced to — and replayed from — the exact configuration that
produced it.
"""
from __future__ import annotations

import hashlib
import json
from dataclasses import dataclass, field
from typing import Any

from repro.api.spec import ExperimentSpec
from repro.obs import console_summary, write_jsonl
from repro.sim import ClientPopulation, SimReport, SimulatedFederation


def event_log_digest(event_log) -> str:
    """SHA-256 over the full (virtual-time, kind, client) event stream —
    same seed + same spec ⇒ same digest, across engine on/off and mesh
    widths."""
    return hashlib.sha256(
        json.dumps(event_log, sort_keys=False).encode()).hexdigest()


@dataclass
class ExperimentResult:
    spec: ExperimentSpec
    report: SimReport
    manifest: dict[str, Any] = field(default_factory=dict)
    # the live simulator behind the run — carries the trained arena, the
    # chain, and the virtual clock so `repro.serve.snapshot/serve` can turn
    # a finished run into a serving tier.  Excluded from repr/comparison:
    # results compare by what they report, not by runtime identity.
    sim: Any = field(default=None, repr=False, compare=False)

    def summary(self) -> str:
        m = self.manifest
        line = (f"[{m['strategy']}/{m['mode']}] {self.report.summary()} "
                f"config_digest={m['config_digest'][:12]}")
        t = m.get("timing")
        if t:
            unit = "flush" if m.get("mode") == "async" else "round"
            line += (f"\n  timing: {unit} p50={t.get('round_ms_p50', 0):.1f}ms"
                     f" p99={t.get('round_ms_p99', 0):.1f}ms")
            if "chain_overhead_pct" in t:
                line += f" chain={t['chain_overhead_pct']:.1f}%"
            line += f" compiles={t.get('compiles', 0)}"
        return line


def build_manifest(spec: ExperimentSpec, sim: SimulatedFederation,
                   report: SimReport) -> dict[str, Any]:
    """The reproducibility record: config digest first, then everything a
    replay must reproduce bit for bit."""
    manifest: dict[str, Any] = {
        "config_digest": spec.config_digest(),
        "strategy": spec.train.strategy,
        "mode": spec.train.mode,
        "sampler": spec.train.sampler,
        "engine": spec.engine,
        "mesh_shards": spec.mesh.shards,
        "seed": spec.seed,
        "n_clients": sim.pop.n_clients,
        "rounds_run": len(report.history),
        "event_log_digest": event_log_digest(report.event_log),
        "block_hashes_digest": hashlib.sha256("".join(
            b.block_hash() for b in sim.trainer.chain.blocks
        ).encode()).hexdigest(),
        "n_blocks": report.n_blocks,
        "chain_valid": report.chain_valid,
        "ledger_conserved": report.ledger_conserved,
        "balances_digest": hashlib.sha256(
            report.balances.tobytes()).hexdigest(),
        "final_accuracy": report.final_accuracy,
    }
    if sim.engine is not None:
        manifest["engine_compile_counts"] = sim.engine.cache_sizes()
    if sim.ckpt is not None:
        manifest["checkpoints_written"] = sim._ckpt_written
        manifest["checkpoint_bytes"] = sim._ckpt_bytes
    if sim._resumed_from is not None:
        manifest["resumed_from"] = sim._resumed_from[0]
        manifest["resume_step"] = sim._resumed_from[1]
    return manifest


def format_manifest(manifest: dict[str, Any]) -> str:
    return "\n".join(f"  {k}: {v}" for k, v in manifest.items())


def run(spec: ExperimentSpec, population: ClientPopulation | None = None,
        resume_from: str | None = None) -> ExperimentResult:
    """Run one experiment end to end.

    ``population`` may be passed explicitly to reuse an already-materialised
    population across experiments (e.g. strategy sweeps over the same
    shards); by default it is built from ``spec.data`` with ``spec.seed``.
    A supplied population must match the spec — the manifest stamps the
    spec's ``config_digest`` as the replay recipe, which only holds if the
    population is the one ``spec.data``/``spec.seed`` would rebuild.

    ``resume_from`` restores a snapshot written by ``spec.checkpoint`` (a
    file path, or a checkpoint directory whose newest readable snapshot is
    used) and continues the run from that boundary.  The snapshot's stamped
    ``resume_digest`` must match the spec's — obs/checkpoint/faults sections
    are free to differ (so a crashed run can be resumed with its fault
    schedule cleared), everything else must be the same experiment.  A
    resumed run finishes with manifest digests bit-identical to the
    uninterrupted run's.
    """
    if population is None:
        population = ClientPopulation.from_spec(spec.population_spec())
    elif population.spec != spec.population_spec():
        raise ValueError(
            "supplied population was built from a different PopulationSpec "
            "than spec.data/spec.seed would rebuild — the manifest's "
            f"config_digest would not replay this run.\n  population: "
            f"{population.spec}\n  spec:       {spec.population_spec()}")
    sim = SimulatedFederation(population, spec)
    profile_dir = spec.obs.profile_dir if spec.obs.enabled else None
    if profile_dir is not None:
        import jax
        with jax.profiler.trace(profile_dir):
            report = sim.run(resume_from=resume_from)
    else:
        report = sim.run(resume_from=resume_from)
    manifest = build_manifest(spec, sim, report)
    if sim.obs.enabled:
        _emit_trace(spec, sim, manifest)
    return ExperimentResult(spec, report, manifest, sim=sim)


def _emit_trace(spec: ExperimentSpec, sim: SimulatedFederation,
                manifest: dict[str, Any]) -> None:
    """Flush the flight recorder's sinks and stamp the trace digest into the
    manifest.  Strictly post-run: by construction nothing here can perturb
    the simulation it describes."""
    obs = sim.obs
    meta = {k: manifest[k] for k in
            ("config_digest", "strategy", "mode", "engine", "mesh_shards",
             "seed", "n_clients", "rounds_run")}
    digest = write_jsonl(spec.obs.trace_path, meta, obs.records, obs.metrics)
    manifest["trace_path"] = spec.obs.trace_path
    manifest["trace_digest"] = digest
    manifest["timing"] = obs.timing_summary()
    if spec.obs.console:
        print(console_summary(
            obs.metrics, title=f"trace {spec.train.strategy}/"
            f"{spec.train.mode} -> {spec.obs.trace_path}"))
