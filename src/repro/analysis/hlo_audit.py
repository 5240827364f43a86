"""Layer 2 of the invariant auditor: checks on the COMPILED artifacts.

Source rules can only see what the code says; this module lowers the round
engine's *actual* jitted entries (the exact programs ``repro.sim`` runs)
on a small real probe federation and audits the post-SPMD HLO text:

``hlo-donation``
    the donated sync entry's compiled module must alias its arena
    parameter to an output (``input_output_alias`` header) — a silent
    donation failure doubles peak arena memory;
``hlo-combine-collective``
    zero REDUCTION collectives (all-reduce / reduce-scatter) whose
    ``op_name`` metadata lies inside the ``cohort_combine`` named scope —
    an all-reduce there is exactly the PR 7 class of bug (GSPMD rewriting
    the replicated fixed-order combine into partial sums, 1-ULP replay
    drift).  All-gathers materialising the scope's replication pins are
    bit-preserving data movement and allowed (reported as info);
``hlo-f64``
    no op producing ``f64`` with jax x64 disabled (a hit means a python
    float silently widened through numpy);
``hlo-cache-stability``
    executing every entry twice with varying arrival masks / labels / ids
    (same shapes) leaves each jit cache at exactly one executable — the
    1-compile-per-entry contract, reused from ``RoundEngine.cache_sizes``;
``hlo-selftest``
    the detector must NOT be vacuous: a deliberately partition-unsafe toy
    (a cohort-sharded reduction inside a ``cohort_combine`` scope) must
    produce at least one attributed collective at mesh width > 1.

Run directly (the CLI uses this as a subprocess so a 1-device box can
audit a forced 8-device mesh)::

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        PYTHONPATH=src python -m repro.analysis.hlo_audit --shards 8

jax is imported lazily inside :func:`run_audit` — ``repro.analysis``
Layer 1 stays importable without it.
"""
from __future__ import annotations

import argparse
import json
import sys

from repro.analysis.findings import Finding

ENGINE_PATH = "src/repro/core/engine.py"
SERVE_ENGINE_PATH = "src/repro/serve/engine.py"
HLO_RULES = ("hlo-donation", "hlo-combine-collective", "hlo-f64",
             "hlo-cache-stability", "hlo-selftest")

# entries whose jax.jit declares donate_argnums -> the donated param indices
# (the serve engine's forward states donate_argnums=() — nothing expected)
DONATING_ENTRIES = {"sync_step": (0,)}


def _build_probe(mesh_shards: int, n_clients: int = 32, cohort_k: int = 8):
    """A small but REAL federation: the audit lowers the same entry
    programs the driver runs, not hand-built lookalikes."""
    import warnings

    import jax.numpy as jnp
    import numpy as np

    from repro.sim import (ClientPopulation, PopulationSpec, SimConfig,
                           SimulatedFederation)

    pop = ClientPopulation.from_spec(PopulationSpec(
        n_clients=n_clients, dataset="synth10", beta=0.3, n_batches=1,
        batch_size=16, seed=7))
    with warnings.catch_warnings():
        # the SimConfig shim is the stable probe surface; the audit doesn't
        # care about the ExperimentSpec migration
        warnings.simplefilter("ignore", DeprecationWarning)
        sim = SimulatedFederation(pop, SimConfig(
            rounds=1, sample_frac=cohort_k / n_clients, n_clusters=2,
            seed=7, engine=True, mesh_shards=mesh_shards))

    k = cohort_k
    cohort = np.arange(k)
    data_x, data_y = sim.step_data
    cx, cy = sim.flush_data(cohort)
    arrived = np.ones((k,), np.float32)
    ex, ey = pop.test_x[:32], pop.test_y[:32]
    # replicated (k, N) rows, exactly like the driver's flush snapshots
    rows = jnp.asarray(np.asarray(sim.arena.data[:k]))
    labels = jnp.zeros((k,), jnp.int32)
    staleness = np.arange(k) % 3
    verified = np.ones((k,), np.float32)

    entry_args = {
        "sync_step": (sim.arena.data, cohort, data_x, data_y, arrived),
        "async_step": (rows, cx, cy),
        "async_merge": (rows[0], rows, rows, staleness, verified, 0.5, 1.0),
        "eval_cohort": (rows, arrived, labels, ex, ey),
        "eval_global": (rows[0], ex, ey),
        "eval_population": (sim.arena.data, cohort, ex, ey),
    }
    # same shapes, different values — must NOT retrace
    varied = {
        "sync_step": (sim.arena.data, cohort[::-1],
                      data_x, data_y, np.r_[0.0, arrived[1:]]),
        "async_step": (rows, cx, cy),
        "async_merge": (rows[1], rows, rows[::-1], staleness[::-1],
                        np.r_[0.0, verified[1:]], 0.25, 0.5),
        "eval_cohort": (rows, np.r_[0.0, arrived[1:]],
                        labels.at[0].set(1), ex, ey),
        "eval_global": (rows[1], ex, ey),
        "eval_population": (sim.arena.data, cohort[::-1], ex, ey),
    }
    return sim, entry_args, varied


def _audit_entry(name: str, hlo_text: str, mesh_shards: int,
                 findings: list[Finding], path: str = ENGINE_PATH) -> dict:
    from repro.launch.hlo import (collective_counts, collective_lines,
                                  donated_params, f64_op_count)

    donated = sorted(donated_params(hlo_text))
    combine_all = [(comp, kind, op) for comp, kind, op
                   in collective_lines(hlo_text)
                   if "cohort_combine" in op]
    # the drift-bug class is REDUCTION collectives (partial sums whose
    # rounding path diverges from the single-device op sequence); the
    # all-gathers/all-to-alls that materialise the scope's replication
    # pins are bit-preserving data movement and expected
    combine_hits = [h for h in combine_all
                    if h[1] in ("all-reduce", "reduce-scatter")]
    f64 = f64_op_count(hlo_text)

    for idx in DONATING_ENTRIES.get(name, ()):
        if idx not in donated:
            findings.append(Finding(
                "hlo-donation", path, 0,
                f"entry `{name}` declares donate_argnums but the compiled "
                f"module does not alias param {idx} to an output "
                f"(mesh_shards={mesh_shards})",
                detail={"entry": name, "mesh_shards": mesh_shards}))
    if combine_hits:
        findings.append(Finding(
            "hlo-combine-collective", path, 0,
            f"entry `{name}` compiles {len(combine_hits)} reduction "
            f"collective(s) inside the cohort_combine scope at mesh_shards="
            f"{mesh_shards} — the combine must run replicated "
            f"(replicate-before-combine)",
            detail={"entry": name, "mesh_shards": mesh_shards,
                    "collectives": [kind for _, kind, _ in combine_hits]}))
    if f64:
        findings.append(Finding(
            "hlo-f64", path, 0,
            f"entry `{name}` compiles {f64} f64-producing op(s) with jax "
            f"x64 disabled (mesh_shards={mesh_shards})",
            detail={"entry": name, "mesh_shards": mesh_shards}))

    return {
        "donated_params": donated,
        "combine_reductions": len(combine_hits),
        "combine_data_movement": len(combine_all) - len(combine_hits),
        "f64_ops": f64,
        "collective_counts": collective_counts(hlo_text),
    }


def _audit_serve(sim, mesh_shards: int, findings: list[Finding],
                 cache_check: bool) -> dict:
    """The same compiled-artifact checks on the serving tier's mixed-batch
    forward (`repro.serve.engine`), through the REAL provenance gate: the
    probe snapshot publishes a release block on the probe chain and the
    engine refuses to build unless verification passes.  No donation is
    expected (the bank is persistent serving state); f64 leaks and the
    1-compile-per-batch-shape contract are audited like the round engine."""
    import jax
    import jax.numpy as jnp

    from repro.serve import ServingEngine, snapshot

    bank = snapshot(sim)                      # publishes + verifies
    eng = ServingEngine(bank, sim.trainer.chain)
    batch = 8
    x = jnp.linspace(-1.0, 1.0, batch * bank.mcfg.in_dim,
                     dtype=jnp.float32).reshape(batch, bank.mcfg.in_dim)
    cids = jnp.arange(batch, dtype=jnp.int32) % bank.n_models
    text = eng.lower_entry("forward", bank.data, x, cids).compile().as_text()
    info = {"forward": _audit_entry("serve_forward", text, mesh_shards,
                                    findings, path=SERVE_ENGINE_PATH)}
    if cache_check:
        # same batch shape, different values/routing — must NOT retrace
        jax.block_until_ready(eng.forward(x, cids))
        jax.block_until_ready(eng.forward(x + 1.0, cids[::-1]))
        sizes = eng.cache_sizes()
        info["cache_sizes"] = sizes
        for name, size in sizes.items():
            if size != 1:
                findings.append(Finding(
                    "hlo-cache-stability", SERVE_ENGINE_PATH, 0,
                    f"serve entry `{name}` compiled {size} executables "
                    f"across same-shape calls (mesh_shards={mesh_shards}) — "
                    f"the 1-compile-per-batch-shape contract is broken",
                    detail={"entry": name, "mesh_shards": mesh_shards}))
    return info


def _selftest(mesh_shards: int, findings: list[Finding]) -> dict:
    """Compile a deliberately partition-unsafe combine (cohort-sharded
    reduction) and prove the detector sees its collective."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.launch.hlo import collective_lines
    from repro.launch.mesh import CLIENT_AXIS, make_client_mesh

    mesh = make_client_mesh(mesh_shards)
    sharded = NamedSharding(mesh, PartitionSpec(CLIENT_AXIS))

    def unsafe_combine(x):
        x = jax.lax.with_sharding_constraint(x, sharded)
        with jax.named_scope("cohort_combine"):
            return jnp.sum(x, axis=0)

    x = jnp.ones((mesh_shards * 4, 64), jnp.float32)
    text = jax.jit(unsafe_combine).lower(x).compile().as_text()
    hits = collective_lines(text)
    attributed = [h for h in hits if "cohort_combine" in h[2]]
    if not hits:
        findings.append(Finding(
            "hlo-selftest", "src/repro/analysis/hlo_audit.py", 0,
            f"seeded partition-unsafe reduction compiled with NO detectable "
            f"collective at mesh_shards={mesh_shards} — the combine "
            f"detector is blind",
            detail={"mesh_shards": mesh_shards}))
    return {"collectives": len(hits), "attributed": len(attributed)}


def run_audit(mesh_shards: int = 1, *, cache_check: bool = True
              ) -> tuple[list[Finding], dict]:
    """Lower + audit every engine entry at ``mesh_shards``.

    Returns ``(findings, info)``; ``info`` is the per-entry summary that
    lands in the JSON report.  Requires ``len(jax.devices()) >=
    mesh_shards`` — the CLI dispatches a subprocess with forced host
    devices when it isn't.
    """
    import jax

    if len(jax.devices()) < mesh_shards:
        raise RuntimeError(
            f"audit at mesh_shards={mesh_shards} needs that many devices "
            f"(have {len(jax.devices())}); run under XLA_FLAGS="
            f"--xla_force_host_platform_device_count={mesh_shards}")

    findings: list[Finding] = []
    sim, entry_args, varied = _build_probe(mesh_shards)
    eng = sim.engine
    info: dict = {"mesh_shards": mesh_shards, "entries": {}}

    for name in eng.entry_names():
        text = eng.lower_entry(name, *entry_args[name]).compile().as_text()
        info["entries"][name] = _audit_entry(name, text, mesh_shards,
                                             findings)

    # serve audit first: the engine cache check below EXECUTES sync_step,
    # whose donation deletes the probe arena the snapshot reads
    info["serve"] = _audit_serve(sim, mesh_shards, findings, cache_check)

    if cache_check:
        # run order matters: sync_step donates the arena, and
        # eval_population reads it — exercise the donating entry last,
        # chaining its returned arena into the second call
        raw = eng._entries
        for name in eng.entry_names():
            if name in DONATING_ENTRIES:
                continue
            jax.block_until_ready(raw[name](*entry_args[name]))
            jax.block_until_ready(raw[name](*varied[name]))
        arena, _ = raw["sync_step"](*entry_args["sync_step"])
        _, idx, cx, cy, arrived = varied["sync_step"]
        arena, _ = raw["sync_step"](arena, idx, cx, cy, arrived)
        jax.block_until_ready(arena)
        sizes = eng.cache_sizes()
        info["cache_sizes"] = sizes
        for name, size in sizes.items():
            if size != 1:
                findings.append(Finding(
                    "hlo-cache-stability", ENGINE_PATH, 0,
                    f"entry `{name}` compiled {size} executables across "
                    f"same-shape calls (mesh_shards={mesh_shards}) — the "
                    f"1-compile-per-entry contract is broken",
                    detail={"entry": name, "mesh_shards": mesh_shards}))

    if mesh_shards > 1:
        info["selftest"] = _selftest(mesh_shards, findings)

    return findings, info


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis.hlo_audit",
        description="compiled-artifact audit of the round engine's entries")
    ap.add_argument("--shards", type=int, default=1,
                    help="mesh width to audit (needs that many devices)")
    ap.add_argument("--no-cache-check", action="store_true",
                    help="skip the execute-twice jit-cache stability check")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write machine-readable results ('-' for stdout)")
    args = ap.parse_args(argv)

    findings, info = run_audit(args.shards,
                               cache_check=not args.no_cache_check)
    doc = {
        "findings": [{"rule": f.rule, "path": f.path, "line": f.line,
                      "message": f.message, "detail": f.detail}
                     for f in sorted(findings)],
        "info": info,
    }
    if args.json == "-":
        json.dump(doc, sys.stdout, sort_keys=True)
        sys.stdout.write("\n")
    else:
        if args.json:
            with open(args.json, "w") as f:
                json.dump(doc, f, sort_keys=True, indent=1)
                f.write("\n")
        for f_ in findings:
            print(f_.format())
        print(f"hlo audit @ mesh_shards={args.shards}: "
              f"{len(findings)} finding(s)")
    return 1 if findings else 0


if __name__ == "__main__":
    sys.exit(main())
