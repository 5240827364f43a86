"""Simulator throughput: rounds/s and events/s across population scale,
sampling rate and mode.

Measures the event-driven federation simulator (`repro.sim`) end to end —
virtual-clock event processing + jitted cohort training + the host-side
blockchain protocol — on CPU.  The interesting scaling axes:

  * population size at fixed cohort (event machinery + ledger scale),
  * sampling rate at fixed population (cohort-training compile + run scale),
  * sync block slots vs async buffer flushes.

Prints ``sim,<name>,<us_per_round>,<derived>`` CSV like the other benches.
"""
from __future__ import annotations

import time

from repro.api import ExperimentSpec
from repro.sim import ClientPopulation, PopulationSpec, SimulatedFederation


def _warm(sim: SimulatedFederation) -> None:
    """Compile the jitted cohort program before timing (XLA compile is a
    one-time cost that would otherwise dominate a short run)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    cfg, pop = sim.cfg, sim.pop
    if cfg.mode == "sync":
        k = max(1, int(round(cfg.sample_frac * pop.n_clients)))
    else:
        k = cfg.buffer_size
    cohort = np.arange(k)
    cx, cy = pop.cohort_data(cohort)
    if sim.engine is not None:
        # arena engine: warm the fused step, then rebind (donated input)
        if cfg.mode == "sync":
            sim.arena.data, out = sim.engine.sync_step(
                sim.arena.data, jnp.asarray(cohort), cx, cy,
                jnp.zeros((k,), jnp.float32))   # zero mask: no-op scatter
            out = out.residues
        else:
            out, _, _ = sim.engine.async_step(sim.arena.data[:k], cx, cy)
    else:
        params = jax.tree.map(lambda x: x[:k], sim.params)
        if cfg.mode == "sync":
            out = sim._cohort_round(params, cx, cy, jnp.ones((k,), jnp.float32))
        else:
            out = sim._local_only(params, cx, cy)
    jax.block_until_ready(jax.tree.leaves(out)[0])


def _run_case(name: str, n_clients: int, rounds: int, **cfg_kw) -> tuple:
    spec = PopulationSpec(n_clients=n_clients, straggler_frac=0.1,
                          dropout_rate=0.03, byzantine_frac=0.05, seed=0)
    pop = ClientPopulation.from_spec(spec)
    cfg = ExperimentSpec.from_flat(rounds=rounds, eval_every=0, seed=0,
                                   **cfg_kw)
    sim = SimulatedFederation(pop, cfg)
    _warm(sim)
    t0 = time.perf_counter()
    rep = sim.run()
    wall = time.perf_counter() - t0
    us_per_round = wall / max(len(rep.history), 1) * 1e6
    ev_per_s = len(rep.event_log) / wall
    return (name, us_per_round,
            f"n={n_clients} rounds={len(rep.history)} "
            f"events={len(rep.event_log)} ev/s={ev_per_s:.0f} "
            f"acc={rep.final_accuracy:.3f}")


def main(quick: bool = True):
    rows = [
        _run_case("sync_n200_s10", 200, 6, sample_frac=0.10, n_clusters=3),
        _run_case("sync_n1000_s5", 1000, 5, sample_frac=0.05, n_clusters=5),
        _run_case("sync_n1000_s10", 1000, 5, sample_frac=0.10, n_clusters=5),
        _run_case("async_n1000_K16", 1000, 5, mode="async", buffer_size=16,
                  concurrency=64),
    ]
    if not quick:
        rows += [
            _run_case("sync_n2000_s10", 2000, 5, sample_frac=0.10,
                      n_clusters=5),
            _run_case("async_n2000_K32", 2000, 5, mode="async",
                      buffer_size=32, concurrency=128),
        ]
    for name, us, derived in rows:
        print(f"sim,{name},{us:.0f},{derived}", flush=True)
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main(quick=False)
