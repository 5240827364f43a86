"""Sync federation rounds: ``SimulatedFederation._run_sync_round``, the
round the simulator runs, which calls the fused ``RoundEngine.sync_step``.

Set-up makes the population and the initial weights from the seed, builds
the simulator, and drives its first rounds through the same round call the
window makes: they compile every entry the window uses (the cohort eval
first runs at the first eval round), and the first ``checked_rounds`` of
them are the ones the reference follows.  The window then runs whole
rounds until ``--seconds`` have passed and ends on finished device work;
``round_ms`` is the window over the rounds completed in it.

Checks, from what those rounds left behind (per round: the cohort's rows
read back from the arena the step returned, the cluster labels and the
mean local loss; and the chain's blocks), against ``bench/reference.py``
following the same rounds from the same weights and data.  The reference
runs PAA itself on its own trained rows (prototypes of the probe batch,
Pearson, spectral embedding, k-means) and scores the program's partition
on that embedding; its cluster means are then taken over the program's
partition, so that the rows can be compared row by row.  A number the
cell's limits leave out is reported under ``info`` and not compared.

  loss_gap      worst round's |loss - ref| / |ref|
  change_gap    worst (round, leaf): | |rows - prev| - |ref - prev| | over
                max(|ref - prev| of the leaf, of the median leaf)
  row_gap       worst (round, leaf): |rows - ref| over the same
  paa_gap       worst round's excess k-means cost of the program's
                partition over the reference's, on the reference's
                embedding, as a share of one cluster's cost (BFLN only;
                not compared, ``PERF.md`` says why)
  verdict_errs  arrived clients whose verdict, recomputed from the block's
                records, is not "verified" for an honest client or
                "refused" for a Byzantine one, plus rounds whose verified
                share differs from that count
  link_breaks   blocks whose ``prev`` is not the hash of the block before
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference as ref
from bench.federation import (
    Federation,
    RoundView,
    build,
    chain_checks,
    compare,
    control_arith,
    judge,
    layer_context,
    paa_gap,
)
from bench.harness import RunContext, RunResult, Window, memory_peak_bytes


def warm_rounds(fed: Federation) -> None:
    """The set-up's rounds, through the window's own round call; the first
    ``checked_rounds`` keep their step outputs for the check."""
    sim, n_check = fed.sim, int(fed.traffic["checked_rounds"])
    step = sim.engine.sync_step

    def keep(*args):
        arena, out = step(*args)
        if len(fed.captured) < n_check:
            rows = np.asarray(arena[args[1]])
            fed.captured.append((len(sim.history), out, rows))
        return arena, out

    sim.engine.sync_step = keep
    try:
        for r in range(int(fed.traffic["warm_rounds"])):
            sim.history.append(sim._run_sync_round(r))
    finally:
        sim.engine.sync_step = step
    jax.block_until_ready(sim.arena.data)


def window(fed: Federation, ctx: RunContext) -> tuple[Window, int]:
    sim = fed.sim
    r0 = r = int(fed.traffic["warm_rounds"])
    w = Window(ctx)
    with w:
        stop = w.t0 + ctx.seconds
        while True:
            sim.history.append(sim._run_sync_round(r))
            r += 1
            if time.perf_counter() >= stop:
                break
        jax.block_until_ready(sim.arena.data)
        jax.block_until_ready([h.accuracy for h in sim.history[r0:]
                               if not isinstance(h.accuracy, float)])
        w.end()
    return w, r - r0


def observed(fed: Federation) -> list[RoundView]:
    views = []
    for r, out, rows in fed.captured:
        rec = fed.sim.history[r]
        views.append(RoundView(
            cohort=np.asarray(rec.cohort), arrived=np.asarray(rec.arrived),
            labels=np.asarray(out.labels), loss=float(out.mean_loss),
            rows=rows))
    return views


def follow(fed: Federation, given: list[RoundView], arith=ref.REFERENCE,
           state: dict[int, np.ndarray] | None = None,
           own_labels: bool = False) -> list[RoundView]:
    """The reference over the checked rounds, in ``arith`` (dtype,
    precision); the control's ``arith`` is one step lower.  Under BFLN it runs PAA on its own trained rows, and takes its
    cluster means over the labels each round was given (``own_labels``:
    over its own PAA labels, as the control in the program's place does).
    ``state`` collects the reference's row of every client the rounds
    touched."""
    cfg = fed.config
    model, train = cfg["model"], cfg["train"]
    mk = ref.model_key(model)
    steps = int(train["local_epochs"]) * int(cfg["data"]["n_batches"])
    k_clusters = int(train["n_clusters"])
    clustered = train["strategy"] == "bfln"
    dt = jnp.dtype(arith[0])
    state = {} if state is None else state
    out = []
    for g in given:
        prev = np.stack([state.get(int(c), fed.init_row) for c in g.cohort])
        trained, losses = ref.train_cohort(
            mk, jnp.asarray(prev), jnp.asarray(fed.pd.cx[g.cohort]),
            jnp.asarray(fed.pd.cy[g.cohort]), float(train["lr"]), steps,
            arith)
        emb = mine = None
        labels = g.labels
        if clustered:
            emb, mine = ref.paa(mk, trained, jnp.asarray(fed.pd.probe),
                                k_clusters, arith)
            labels = mine if own_labels else g.labels
        trained = np.asarray(trained.astype(jnp.float32))
        w = g.arrived.astype(np.float64)
        if clustered:
            new = ref.cluster_means(trained, labels, w, k_clusters)
        else:
            new = ref.masked_mean(trained, w)
        rows = np.where(g.arrived[:, None], new, prev)
        rows = np.asarray(jnp.asarray(rows, jnp.float32).astype(dt)
                          .astype(jnp.float32))
        for c, row in zip(g.cohort, rows):
            state[int(c)] = row
        out.append(RoundView(g.cohort, g.arrived, labels,
                             float(np.mean(np.asarray(losses, np.float64))),
                             rows, prev, emb, mine))
    return out


def numbers(fed: Federation, control: bool = False) -> dict[str, float]:
    """The compared numbers of the program, or of the reference computed
    one step lower put in its place (``control``)."""
    got = observed(fed)
    if control:
        got = follow(fed, got, control_arith(fed.config), own_labels=True)
    want = follow(fed, got)
    nums = compare(fed.config["model"], got, want)
    if fed.config["train"]["strategy"] == "bfln":
        nums["paa_gap"] = paa_gap(got, want)
    nums.update(chain_checks(fed))
    return nums


def run(ctx: RunContext) -> RunResult:
    fed = build(ctx.config, ctx.traffic, ctx.seed, obs=ctx.trace)
    warm_rounds(fed)
    w, n_rounds = window(fed, ctx)
    result = RunResult(
        e2e={"round_ms": w.seconds / n_rounds * 1e3,
             "setup_s": w.t0 - ctx.t_process},
        checks=[], attempted=n_rounds, failed=0,
        memory_peak_bytes=memory_peak_bytes())
    if ctx.trace:
        r0 = int(fed.traffic["warm_rounds"])
        result.layer = layer_context(fed, ctx, w,
                                     fed.sim.history[r0:r0 + n_rounds])
    judge(result, ctx.workload, numbers(fed))
    return result
