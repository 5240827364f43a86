"""FedBuff buffered-async flushes: ``SimulatedFederation._run_async``, the
simulator's event loop, whose flush calls ``RoundEngine.async_step``, the
chain round and the staleness-weighted merge.

Set-up makes the population and the weights from the seed and runs the
loop through its first ``warm_flushes`` flushes (compiling ``async_step``,
the merge and the global eval, first due at flush 5); the first
``checked_flushes`` of them are the ones the reference follows.  The window
is the same loop running on: it opens at the end of the last warm flush and
closes at the end of the first flush that finishes after ``--seconds``, on
the finished global model.  ``flush_ms`` is the window over the flushes
completed in it, the event-loop time between flushes included.

Checks, against ``bench/reference.py`` following the checked flushes from
the same weights and data (each buffered client trains from the global
model of the version it was dispatched at; the merge weighs its change by
(1 + staleness)^-alpha, zero where the chain refused it):

  loss_gap      worst flush's |loss - ref| / |ref|
  change_gap    worst (flush, leaf): | |global - prev| - |ref - prev| | over
                max(|ref - prev| of the leaf, of the median leaf)
  row_gap       worst (flush, leaf): |global - ref| over the same
  residue_errs  honest buffered clients whose committed digest is not the
                reference's fingerprint of the trained row ``async_step``
                returned for them (the fingerprint kernel, exactly)
  verdict_errs, link_breaks   as for the sync rounds
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
)
from bench.harness import (
    BenchError,
    RunContext,
    RunResult,
    Window,
    memory_peak_bytes,
)


class _WindowClosed(Exception):
    """Raised at the flush boundary that ends the window."""


def drive(fed: Federation, ctx: RunContext | None) -> tuple[Window | None,
                                                            int]:
    """Run the event loop: warm flushes, then (with ``ctx``) the window.
    Without ``ctx`` the loop stops after the warm flushes."""
    sim, tr = fed.sim, fed.traffic
    n_warm, n_check = int(tr["warm_flushes"]), int(tr["checked_flushes"])
    flush, step = sim._async_flush, sim.engine.async_step
    state = {"n": 0, "stop": None, "trained": None}
    w = Window(ctx) if ctx is not None else None

    def keep(*args):
        out = step(*args)
        if len(fed.captured) < n_check:
            state["trained"] = (np.asarray(out[0]), np.asarray(out[1]))
        return out

    def flush_and_time(agg, version, global_state, snapshots):
        buffered = [(u.client, u.version) for u in agg.buffer]
        new_version, new_global = flush(agg, version, global_state,
                                        snapshots)
        state["n"] += 1
        if len(fed.captured) < n_check:
            fed.captured.append((len(sim.history) - 1,
                                 (buffered, new_global, state["trained"])))
        if state["n"] == n_warm:
            jax.block_until_ready(new_global)
            if w is None:
                raise _WindowClosed
            w.__enter__()
            state["stop"] = w.t0 + ctx.seconds
        elif state["stop"] is not None and time.perf_counter() >= \
                state["stop"]:
            jax.block_until_ready(new_global)
            jax.block_until_ready([h.accuracy
                                   for h in sim.history[n_warm:]
                                   if not isinstance(h.accuracy, float)])
            w.end()
            raise _WindowClosed
        return new_version, new_global

    sim._async_flush, sim.engine.async_step = flush_and_time, keep
    try:
        sim._run_async()
    except _WindowClosed:
        pass
    finally:
        sim._async_flush, sim.engine.async_step = flush, step
        if w is not None and state["stop"] is not None and not w.t1:
            w.__exit__(BenchError, None, None)       # the window failed
    if ctx is not None and state["stop"] is None:
        raise BenchError("the event loop ended before the window opened")
    return w, state["n"] - n_warm


def observed(fed: Federation) -> list[RoundView]:
    """The checked flushes: the buffered clients, their dispatch versions
    (in ``labels``), the flush's mean loss and the new global model."""
    views = []
    for r, (buffered, new_global, _) in fed.captured:
        views.append(RoundView(
            cohort=np.asarray([c for c, _ in buffered]),
            arrived=np.ones(len(buffered), bool),
            labels=np.asarray([v for _, v in buffered]),
            loss=float(fed.sim.history[r].mean_loss),
            rows=np.asarray(new_global)[None]))
    return views


def follow(fed: Federation, given: list[RoundView], arith=ref.REFERENCE
           ) -> list[RoundView]:
    """The reference over the checked flushes (``labels`` of a view hold
    each buffered client's dispatch version)."""
    cfg, tr = fed.config, fed.traffic
    model, train = cfg["model"], cfg["train"]
    a = tr["async"]
    mk = ref.model_key(model)
    steps = int(train["local_epochs"]) * int(cfg["data"]["n_batches"])
    dt = jnp.dtype(arith[0])
    globals_ = {0: fed.init_row.astype(np.float64)}
    out = []
    for f, g in enumerate(given):
        base = np.stack([globals_[int(v)] for v in g.labels]) \
            .astype(np.float32)
        trained, losses = ref.train_cohort(
            mk, jnp.asarray(base), jnp.asarray(fed.pd.cx[g.cohort]),
            jnp.asarray(fed.pd.cy[g.cohort]), float(train["lr"]), steps,
            arith)
        trained = np.asarray(trained.astype(jnp.float32), np.float64)
        verified = ~fed.pd.byzantine[g.cohort]
        w = ref.fedbuff_weights(f - g.labels, verified,
                                float(a["staleness_alpha"]))
        delta = (w[:, None] * (trained - base)).sum(0) / max(w.sum(), 1e-9)
        new = globals_[f] + float(a["server_lr"]) * delta
        new = np.asarray(jnp.asarray(new, jnp.float32).astype(dt)
                         .astype(jnp.float32), np.float64)
        globals_[f + 1] = new
        out.append(RoundView(g.cohort, g.arrived, g.labels,
                             float(np.mean(np.asarray(losses, np.float64))),
                             new[None], globals_[f][None]))
    return out


def residue_errs(fed: Federation) -> float:
    """Honest buffered clients of the checked flushes whose residues, or
    whose ``model_hash`` on the chain, are not the reference's fingerprint
    of the row ``async_step`` returned for them.  Integer arithmetic is
    exact, so the limit is 0 and no lower precision stands as a control."""
    n_params = int(fed.config["model"]["n_params"])
    blocks = {b.round_idx: b for b in fed.sim.trainer.chain.blocks[1:]}
    errs = 0
    for r, (buffered, _, (rows, residues)) in fed.captured:
        committed, _ = ref.block_commitments(
            blocks[fed.sim.history[r].round_idx])
        want = ref.fingerprint(rows)
        for (client, _), res, exp in zip(buffered, residues, want):
            if fed.pd.byzantine[client]:
                continue
            digest = ref.digest(res, n_params)
            errs += int(digest != ref.digest(exp, n_params)
                        or committed.get(int(client)) != digest)
    return float(errs)


def numbers(fed: Federation, control: bool = False) -> dict[str, float]:
    got = observed(fed)
    want = follow(fed, got)
    if control:
        got = follow(fed, got, control_arith(fed.config))
    nums = compare(fed.config["model"], got, want)
    nums["residue_errs"] = residue_errs(fed)
    nums.update(chain_checks(fed))
    return nums


def run(ctx: RunContext) -> RunResult:
    fed = build(ctx.config, ctx.traffic, ctx.seed, obs=ctx.trace)
    w, n_flushes = drive(fed, ctx)
    result = RunResult(
        e2e={"flush_ms": w.seconds / n_flushes * 1e3,
             "setup_s": w.t0 - ctx.t_process},
        checks=[], attempted=n_flushes, failed=0,
        memory_peak_bytes=memory_peak_bytes())
    if ctx.trace:
        n0 = int(fed.traffic["warm_flushes"])
        result.layer = layer_context(fed, ctx, w,
                                     fed.sim.history[n0:n0 + n_flushes])
    judge(result, ctx.workload, numbers(fed))
    return result
