"""Open-loop serving of the released cluster models: ``ServeFrontend`` (the
size-bucketed request queue) in front of ``ServingEngine.forward`` (one
fused dispatch per batch over the chain-verified bank).

Set-up trains ``warm_rounds`` sync rounds (as the sync cell does), takes the
bank with ``repro.serve.snapshot`` (which publishes its release on the
chain and verifies it), builds the engine on the chain, and runs one
dispatch of every bucket size so that each compiles before the window.

The window is an open loop: ``round(rate x seconds)`` requests at times
drawn uniformly over the window and sorted (Poisson arrivals with their
count fixed, so every seed offers the same load), each for a cluster drawn
from a Zipf law over the K models and an input drawn from the shared test
split, all from the seed.  One thread submits each request when it falls
due, pumps the frontend's max-wait deadline, and sleeps until the next due
time.  A request's latency runs from its scheduled time to its completion;
a rejected request counts as never served, at the window's length.  The
window closes when the last request has been answered.

Checks, once the window has closed, against the bank the reference builds
itself: it follows the warm rounds as the sync check does (running PAA on
its own rows and judging the program's partition, then taking the cluster
means over that partition) and takes each cluster's model as the mean of
the rows last assigned to it, as ``snapshot`` defines the bank.  The bank
the window served is compared with it as a change from the initial row,
and a sample of the served requests, drawn from the seed, with the
reference's forward of its bank.

  bank_change_gap  worst leaf: | |bank - init| - |ref - init| | over
                   max(|ref - init| of the leaf, of the median leaf)
  bank_row_gap     worst leaf: |bank - ref| over the same
  logit_gap        worst |served logit - ref| over max(1, max |ref logit|)
  paa_gap          as for the sync rounds, over the warm rounds (not
                   compared, ``PERF.md`` says why)
  top_gap          widest gap by which the served class's reference logit
                   lies below the reference's best (not compared)
"""
from __future__ import annotations

import time
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference as ref
from bench.drivers import sync
from bench.federation import (
    Federation,
    build,
    control_arith,
    judge,
    leaf_norms,
    paa_gap,
    traced,
)
from bench.harness import (
    BenchError,
    RunContext,
    RunResult,
    Window,
    memory_peak_bytes,
)


def requests(traffic: dict, pd, n_models: int, seconds: float, seed: int):
    """(due times in s, cluster ids, inputs) of the window's requests."""
    rng = np.random.default_rng([seed, 7])
    n = int(round(float(traffic["rate_per_s"]) * seconds))
    due = np.sort(rng.uniform(0.0, seconds, n))
    p = 1.0 / np.arange(1, n_models + 1) ** float(traffic["zipf_s"])
    cids = rng.choice(n_models, size=n, p=p / p.sum()).astype(np.int32)
    x = pd.test_x[rng.integers(0, pd.test_x.shape[0], n)]
    return due, cids, x


def setup(fed: Federation, traffic: dict):
    from repro.serve import ServeConfig, ServeFrontend, ServingEngine, snapshot
    sync.warm_rounds(fed)
    sim = fed.sim
    bank = snapshot(sim)
    engine = ServingEngine(bank, sim.trainer.chain, obs=sim.obs)
    cfg = ServeConfig(buckets=tuple(traffic["buckets"]),
                      max_wait=float(traffic["max_wait_s"]),
                      max_pending=int(traffic["max_pending"]))
    for b in cfg.buckets:
        x = np.zeros((b, bank.mcfg.in_dim), np.float32)
        jax.block_until_ready(engine.forward(x, np.zeros((b,), np.int32)))
    fe = ServeFrontend(engine, config=cfg, clock=time.perf_counter,
                       obs=sim.obs)
    return bank, engine, fe


@dataclass
class Served:
    """What the window's requests got: completion time (nan if never
    answered), whether each was served, how late each submission ran, and
    the logits of the requests sampled for the check."""
    t_done: np.ndarray
    ok: np.ndarray
    late: np.ndarray
    sample: np.ndarray
    logits: dict[int, np.ndarray]


def sample_ids(n: int, n_sample: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng([seed, 11])
    return np.sort(rng.choice(n, size=min(n_sample, n), replace=False))


def serve_window(fe, due, cids, x, w: Window, max_wait: float,
                 sample: np.ndarray) -> Served:
    """Offer every request at its due time and collect the answers."""
    n = len(due)
    out = Served(np.full(n, np.nan), np.zeros(n, bool), np.zeros(n),
                 sample, {})
    keep = set(int(i) for i in sample)

    def collect():
        for c in fe.take_completed():
            out.t_done[c.req_id] = c.t_done
            if c.status == "ok":
                out.ok[c.req_id] = True
                if c.req_id in keep:
                    out.logits[c.req_id] = c.logits

    with w:
        t0 = w.t0
        i = 0
        while i < n or fe.queue_depth:
            now = time.perf_counter()
            while i < n and t0 + due[i] <= now:
                out.late[i] = now - (t0 + due[i])
                fe.submit(int(cids[i]), x[i])
                i += 1
                now = time.perf_counter()
            if i >= n:
                fe.drain()
            else:
                fe.pump()
            collect()
            now = time.perf_counter()
            nxt = t0 + due[i] if i < n else now
            if fe.queue_depth:
                nxt = min(nxt, fe._pending[0].t_arrival + max_wait)
            if nxt - now > 2e-4:
                time.sleep(nxt - now - 1e-4)
        collect()
        w.end()
    return out


def latencies_ms(served: Served, due: np.ndarray, w: Window) -> np.ndarray:
    """From each request's due time to its answer; a request never served
    counts at the window's length."""
    lat = np.where(served.ok, served.t_done - (w.t0 + due), w.seconds)
    return lat * 1e3


def p95(values: np.ndarray) -> float:
    """Nearest-rank 95th percentile."""
    v = np.sort(values)
    return float(v[int(np.ceil(0.95 * len(v))) - 1])


def bank_of(fed: Federation, views, state: dict[int, np.ndarray]
            ) -> np.ndarray:
    """(K, N) the bank as ``snapshot`` defines it: each cluster's mean of
    the rows last assigned to it (by ``views``' labels, arrived clients
    only), an empty cluster the mean of every labelled row, or of every
    row where none is labelled."""
    k = int(fed.config["train"]["n_clusters"])
    n = fed.pd.cx.shape[0]
    labels = np.full(n, -1)
    for v in views:
        labels[v.cohort[v.arrived]] = v.labels[v.arrived]
    rows = lambda ids: np.stack([state.get(int(i), fed.init_row)  # noqa
                                 for i in ids]).astype(np.float64)
    labeled = np.flatnonzero(labels >= 0)
    if labeled.size:
        fallback = rows(labeled).mean(0)
    else:
        fallback = rows(np.arange(n)).mean(0)
    return np.stack([rows(np.flatnonzero(labels == c)).mean(0)
                     if np.any(labels == c) else fallback for c in range(k)])


def bank_gaps(model: dict, got: np.ndarray, want: np.ndarray,
              init_row: np.ndarray) -> dict[str, float]:
    """The bank's change from the initial row against the reference's, and
    the two banks' difference, per leaf over the larger of the reference's
    change of that leaf and of the median leaf."""
    ng = leaf_norms(model, got, init_row[None])
    nr = leaf_norms(model, want, init_row[None])
    base = np.maximum(nr, np.median(nr))
    return {"bank_change_gap": float(np.max(np.abs(ng - nr) / base)),
            "bank_row_gap": float(np.max(leaf_norms(model, got, want)
                                         / base))}


def served_numbers(fed: Federation, served: Served, bank: np.ndarray, cids,
                   x, control: bool = False) -> dict[str, float]:
    """Compare the served bank and the sampled served requests with the
    reference.  With ``control`` the reference computed one step lower,
    with its own PAA, stands in the program's place: its bank, and its
    forward of that bank, replace what the window served."""
    model = fed.config["model"]
    got = sync.observed(fed)
    arith = control_arith(fed.config) if control else ref.REFERENCE
    if control:
        cstate: dict[int, np.ndarray] = {}
        got = sync.follow(fed, got, arith, cstate, own_labels=True)
        bank = bank_of(fed, got, cstate)
    state: dict[int, np.ndarray] = {}
    want = sync.follow(fed, got, state=state)
    want_bank = bank_of(fed, want, state)
    pick = np.asarray(sorted(served.logits))
    want_logits = _bank_logits(model, want_bank, cids[pick], x[pick])
    if control:
        got_logits = _bank_logits(model, bank, cids[pick], x[pick], arith)
    else:
        got_logits = np.stack([served.logits[int(r)] for r in pick])
    scale = max(1.0, float(np.abs(want_logits).max()))
    top = want_logits[np.arange(len(pick)), np.argmax(got_logits, axis=1)]
    nums = bank_gaps(model, np.asarray(bank, np.float64), want_bank,
                     fed.init_row.astype(np.float64))
    nums["paa_gap"] = paa_gap(got, want)
    nums["logit_gap"] = float(np.abs(got_logits - want_logits).max()) / scale
    nums["top_gap"] = float(np.max(want_logits.max(axis=1) - top))
    return nums


def _bank_logits(model, bank, cids, x, arith=ref.REFERENCE) -> np.ndarray:
    dt = jnp.dtype(arith[0])
    p = ref.unflatten(model, jnp.asarray(bank[cids], jnp.float32).astype(dt))
    f = jax.vmap(lambda q, xi: ref.logits(model, q, xi[None], arith[1])[0])
    return np.asarray(f(p, jnp.asarray(x).astype(dt)).astype(jnp.float32),
                      np.float64)


def run(ctx: RunContext) -> RunResult:
    tr = dict(ctx.traffic)
    tr["checked_rounds"] = tr["warm_rounds"]
    fed = build(ctx.config, tr, ctx.seed, obs=ctx.trace)
    bank, engine, fe = setup(fed, tr)
    due, cids, x = requests(tr, fed.pd, bank.n_models, ctx.seconds, ctx.seed)
    sample = sample_ids(len(due), int(tr["sample_requests"]), ctx.seed)
    compiles = dict(engine.cache_sizes())
    w = Window(ctx)
    served = serve_window(fe, due, cids, x, w, float(tr["max_wait_s"]),
                          sample)
    if engine.cache_sizes() != compiles:
        raise BenchError(f"the window compiled: {engine.cache_sizes()}")
    result = RunResult(
        e2e={"serve_p95_ms": p95(latencies_ms(served, due, w)),
             "setup_s": w.t0 - ctx.t_process},
        checks=[], attempted=len(due), failed=int((~served.ok).sum()),
        memory_peak_bytes=memory_peak_bytes())
    result.info = {"late_p95_ms": p95(served.late) * 1e3,
                   "late_max_ms": float(served.late.max()) * 1e3,
                   "served_per_s": float(served.ok.sum()) / w.seconds}
    if ctx.trace:
        result.layer = dict(traced(fed.sim.obs, ctx, w), units=fe.n_flushes)
    judge(result, ctx.workload, served_numbers(
        fed, served, np.asarray(engine.bank.data), cids, x))
    return result
