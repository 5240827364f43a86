"""The federation a cell runs, and what its checks share.

Builds the program's simulator over the benchmark's own population and
weights (both made from the seed), and holds the comparisons of what the
timed path wrote back with what the reference computed.
"""
from __future__ import annotations

import json
import os
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference as ref
from bench.harness import (
    BENCH_DIR,
    Check,
    RunContext,
    RunResult,
    Window,
    spans_in_window,
)
from bench.population import PopulationData, make_population, program_population

LIMITS_DIR = os.path.join(BENCH_DIR, "limits")


def limits_for(workload: str) -> dict:
    """The limits of the cell's compared numbers, ``bench/limits/<cell>.json``."""
    with open(os.path.join(LIMITS_DIR, workload + ".json")) as f:
        return json.load(f)["limits"]


def judge(result: RunResult, workload: str, nums: dict[str, float]) -> None:
    """The cell's compared numbers beside their limits go to the result's
    checks; a number the cell's limits leave out is not compared
    (``PERF.md`` says why) and is reported under ``info``."""
    lim = limits_for(workload)
    result.checks = [Check(k, nums[k], float(v)) for k, v in lim.items()]
    result.info.update({k: v for k, v in nums.items() if k not in lim})


def control_arith(config: dict) -> tuple[str, str]:
    """(dtype, precision) of the control: one step below what the
    configuration states, as its ``control`` entry names it."""
    return (config["control"]["dtype"], config["control"]["precision"])


def _seed_parts(seed: int) -> tuple[np.uint32, np.uint32]:
    return np.uint32(seed & 0xFFFFFFFF), np.uint32((seed >> 32) & 0xFFFFFFFF)


@jax.jit
def _key_from_seed(lo, hi):
    return jax.random.fold_in(jax.random.fold_in(jax.random.key(0), hi), lo)


def initial_weights(model: dict, seed: int, n_clients: int):
    """(n, N) arena every client starts from, made on the device in one
    jitted call from the seed; also the (N,) row itself."""
    key = _key_from_seed(*_seed_parts(seed))
    return _init_arena(ref.model_key(model), key, n_clients)


def _init_arena_impl(mk, key, n):
    model = dict(mk)
    model["hidden"] = list(model["hidden"])
    row = ref.init_row_from_key(model, key)
    return jnp.broadcast_to(row[None], (n, row.shape[0])) + 0.0, row


_init_arena = jax.jit(_init_arena_impl, static_argnums=(0, 2))


def experiment_spec(config: dict, traffic: dict, seed: int, obs: bool):
    import repro.api as api
    t, m = config["train"], config["model"]
    return api.ExperimentSpec(
        data=api.DataSpec(**config["data"]),
        train=api.TrainSpec(
            strategy=t["strategy"], rounds=10 ** 9,
            sample_frac=traffic["sample_frac"],
            n_clusters=t["n_clusters"], local_epochs=t["local_epochs"],
            lr=t["lr"], deadline=traffic.get("deadline", 30.0),
            sampler=t["sampler"], mode=traffic["mode"],
            hidden=tuple(m["hidden"]), rep_dim=m["rep_dim"]),
        async_=api.AsyncSpec(**traffic.get("async", {})),
        eval=api.EvalSpec(**traffic["eval"]),
        chain=api.ChainSpec(**config["chain"]),
        obs=api.ObsSpec(enabled=obs, block_until_ready=True),
        seed=seed)


@dataclass
class Federation:
    """A built simulator and what the benchmark keeps beside it."""
    config: dict
    traffic: dict
    seed: int
    pd: PopulationData
    sim: object
    init_row: np.ndarray
    captured: list = field(default_factory=list)


def build(config: dict, traffic: dict, seed: int, obs: bool = False
          ) -> Federation:
    from repro.sim import SimulatedFederation
    data = config["data"]
    pd = make_population(data, seed)
    pop = program_population(pd, data, seed)
    sim = SimulatedFederation(pop, experiment_spec(config, traffic, seed,
                                                   obs))
    arena, row = initial_weights(config["model"], seed, pd.cx.shape[0])
    sim.arena.rebind(arena)
    return Federation(config, traffic, seed, pd, sim, np.asarray(row))


@dataclass
class RoundView:
    """One checked round, as host arrays."""
    cohort: np.ndarray
    arrived: np.ndarray
    labels: np.ndarray
    loss: float
    rows: np.ndarray            # (k, N) the cohort's rows after the round
    prev: np.ndarray | None = None
    embedding: np.ndarray | None = None   # (k, K) PAA's spectral embedding
    own_labels: np.ndarray | None = None  # the reference's own partition


def leaf_norms(model: dict, rows: np.ndarray, prev: np.ndarray
               ) -> np.ndarray:
    """Per leaf, the norm of ``rows - prev`` over all the rows."""
    d = (rows.astype(np.float64) - prev.astype(np.float64))
    out, off = [], 0
    for _, shape in ref.layout(model):
        size = int(np.prod(shape))
        out.append(np.sqrt(np.sum(d[:, off:off + size] ** 2)))
        off += size
    return np.asarray(out)


def compare(model: dict, got: list[RoundView], want: list[RoundView]
            ) -> dict[str, float]:
    """``loss_gap``: worst relative gap of a round's mean loss.
    ``change_gap``: worst (round, leaf) gap between the norms of the
    program's and the reference's change of the written-back rows, over the
    larger of the reference's norm for that leaf and for the median leaf.
    ``row_gap``: the same worst case for the norm of the difference of the
    rows themselves, which sign flips of single updates move and norms of
    changes do not."""
    loss_gap = change_gap = row_gap = 0.0
    for g, r in zip(got, want):
        loss_gap = max(loss_gap, abs(g.loss - r.loss) / abs(r.loss))
        ng = leaf_norms(model, g.rows, r.prev)
        nr = leaf_norms(model, r.rows, r.prev)
        base = np.maximum(nr, np.median(nr))
        change_gap = max(change_gap, float(np.max(np.abs(ng - nr) / base)))
        nd = leaf_norms(model, g.rows, r.rows)
        row_gap = max(row_gap, float(np.max(nd / base)))
    return {"loss_gap": loss_gap, "change_gap": change_gap,
            "row_gap": row_gap}


def paa_gap(got: list[RoundView], want: list[RoundView]) -> float:
    """Worst round's excess k-means cost of the program's partition over
    the reference's own, both on the reference's spectral embedding, as a
    share of the cost of one cluster holding every client.  A partition is
    judged whatever its clusters are numbered."""
    gap = 0.0
    for g, r in zip(got, want):
        whole = ref.partition_cost(r.embedding, np.zeros_like(g.labels))
        gap = max(gap, (ref.partition_cost(r.embedding, g.labels)
                        - ref.partition_cost(r.embedding, r.own_labels))
                  / whole)
    return gap


def chain_checks(fed: Federation) -> dict[str, float]:
    """Verdicts of the checked rounds (``fed.captured`` holds their history
    indices) and the links of the whole chain."""
    sim = fed.sim
    blocks = {b.round_idx: b for b in sim.trainer.chain.blocks[1:]}
    errs = 0
    for r, *_ in fed.captured:
        rec = sim.history[r]
        arrived = np.asarray(rec.cohort)[np.asarray(rec.arrived)]
        if arrived.size == 0:
            continue
        committed, recorded = ref.block_commitments(blocks[rec.round_idx])
        honest = 0
        for gid in arrived:
            gid = int(gid)
            ok = committed.get(gid) is not None \
                and committed.get(gid) == recorded.get(gid)
            byz = bool(fed.pd.byzantine[gid])
            errs += int(ok == byz)
            honest += int(not byz)
        errs += int(round(rec.verified_frac * arrived.size) != honest)
    return {"verdict_errs": float(errs),
            "link_breaks": float(ref.block_link_breaks(
                sim.trainer.chain.blocks))}


def traced(recorder, ctx: RunContext, w: Window) -> dict:
    """The traced window: its trace reduction, the flight recorder's spans
    inside it, and the device's idle gaps put down to the host span that
    was open in each."""
    from bench import trace_reduce as tr
    red = tr.reduce_file(tr.find_xplane(ctx.trace_dir))
    spans = spans_in_window(recorder, w)
    offset = red.window_start_ns - w.t0_ns
    host = [(s["name"], s["t0_ns"] + offset, s["t1_ns"] + offset)
            for s in spans]
    return {"trace": red, "spans": spans, "window_s": w.seconds,
            "device_kind": jax.devices()[0].device_kind,
            "idle_by_host": tr.attribute_gaps(red.gaps, host)}


def layer_context(fed: Federation, ctx: RunContext, w: Window,
                  records: list) -> dict:
    """What the per-layer readers of a federation cell read: the traced
    window, the rounds or flushes in it (``records``, the simulator's
    history entries) and the local training they did."""
    data, train = fed.config["data"], fed.config["train"]
    return dict(
        traced(fed.sim.obs, ctx, w), units=len(records),
        model=fed.config["model"],
        arrived=int(sum(int(np.sum(h.arrived)) for h in records)),
        samples_per_client=int(data["n_batches"]) * int(data["batch_size"])
        * int(train["local_epochs"]))
