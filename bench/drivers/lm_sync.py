"""Sync federation rounds of a LoRA-adapted language model: the same
``SimulatedFederation._run_sync_round`` -> ``RoundEngine.sync_step`` path
as the ``sync`` driver, with the arena's rows the clients' adapters and the
frozen base an argument of the step.  The configuration's ``arch`` names
the program's architecture; its catalog keys and ``lora`` section give the
reference the same model.

Set-up makes the token population and the weights (the bfloat16 base and
the adapter row every client starts from) from the seed with the
benchmark's own code (``bench/lm_population.py``,
``bench/reference_lm.py``), hands both to the program, and drives the
warm rounds through the window's own round call (``bench.drivers.sync``);
the first ``checked_rounds`` are the ones the reference follows.

Checks, as the ``sync`` driver defines them, over the adapter leaves of
the reference's row layout, against ``bench/reference_lm.py`` following the
same rounds from the same weights and tokens, one client at a time:

  loss_gap      worst round's |loss - ref| / |ref| (mean next-token loss)
  change_gap    worst (round, leaf): | |rows - prev| - |ref - prev| | over
                max(|ref - prev| of the leaf, of the median leaf)
  logit_gap     max |program - reference| logits over max(1, max |ref|),
                of the first checked round's first arrived client's
                written-back row, on one held-out row, through the
                program's own forward (``ModelBundle.apply_fn``)
  scan_gap      the selective scan alone: max |program - reference| over
                max |reference| of the scan's outputs in the first layer,
                on the reference's inputs there (that client's reference
                row, the same held-out row), the program's
                ``selective_scan`` against the reference's plain scan
  verdict_errs, link_breaks   as the sync driver
  row_gap       under ``info``: worst (round, leaf) |rows - ref| over the
                change_gap's denominator
  paa_gap       under ``info``, as the sync driver

The control puts the reference with its scan state rounded to bfloat16 in
the program's place, its scan in ``scan_gap`` too; the faults
(``numbers(fed, fault=...)``) put in the reference with the adapters
returned unchanged, half of each row's tokens left out, or Jamba's Δ/B/C
norms left out, and leave the scan as the reference's.
"""
from __future__ import annotations

from dataclasses import dataclass, field, replace

import jax
import jax.numpy as jnp
import numpy as np

from bench import lm_population
from bench import reference as ref
from bench import reference_lm as rlm
from bench.drivers.sync import observed, warm_rounds, window
from bench.federation import (
    Federation,
    RoundView,
    _key_from_seed,
    _seed_parts,
    chain_checks,
    judge,
    paa_gap,
    traced,
)
from bench.harness import RunContext, RunResult, memory_peak_bytes

def model_section(config: dict) -> dict:
    """The reference's model description from the configuration's
    catalog keys and its ``lora`` section."""
    return {
        "n_layers": config["num_hidden_layers"],
        "d_model": config["hidden_size"],
        "n_heads": config["num_attention_heads"],
        "n_kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "d_ff": config["intermediate_size"],
        "vocab_size": config["vocab_size"],
        "attn_layer_period": config["attn_layer_period"],
        "attn_layer_offset": config["attn_layer_offset"],
        "mamba_d_state": config["mamba_d_state"],
        "mamba_d_conv": config["mamba_d_conv"],
        "mamba_expand": config["mamba_expand"],
        "mamba_dt_rank": config["mamba_dt_rank"],
        "rms_norm_eps": config["rms_norm_eps"],
        "lora_rank": config["lora"]["rank"],
        "lora_alpha": config["lora"]["alpha"],
    }


def program_overrides(model: dict) -> dict:
    """``TrainSpec.model_overrides`` that give the program the same model."""
    return {"n_layers": model["n_layers"], "d_model": model["d_model"],
            "n_heads": model["n_heads"], "n_kv_heads": model["n_kv_heads"],
            "head_dim": model["head_dim"], "d_ff": model["d_ff"],
            "vocab_size": model["vocab_size"],
            "attn_layer_period": model["attn_layer_period"],
            "attn_layer_offset": model["attn_layer_offset"],
            "mamba_d_state": model["mamba_d_state"],
            "mamba_d_conv": model["mamba_d_conv"],
            "mamba_expand": model["mamba_expand"]}


def program_base(model: dict, base: dict) -> dict:
    """The reference's base in the program's parameter tree (the same
    arrays: no copy)."""
    layers = []
    for pos, lw in enumerate(base["layers"]):
        p = {"norm1": {"scale": lw["norm1"]}, "norm2": {"scale": lw["norm2"]},
             "ffn": {k: lw[k] for k in ("w_gate", "w_up", "w_down")}}
        if rlm.is_attn(model, pos):
            p.update({k: lw[k] for k in ("q", "k", "v", "o")})
        else:
            m = {k: lw[k] for k in ("in_proj", "conv_w", "conv_b", "x_proj",
                                    "dt_proj", "dt_bias", "A_log", "D",
                                    "out_proj")}
            m.update({k: {"scale": lw[k]}
                      for k in ("dt_norm", "b_norm", "c_norm")})
            p["mamba"] = m
        layers.append(p)
    return {"embed": base["embed"], "final_norm": {"scale": base["final_norm"]},
            "layers": layers, "rem_layers": []}


def experiment_spec(config: dict, traffic: dict, seed: int, obs: bool,
                    model: dict):
    """The program's spec: the configuration's ``arch``, cut and changed to
    the reference's model by ``TrainSpec.model_overrides``."""
    import repro.api as api
    t, d = config["train"], dict(config["data"])
    return api.ExperimentSpec(
        data=api.DataSpec(**d, vocab_size=model["vocab_size"]),
        train=api.TrainSpec(
            strategy=t["strategy"], rounds=10 ** 9,
            sample_frac=traffic["sample_frac"],
            n_clusters=t["n_clusters"], local_epochs=t["local_epochs"],
            lr=t["lr"], deadline=traffic.get("deadline", 30.0),
            sampler=t["sampler"], mode=traffic["mode"], model=config["arch"],
            model_overrides=program_overrides(model)),
        eval=api.EvalSpec(**traffic["eval"]),
        chain=api.ChainSpec(**config["chain"]),
        obs=api.ObsSpec(enabled=obs, block_until_ready=True),
        seed=seed)


@dataclass
class LMFederation(Federation):
    model: dict = field(default_factory=dict)
    base: dict = field(default_factory=dict)     # the reference's base
    forward: object = None                       # the program's logits
    scan: object = None                          # the program's scan
    want: list | None = None                     # the reference's rounds


def initial_weights(model: dict, seed: int):
    """(base, adapter row) from the seed, made on the device."""
    return rlm.init_weights(model, _key_from_seed(*_seed_parts(seed)))


def build(config: dict, traffic: dict, seed: int, obs: bool = False
          ) -> LMFederation:
    from repro.sim import SimulatedFederation
    model = model_section(config)
    # the spec first: a program without the LM path refuses it at once
    spec = experiment_spec(config, traffic, seed, obs, model)
    from repro.models import lora
    if (model["lora_rank"], model["lora_alpha"]) != (lora.RANK, lora.ALPHA):
        raise ValueError(f"the configuration's LoRA (rank {model['lora_rank']}"
                         f", alpha {model['lora_alpha']}) is not the "
                         f"program's ({lora.RANK}, {lora.ALPHA})")
    data, vocab = config["data"], model["vocab_size"]
    pd = lm_population.make_population(data, vocab, seed)
    pop = lm_population.program_population(pd, data, vocab, seed)
    base, row = initial_weights(model, seed)
    sim = SimulatedFederation(pop, spec, weights=(program_base(model, base),
                                                  row))
    if sim.arena.layout.n_params != row.shape[0]:
        raise ValueError("the program's adapter row is not the reference's")
    return LMFederation(config, traffic, seed, pd, sim, np.asarray(row),
                        model=model, base=base, forward=program_logits(sim),
                        scan=program_scan())


def program_logits(sim):
    """(base, row, tokens (S,)) -> (S, V) logits through the program's own
    forward and tied head (``ModelBundle.apply_fn``)."""
    bundle, layout = sim.bundle, sim.arena.layout

    def logits(b, r, x):
        th = bundle.with_base(b).apply_fn(layout.unflatten(r[None]), x[None])
        return (th.hidden @ th.table.T)[0]

    return jax.jit(logits)


def program_scan():
    """The reference scan's arguments for one sequence -> the program's
    ``selective_scan`` of them, (S, di)."""
    from repro.models.mamba import selective_scan

    def scan(u, delta, a_log, b, c, d, z):
        return selective_scan(u[None], delta[None], a_log, b[None], c[None],
                              d, z[None])[0]

    return jax.jit(scan)


# --------------------------------------------------------------------------- #
# the reference over the checked rounds, and the comparison
# --------------------------------------------------------------------------- #

def follow(fed: LMFederation, given, control: bool = False, fault: str = ""
            ) -> list[RoundView]:
    """The reference over the checked rounds, with its scan state rounded
    to bfloat16 (``control``) or ``fault`` ("half", "norms") in its
    training.  Under BFLN
    it runs PAA on its own trained rows (for ``paa_gap``) and takes the
    cluster means over the labels each round was given."""
    cfg, model = fed.config, fed.model
    train = cfg["train"]
    mk = rlm.model_key(model)
    steps = int(train["local_epochs"]) * int(cfg["data"]["n_batches"])
    kc = int(train["n_clusters"])
    clustered = train["strategy"] == "bfln"
    probe = jnp.asarray(fed.pd.probe)
    seq = np.shape(fed.pd.cx)[-1]
    arith = (bool(control), fault != "norms",
             seq // 2 if fault == "half" else seq)
    rows_of: dict[int, np.ndarray] = {}
    out = []
    for g in given:
        prev = np.stack([rows_of.get(int(c), fed.init_row) for c in g.cohort])
        trained, losses = [], []
        for i, c in enumerate(g.cohort):
            r, loss = rlm.train_client(
                mk, fed.base, jnp.asarray(prev[i]), jnp.asarray(fed.pd.cx[c]),
                jnp.asarray(fed.pd.cy[c]), steps, float(train["lr"]), *arith)
            trained.append(np.asarray(r))
            losses.append(float(loss))
        trained = np.stack(trained)
        emb = mine = None
        if clustered:
            protos = np.stack([np.asarray(rlm.prototype(
                mk, fed.base, jnp.asarray(t), probe, bool(control)))
                for t in trained])
            emb = ref.spectral_embedding(ref.pearson(protos), kc)
            mine = ref.kmeans(emb, kc)
            new = ref.cluster_means(trained, g.labels,
                                    g.arrived.astype(np.float64), kc)
        else:
            new = ref.masked_mean(trained, g.arrived.astype(np.float64))
        rows = np.where(g.arrived[:, None], new, prev).astype(np.float32)
        for c, row in zip(g.cohort, rows):
            rows_of[int(c)] = row
        out.append(RoundView(g.cohort, g.arrived, g.labels,
                             float(np.mean(losses)), rows, prev, emb, mine))
    return out


def leaf_norms(model: dict, rows: np.ndarray, prev: np.ndarray) -> np.ndarray:
    d = rows.astype(np.float64) - prev.astype(np.float64)
    out, off = [], 0
    for _, shape in rlm.row_layout(model):
        size = int(np.prod(shape))
        out.append(np.sqrt(np.sum(d[:, off:off + size] ** 2)))
        off += size
    return np.asarray(out)


def compare(model: dict, got, want) -> dict[str, float]:
    loss_gap = change_gap = row_gap = 0.0
    for g, r in zip(got, want):
        loss_gap = max(loss_gap, abs(g.loss - r.loss) / abs(r.loss))
        ng = leaf_norms(model, g.rows, r.prev)
        nr = leaf_norms(model, r.rows, r.prev)
        base = np.maximum(nr, np.median(nr))
        change_gap = max(change_gap, float(np.max(np.abs(ng - nr) / base)))
        row_gap = max(row_gap, float(np.max(
            leaf_norms(model, g.rows, r.rows) / base)))
    return {"loss_gap": loss_gap, "change_gap": change_gap,
            "row_gap": row_gap}


def logit_gap(fed: LMFederation, views, control: bool = False,
              fault: str = "") -> float:
    """The checked client's logits on the first held-out row: the program's
    forward (or, for the control and the "norms" fault, the reference in
    its place) against the reference's."""
    g = views[0]
    slot = int(np.flatnonzero(g.arrived)[0]) if g.arrived.any() else 0
    row = jnp.asarray(g.rows[slot])
    tokens = jnp.asarray(fed.pd.test_x[0])
    mk = rlm.model_key(fed.model)
    want = np.asarray(rlm.client_logits(mk, fed.base, row, tokens))
    if control or fault == "norms":
        got = np.asarray(rlm.client_logits(mk, fed.base, row, tokens,
                                           control, fault != "norms"))
    else:
        got = np.asarray(fed.forward(fed.sim.bundle.base, row, tokens))
    return float(np.max(np.abs(got.astype(np.float64) - want))
                 / max(1.0, float(np.max(np.abs(want)))))


def scan_gap(fed: LMFederation, want, control: bool = False,
             fault: str = "") -> float:
    """The first layer's scan on the reference's inputs there (the first
    checked round's first arrived client's reference row, the first
    held-out row): the program's ``selective_scan`` (or the control's
    bfloat16-state scan, or under a fault the reference's own) against the
    reference's plain float32 scan."""
    g = want[0]
    slot = int(np.flatnonzero(g.arrived)[0]) if g.arrived.any() else 0
    args = rlm.first_scan_inputs(rlm.model_key(fed.model), fed.base,
                                 jnp.asarray(g.rows[slot]),
                                 jnp.asarray(fed.pd.test_x[0]))
    ref_y = np.asarray(rlm.scan_alone(*args), np.float64)
    if control:
        got = rlm.scan_alone(*args, True)
    elif fault:
        got = ref_y
    else:
        got = fed.scan(*args)
    return float(np.max(np.abs(np.asarray(got, np.float64) - ref_y))
                 / np.max(np.abs(ref_y)))


def numbers(fed: LMFederation, control: bool = False, fault: str = ""
            ) -> dict[str, float]:
    """The compared numbers of the program; or of the reference one step
    lower (``control``), or with a fault ("unchanged", "half", "norms"),
    put in the program's place.  Control and faults take the program's
    cluster labels, so that they differ from the reference in their
    arithmetic alone, and the reference's rounds are followed once."""
    got = observed(fed)
    if fed.want is None:
        fed.want = follow(fed, got)
    want = fed.want
    if control or fault in ("half", "norms"):
        got = follow(fed, got, control, fault)
    elif fault == "unchanged":
        got = [replace(w, rows=w.prev) for w in want]
    nums = compare(fed.model, got, want)
    nums["logit_gap"] = logit_gap(fed, got, control, fault)
    nums["scan_gap"] = scan_gap(fed, want, control, fault)
    if fed.config["train"]["strategy"] == "bfln":
        nums["paa_gap"] = paa_gap(got, want)
    nums.update(chain_checks(fed))
    return nums


def _tokens(fed: LMFederation) -> float:
    return fed.sim.obs.metrics.counters.get("engine.tokens", 0.0) \
        if fed.sim.obs.enabled else 0.0


def run(ctx: RunContext) -> RunResult:
    fed = build(ctx.config, ctx.traffic, ctx.seed, obs=ctx.trace)
    warm_rounds(fed)
    tokens0 = _tokens(fed)
    w, n_rounds = window(fed, ctx)
    result = RunResult(
        e2e={"round_ms": w.seconds / n_rounds * 1e3,
             "setup_s": w.t0 - ctx.t_process},
        checks=[], attempted=n_rounds, failed=0,
        memory_peak_bytes=memory_peak_bytes())
    if ctx.trace:
        data = ctx.config["data"]
        k = max(1, int(round(ctx.traffic["sample_frac"]
                             * int(data["n_clients"]))))
        result.layer = dict(
            traced(fed.sim.obs, ctx, w), units=n_rounds, model=fed.model,
            tokens=_tokens(fed) - tokens0, seq_len=int(data["seq_len"]),
            probe_tokens=k * int(data["psi"]) * int(data["probe_len"]),
            probe_len=int(data["probe_len"]))
    judge(result, ctx.workload, numbers(fed))
    return result
