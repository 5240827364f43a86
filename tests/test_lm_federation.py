"""A LoRA-adapted hybrid LM (AI21-Jamba2-3B's block) federated through the
normal sync path: the model interface, the frozen base as an argument of the
compiled entries, the arena of adapter rows, the trace names, and the paths
that refuse the model.  Small sizes on the CPU; the comparison with the
plain reference is in ``bench/tests/test_lm_reference.py``."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.api as api
from repro.configs import ARCHS, FEDERATED_ARCHS
from repro.models import lora

OVERRIDES = {"d_model": 64, "mamba_d_state": 4, "n_layers": 4,
             "attn_layer_period": 4, "attn_layer_offset": 2,
             "vocab_size": 256, "n_heads": 4, "n_kv_heads": 1,
             "head_dim": 16, "d_ff": 128}


VOCAB = 16384     # a 2 MB bfloat16 embedding: over the constant test's 1 MB


def lm_spec(vocab=VOCAB, rounds=2, mode="sync", obs=None, **over):
    """``obs``: a trace path to record to, or None."""
    return api.ExperimentSpec(
        data=api.DataSpec(n_clients=16, dataset="markov-topics", n_batches=1,
                          batch_size=1, seq_len=32, vocab_size=vocab, psi=4,
                          probe_len=16, byzantine_frac=0.1),
        train=api.TrainSpec(strategy="bfln", rounds=rounds, sample_frac=0.5,
                            n_clusters=2, lr=2e-4, mode=mode,
                            model="jamba2-3b",
                            model_overrides=dict(OVERRIDES, vocab_size=vocab,
                                                 **over)),
        async_=api.AsyncSpec(buffer_size=4, concurrency=4),
        eval=api.EvalSpec(every=1, clients=4, examples=2),
        obs=api.ObsSpec(enabled=True, trace_path=obs) if obs
        else api.ObsSpec(), seed=5)


@pytest.fixture(scope="module")
def result(tmp_path_factory):
    return api.run(lm_spec(obs=str(tmp_path_factory.mktemp("lm") / "t.jsonl")))


def _step_args(sim):
    k = max(1, int(round(sim.cfg.sample_frac * sim.pop.n_clients)))
    return (sim.arena.data, np.arange(k, dtype=np.int64), *sim.step_data,
            np.ones(k, np.float32), sim.bundle.base)


def test_published_widths_and_adapter_row():
    """The config keeps Jamba2-3B's published widths; at the cell's 14
    layers the arena row is the 4,509,696 adapter values (18.0 MB), the
    1.6 B-parameter base stays out of it."""
    cfg = FEDERATED_ARCHS["jamba2-3b"](n_layers=14)
    assert ARCHS["jamba2-3b"].n_layers == 28
    assert (cfg.d_model, cfg.mamba_d_inner, cfg.mamba_d_state,
            cfg.mamba_d_conv, cfg.mamba_dt_rank) == (2560, 5120, 16, 4, 160)
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_ff,
            cfg.vocab_size) == (20, 1, 128, 8192, 65536)
    assert [s.mixer for s in cfg.pattern].count("attn") == 1
    assert cfg.pattern[7].mixer == "attn" and not cfg.pattern[7].rope
    shapes = jax.eval_shape(lambda: lora.init_adapters(
        cfg, jax.random.PRNGKey(0)))
    assert sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes)) \
        == 4_509_696


def test_arena_row_is_the_adapter_count(result):
    sim = result.sim
    shapes = jax.eval_shape(lambda: lora.init_adapters(
        sim.model.arch, jax.random.PRNGKey(0)))
    n_adapters = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    assert sim.arena.data.shape == (16, n_adapters)
    assert sim.arena.layout.n_params == n_adapters
    n_base = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(
        sim.bundle.base))
    assert n_base > 10 * n_adapters


def test_lm_round_trains_and_reports_held_out_loss(result):
    rep = result.report
    assert len(rep.history) == 2 and rep.chain_valid
    for h in rep.history:
        assert np.isfinite(h.mean_loss)
        # the eval of a token model is its mean held-out next-token loss
        assert 0.5 * np.log(VOCAB) < h.accuracy < 3 * np.log(VOCAB)
    assert result.sim.engine.cache_sizes()["sync_step"] == 1


def test_tokens_counter_counts_the_trained_tokens(result):
    """``engine.tokens``: every cohort slot's rows × row length a round."""
    counters = result.sim.obs.metrics.counters
    assert counters["engine.tokens"] == 2 * 8 * 32
    assert counters["engine.sync_step"] == 2


def _constants(text: str) -> list[int]:
    """Bytes of every constant in lowered StableHLO text."""
    sizes = []
    width = {"f32": 4, "bf16": 2, "i32": 4, "ui32": 4, "i64": 8, "f64": 8,
             "i1": 1, "i8": 1, "ui8": 1, "f16": 2}
    for line in text.splitlines():
        if "stablehlo.constant" not in line:
            continue
        m = re.findall(r"tensor<([0-9x]*)([a-z]+[0-9]*)>", line)
        if m:
            dims, dt = m[-1]
            n = int(np.prod([int(d) for d in dims.split("x") if d])) \
                if dims else 1
            sizes.append(n * width.get(dt, 4))
    return sizes


def test_lm_sync_step_carries_no_large_constant(result):
    """The frozen base is an argument of ``sync_step``: no constant of the
    lowered program exceeds 1 MB, while the same step closing over the base
    (its 2 MB embedding here) would carry it."""
    sim = result.sim
    args = _step_args(sim)
    assert sim.bundle.base["embed"].nbytes >= 2 ** 21
    text = sim.engine.lower_entry("sync_step", *args).as_text()
    assert max(_constants(text)) < 2 ** 20
    closed = jax.jit(lambda *a: sim.engine.sync_step(*a, args[-1]))
    assert max(_constants(closed.lower(*args[:-1]).as_text())) >= 2 ** 21


def test_lm_scopes_in_the_compiled_sync_step(result):
    """The model's named scopes label the step's device ops (forward and
    backward), as the per-layer readers find them in a trace."""
    sim = result.sim
    hlo = sim.engine.lower_entry("sync_step", *_step_args(sim)) \
        .compile().as_text()
    names = re.findall(r'op_name="([^"]*)"', hlo)
    for scope in ("mamba_scan", "attention", "lora", "lm_loss", "paa",
                  "local_train", "scatter_back"):
        assert any(re.search(r"(^|[/(])" + scope + r"([)/]|$)", n)
                   for n in names), scope
    # the scan's backward pass (inside the transposed layer) is named too
    assert any("transpose(" in n and "/mamba_scan/" in n for n in names)
    from repro.obs.names import SCOPE_NAMES
    assert {"mamba_scan", "attention", "lora", "lm_loss"} <= SCOPE_NAMES


def test_serving_refuses_the_lm(result):
    from repro.serve.snapshot import snapshot
    with pytest.raises(ValueError, match="serv"):
        snapshot(result)


@pytest.mark.parametrize("kw,match", [
    ({"mode": "async"}, "sync rounds only"),
], ids=["async"])
def test_async_refuses_the_lm(kw, match):
    from repro.sim import ClientPopulation, SimulatedFederation
    spec = lm_spec(**kw)
    with pytest.raises(ValueError, match=match):
        SimulatedFederation(ClientPopulation.from_spec(
            spec.population_spec(), spec.token_spec()), spec)


def test_legacy_driver_refuses_the_lm():
    from repro.sim import ClientPopulation, SimulatedFederation
    spec = lm_spec()
    spec = api.ExperimentSpec.from_dict(dict(spec.to_dict(), engine=False))
    with pytest.raises(ValueError, match="round engine only"):
        SimulatedFederation(ClientPopulation.from_spec(
            spec.population_spec(), spec.token_spec()), spec)


def test_lm_spec_round_trips_and_validates():
    spec = lm_spec()
    assert api.ExperimentSpec.from_json(spec.to_json()) == spec
    assert spec.config_digest() != lm_spec(n_layers=8).config_digest()
    with pytest.raises(ValueError, match="unknown model"):
        api.TrainSpec(model="no-such-arch")
    with pytest.raises(ValueError, match="vocab_size"):
        api.DataSpec(dataset="markov-topics", seq_len=32)


def test_token_population_is_non_iid_and_packed():
    """Rows are documents packed end to end (targets are the inputs shifted
    by one), and clients' topic mixes differ."""
    from repro.data.lm import token_population, topic_mixes
    x, y, tx, ty, test_x, test_y, probe = token_population(
        6, 2, 64, 512, 8, 0.3, 4, 32, seed=1)
    assert x.shape == (6, 2, 64) and test_x.shape == (16, 64)
    assert probe.shape == (4, 32)
    np.testing.assert_array_equal(x[:, 0, 1:], y[:, 0, :-1])
    mix = topic_mixes(200, 8, 0.3, np.random.default_rng(0))
    assert np.mean(mix.max(axis=1)) > 0.4         # skewed, not near 1/8


def test_scan_chunk_does_not_change_the_forward():
    from repro.models.mamba import selective_scan
    ks = jax.random.split(jax.random.key(0), 5)
    u = jax.random.normal(ks[0], (1, 19, 8))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (1, 19, 8)))
    b, c = (jax.random.normal(k, (1, 19, 4)) for k in ks[2:4])
    z = jax.random.normal(ks[4], (1, 19, 8))
    a_log = jnp.zeros((8, 4))
    d = jnp.ones((8,))
    whole = selective_scan(u, dt, a_log, b, c, d, z, chunk=19)
    for chunk in (1, 4, 7):
        np.testing.assert_allclose(selective_scan(u, dt, a_log, b, c, d, z,
                                                  chunk=chunk), whole,
                                   rtol=1e-6, atol=1e-6)
