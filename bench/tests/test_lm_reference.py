"""The LoRA-adapted hybrid LM against its plain reference
(``bench/reference_lm.py``), on the CPU at a small size with seeded
weights: ``d_model`` 64, ``d_inner`` 128, ``d_state`` 4, 4 layers with one
attention layer, a 256-token vocabulary, 32-token rows.

Both sides compute in float32 here (the CPU takes float32 products at
every precision), so what separates them is the order of operations: the
program's chunked scan, blocked loss, vmapped clients and fused LoRA
products against the reference's plain loops.  Each tolerance says what
it leaves room for.
"""
import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference_lm as rlm
from bench import run
from bench.drivers import lm_sync
from bench.federation import RoundView
from bench.harness import program_precision

MODEL = {"n_layers": 4, "d_model": 64, "n_heads": 4, "n_kv_heads": 1,
         "head_dim": 16, "d_ff": 128, "vocab_size": 256,
         "attn_layer_period": 4, "attn_layer_offset": 2, "mamba_d_state": 4,
         "mamba_d_conv": 4, "mamba_expand": 2, "mamba_dt_rank": 8,
         "rms_norm_eps": 1e-6, "lora_rank": 16, "lora_alpha": 32.0}
S = 32


def small_config():
    _, _, config, traffic = run.load_cell("jamba2-3b-lora.lm-sync")
    config, traffic = copy.deepcopy(config), copy.deepcopy(traffic)
    config.update({"num_hidden_layers": 4, "attn_layer_period": 4,
                   "attn_layer_offset": 2, "hidden_size": 64,
                   "num_attention_heads": 4, "head_dim": 16,
                   "intermediate_size": 128, "vocab_size": 256,
                   "mamba_d_state": 4, "mamba_dt_rank": 8})
    config["data"].update({"n_clients": 16, "seq_len": S, "probe_len": 16})
    traffic.update({"sample_frac": 0.5, "warm_rounds": 2,
                    "checked_rounds": 2})
    return config, traffic


@pytest.fixture(scope="module")
def weights():
    base, row = lm_sync.initial_weights(MODEL, 2 ** 31 + 11)
    # a trained-looking adapter: B away from zero, so every product counts
    ad = rlm.unflatten(MODEL, row)
    keys = iter(jax.random.split(jax.random.key(1), len(ad)))
    ad = {k: v if k[2] == "a" else 0.05 * jax.random.normal(next(keys),
                                                            v.shape)
          for k, v in ad.items()}
    return base, rlm.flatten(MODEL, ad)


@pytest.fixture(scope="module")
def program(weights):
    """The program's bundle and arena layout over the reference's base."""
    import repro.api as api
    from repro.api.setup import build_model
    from repro.sim.population import ClientPopulation
    base, row = weights
    config, traffic = small_config()
    spec = lm_sync.experiment_spec(config, traffic, 0, False, MODEL)
    pop = ClientPopulation.from_spec(spec.population_spec(),
                                     spec.token_spec())
    setup = build_model(spec.train, pop, 0,
                        weights=(lm_sync.program_base(MODEL, base), row))
    assert isinstance(spec, api.ExperimentSpec)
    return setup


def tokens(seed, n=2):
    return jax.random.randint(jax.random.key(seed), (n, S), 0, 256)


def test_forward_logits_match(weights, program):
    """Logits of the program's forward and tied head against the
    reference's: 1e-5 of the largest logit leaves room for the chunked
    scan's and the fused products' float32 reassociation only."""
    base, row = weights
    x = tokens(3)
    th = program.bundle.apply_fn(program.layout.unflatten(row[None]), x)
    got = np.asarray(jnp.einsum("bsd,vd->bsv", th.hidden,
                                th.table.astype(jnp.float32))[0])
    want = np.asarray(rlm.client_logits(rlm.model_key(MODEL), base, row,
                                        x[0]))
    assert np.max(np.abs(got - want)) <= 1e-5 * np.max(np.abs(want))


def test_loss_and_lora_gradients_match(weights, program):
    """The next-token loss (the program's blocked tied-head loss) and its
    gradient with respect to every adapter leaf.  Loss: 1e-6 relative
    (float32 sums over 64 tokens).  Gradients: per leaf, the norm of the
    difference within 1e-4 of the leaf's norm, room for the backward
    pass's reassociation through four layers."""
    from repro.core.baselines import _xent
    base, row = weights
    x, y = tokens(4), tokens(5)
    layout = program.layout

    def prog_loss(r):
        return _xent(program.bundle.apply_fn(layout.unflatten(r[None]), x), y)

    pl, pg = jax.jit(jax.value_and_grad(prog_loss))(row)
    rl, rg = jax.jit(jax.value_and_grad(
        lambda r: rlm.loss(MODEL, base, r, x, y)))(row)
    assert abs(float(pl) - float(rl)) <= 1e-6 * abs(float(rl))
    off = 0
    for key, shape in rlm.row_layout(MODEL):
        size = int(np.prod(shape))
        d = np.linalg.norm(np.asarray(pg[off:off + size] - rg[off:off + size]))
        assert d <= 1e-4 * np.linalg.norm(np.asarray(rg[off:off + size])) \
            + 1e-12, key
        off += size


@pytest.mark.parametrize("chunk", [8, 5], ids=["divides", "ragged"])
def test_chunked_scan_matches_plain_scan(chunk):
    """The program's chunked, rematerialised scan against the reference's
    plain ``lax.scan``, forward and the gradients with respect to every
    input, at a chunk that divides the length and one that does not.
    1e-5 relative: the same float32 operations in the same order per step;
    only the fused reductions may round differently."""
    from repro.models.mamba import selective_scan
    B, L, di, n = 2, 21, 16, 4
    ks = jax.random.split(jax.random.key(7), 7)
    u = jax.random.normal(ks[0], (B, L, di))
    delta = jax.nn.softplus(jax.random.normal(ks[1], (B, L, di)) - 2.0)
    b = jax.random.normal(ks[2], (B, L, n))
    c = jax.random.normal(ks[3], (B, L, n))
    z = jax.random.normal(ks[4], (B, L, di))
    d = jax.random.normal(ks[5], (di,))
    a_log = jnp.log(jnp.broadcast_to(jnp.arange(1.0, n + 1), (di, n))) \
        + 0.1 * jax.random.normal(ks[6], (di, n))
    args = (u, delta, a_log, b, c, d, z)
    w = jax.random.normal(jax.random.key(8), (B, L, di))

    def prog(*a):
        return jnp.sum(selective_scan(*a, chunk=chunk) * w)

    def plain(u, delta, a_log, b, c, d, z):
        y = jax.vmap(rlm.scan, in_axes=(0, 0, None, 0, 0, None, 0))(
            u, delta, a_log, b, c, d, z)
        return jnp.sum(y * w)

    vp = jax.jit(jax.value_and_grad(prog, argnums=tuple(range(7))))
    vr = jax.jit(jax.value_and_grad(plain, argnums=tuple(range(7))))
    (yp, gp), (yr, gr) = vp(*args), vr(*args)
    assert abs(float(yp) - float(yr)) <= 1e-5 * float(jnp.sum(jnp.abs(w)))
    for name, p, r in zip(("u", "delta", "a_log", "b", "c", "d", "z"), gp, gr):
        assert np.linalg.norm(np.asarray(p - r)) <= 1e-5 * np.linalg.norm(
            np.asarray(r)), name


@pytest.fixture(scope="module")
def ran():
    """Two BFLN sync rounds of a 16-client LM federation through
    ``repro.api.run`` from the reference's weights, each round's step
    outputs kept, wrapped as the cell's driver wraps its warm rounds."""
    import repro.api as api
    import repro.api.setup as setup_mod
    import repro.sim.driver as drv
    config, traffic = small_config()
    seed = 2 ** 31 + 29
    base, row = lm_sync.initial_weights(MODEL, seed)
    real_build = setup_mod.build_model
    captured = []

    class Capturing(drv.RoundEngine):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            step = self.sync_step

            def keep(*args):
                arena, out = step(*args)
                captured.append((len(captured), out, np.asarray(out.new_rows)))
                return arena, out
            self.sync_step = keep

    spec = lm_sync.experiment_spec(config, traffic, seed, False, MODEL)
    spec = api.ExperimentSpec.from_dict(dict(
        spec.to_dict(), train=dict(spec.to_dict()["train"], rounds=2),
        eval=dict(every=0, clients=4, examples=2)))
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(setup_mod, "build_model", lambda t, p, s, weights=None:
                   real_build(t, p, s, weights=(lm_sync.program_base(
                       MODEL, base), row)))
        mp.setattr(drv, "RoundEngine", Capturing)
        with program_precision(config):
            result = api.run(spec)
    sim = result.sim
    fed = lm_sync.LMFederation(config, traffic, seed, sim.pop, sim,
                               np.asarray(row), model=MODEL, base=base,
                               forward=lm_sync.program_logits(sim),
                               scan=lm_sync.program_scan())
    fed.captured = captured
    return fed, result


def test_two_bfln_rounds_through_run_match_reference(ran):
    """The rounds against the reference over the same cohorts, arrivals,
    cluster labels and tokens.  Limits: loss 1e-5 relative (float32 sums);
    change 1e-3 and rows 0.1 of the leaf's change, room for Adam's first
    step, which moves every adapter entry by lr times the sign of its
    gradient, so the few entries whose gradient is at rounding level may
    flip sign between the two orders of summation (each flip moves the
    entry by 2 lr)."""
    fed, result = ran
    views = lm_sync.observed(fed)
    assert len(views) == 2 and all(v.arrived.any() for v in views)
    fed.want = lm_sync.follow(fed, views)
    nums = lm_sync.compare(MODEL, views, fed.want)
    assert nums["loss_gap"] <= 1e-5, nums
    assert nums["change_gap"] <= 1e-3, nums
    assert nums["row_gap"] <= 0.1, nums
    # the final eval reports the held-out next-token loss, not accuracy
    assert 0.5 * np.log(256) < result.report.final_accuracy < 3 * np.log(256)


@pytest.mark.parametrize("fault", ["", "unchanged", "half", "norms",
                                   "control"],
                         ids=["program", "unchanged", "half", "norms",
                              "control"])
def test_the_cells_limits_pass_the_program_and_fail_each_fault(ran, fault):
    """The cell's limits at the small size: the program within all of them;
    adapters left unchanged, half of each row's tokens left out, Jamba's
    Δ/B/C norms left out, or the control (the scan's state in bfloat16),
    outside at least one; the control outside ``scan_gap``."""
    from bench.federation import limits_for
    fed, _ = ran
    lim = limits_for("jamba2-3b-lora.lm-sync")
    control = fault == "control"
    with program_precision(fed.config):
        nums = lm_sync.numbers(fed, control=control,
                               fault="" if control else fault)
    fails = [k for k, v in lim.items() if nums[k] > v]
    assert (fails == []) == (fault == ""), (fault, nums)
    assert ("scan_gap" in fails) == control, (fault, nums)
