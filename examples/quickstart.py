"""Quickstart: BFLN vs FedAvg on skewed synthetic data in under a minute.

Two entry surfaces, one strategy registry:

  1. the legacy full-participation `FederatedTrainer` (the paper's 20-client
     protocol, shown below for bfln vs fedavg), and
  2. the declarative `repro.api.ExperimentSpec` → `run()` one-liner that
     drives the fused round engine + simulator (see
     examples/simulate_population.py for the full scenario surface).

    PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp

import repro.api as api
from repro.core import FederatedTrainer
from repro.core.fl import evaluate
from repro.models import classifier as clf
from repro.optim import adam


def main():
    n_clients, rounds, bias = 8, 5, 0.1
    data = api.load_packed_clients("synth10", n_clients, bias,
                                   probe_category=3, psi=16)
    cfg, bundle = api.make_mlp_bundle(data.in_dim, data.num_classes)

    for name in ["bfln", "fedavg"]:
        strat = api.build_strategy(name, bundle, probe=data.probe,
                                   n_clusters=3)
        sp = clf.init_stacked(cfg, jax.random.PRNGKey(0), n_clients)
        tr = FederatedTrainer(bundle, strat, adam(1e-3), local_epochs=3,
                              n_clusters=3, use_chain=(name == "bfln"))
        p = tr.fit(sp, data.cx, data.cy, data.test_x, data.test_y,
                   rounds=rounds, log_every=1)
        pacc = float(jnp.mean(evaluate(bundle.apply_fn, p,
                                       jnp.asarray(data.tx),
                                       jnp.asarray(data.ty))))
        print(f"== {name}: personalized accuracy {pacc:.4f}")
        if name == "bfln":
            print(f"   chain valid={tr.chain.validate()} "
                  f"blocks={len(tr.chain.blocks)} "
                  f"ledger conserved={tr.ledger.conserved()} "
                  f"balances={tr.ledger.balances.round(2).tolist()}")

    # the same comparison as one declarative spec per strategy, through the
    # fused round engine + event-driven simulator
    print("\n== declarative API (fused engine + simulator) ==")
    for name in ["bfln", "fedavg"]:
        spec = api.ExperimentSpec(
            data=api.DataSpec(n_clients=64, dataset="synth10", beta=bias,
                              n_batches=2, batch_size=32),
            train=api.TrainSpec(strategy=name, rounds=5, sample_frac=0.5,
                                n_clusters=3, local_epochs=3),
            eval=api.EvalSpec(every=0))
        res = api.run(spec)
        print(f"   {name}: final_acc={res.report.final_accuracy:.4f} "
              f"config_digest={res.manifest['config_digest'][:12]}")


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
