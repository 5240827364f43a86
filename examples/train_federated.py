"""End-to-end BFLN training driver: the paper's full protocol (Fig. 1) with
blockchain, incentives, checkpointing and resume.  ``--strategy`` swaps in
any registered baseline (the chain engages for bfln only — baselines are
the paper's chainless comparison points).

    PYTHONPATH=src python examples/train_federated.py \
        --dataset synth10 --bias 0.1 --clients 20 --clusters 5 --rounds 50

The defaults reproduce the paper's Table I hyper-parameters (20 clients,
lr 1e-3, 5 local epochs, batch 64, ρ=2, stake 5, pool 20) at a round count
that fits the CPU container; pass --rounds 50 for the paper's full budget.
"""
import argparse
import os

import jax
import jax.numpy as jnp
import numpy as np

import repro.api as api
from repro.checkpoint import restore_trainer_state, save_trainer_state
from repro.core import FederatedTrainer
from repro.core.fl import evaluate
from repro.models import classifier as clf
from repro.optim import adam


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="synth10",
                    choices=["synth10", "synth100", "synthdigits"])
    ap.add_argument("--bias", type=float, default=0.1)
    ap.add_argument("--clients", type=int, default=20)
    ap.add_argument("--strategy", default="bfln", choices=api.strategy_names())
    ap.add_argument("--clusters", type=int, default=5)
    ap.add_argument("--rounds", type=int, default=15)
    ap.add_argument("--local-epochs", type=int, default=5)
    ap.add_argument("--batch-size", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--psi", type=int, default=32)
    ap.add_argument("--ckpt", default="experiments/fed_ckpt.npz")
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args()

    data = api.load_packed_clients(args.dataset, args.clients, args.bias,
                                   batch_size=args.batch_size, psi=args.psi)
    cfg, bundle = api.make_mlp_bundle(data.in_dim, data.num_classes)
    strat = api.build_strategy(args.strategy, bundle, probe=data.probe,
                               n_clusters=args.clusters)
    tr = FederatedTrainer(bundle, strat, adam(args.lr),
                          local_epochs=args.local_epochs,
                          n_clusters=args.clusters,
                          use_chain=(args.strategy == "bfln"))

    sp = clf.init_stacked(cfg, jax.random.PRNGKey(0), args.clients)
    p, o = tr.init(sp)
    start = 0
    if args.resume and os.path.exists(args.ckpt):
        p, o, start, extra = restore_trainer_state(args.ckpt)
        print(f"resumed from round {start}")

    cx, cy = data.cx, data.cy
    xe, ye = data.test_x, data.test_y
    for r in range(start, args.rounds):
        p, o, rec = tr.run_round(r, p, o, cx, cy, xe, ye)
        chain = (f" clusters={rec.cluster_sizes.tolist()} "
                 f"producer={rec.producer} verified={rec.verified_frac:.2f}"
                 if rec.cluster_sizes is not None else "")
        print(f"round {r:3d} loss={rec.mean_loss:.4f} "
              f"acc={rec.accuracy:.4f}{chain}")
        if (r + 1) % 5 == 0:
            save_trainer_state(args.ckpt, p, o, r + 1,
                               {"dataset": args.dataset, "bias": args.bias})

    pacc = float(jnp.mean(evaluate(bundle.apply_fn, p, jnp.asarray(data.tx),
                                   jnp.asarray(data.ty))))
    print(f"\npersonalized accuracy: {pacc:.4f}")
    if tr.ledger is not None:
        print(f"chain valid: {tr.chain.validate()}  "
              f"blocks: {len(tr.chain.blocks)}  "
              f"ledger conserved: {tr.ledger.conserved()}")
        top = np.argsort(-tr.ledger.balances)[:5]
        print("top balances:",
              [(int(i), round(float(tr.ledger.balances[i]), 2)) for i in top])


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
