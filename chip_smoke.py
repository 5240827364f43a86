"""Chip smoke test: the federation round and the serving tier on a TPU.

Drives the main path once, in one process, through the entry points a user
calls, at the full width of the model the repo federates — the
``ExperimentSpec()`` defaults: BFLN over 1000 synth10 clients, cohorts of
k=100 (``sample_frac=0.10``), MLP ``hidden=(64,)``, ``rep_dim=32``, weights
drawn from ``--seed``.

One chip (no arguments), three phases:

  sync   ``api.run`` for 3 rounds: chain valid, ledger conserved, one compile
         per used engine entry, the arena on the TPU, the Mosaic fingerprint
         kernel inside the compiled ``sync_step``, and the kernel's residues
         on the chip equal to ``fingerprint_ref`` on the same rows, bit for
         bit;
  async  the same spec in FedBuff mode for 3 flushes, with the same checks
         on the chain and the compile counts;
  serve  ``snapshot`` -> ``ServingEngine`` -> ``ServeFrontend`` answers 40
         mixed-cluster requests; the fused outputs agree with per-request
         routing within ``SERVE_TOL``, and a tampered bank is refused.

``--chips 4`` runs only the mesh phase: the sync spec with
``MeshSpec(shards=4)`` (cohort sharded) and, in the same process, with
``shards=1`` on device 0.  Both chains must be valid, final accuracies
agree within ``MESH_ACC_TOL``, the arena must split four ways, and the
sharded step's fingerprint kernel must see a quarter of the cohort per
device.

    python chip_smoke.py [--chips 4] [--seed 0]

It never falls back to the CPU: without a TPU it exits non-zero and prints
no result.  Each phase prints one line; the last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import re
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import repro.api as api  # noqa: E402
from repro.kernels.fingerprint import (  # noqa: E402
    fingerprint_pallas,
    poly_weights,
)
from repro.kernels.ref import fingerprint_ref  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.runtime.arena import bitcast_u32  # noqa: E402
from repro.serve import (  # noqa: E402
    ProvenanceError,
    ServeFrontend,
    ServingEngine,
    snapshot,
    tampered,
    verify_bank,
)
from repro.sim.clock import VirtualClock  # noqa: E402

ROUNDS = 3
SERVE_REQUESTS = 40     # one full 32-bucket flush + a drained 8-bucket batch
# fused mixed-batch logits vs per-request routing: |diff| <= SERVE_TOL *
# max(1, max |logit|).  The CPU contract is bitwise.  On the chip an f32
# matmul at default precision takes one bf16 pass, which rounds each operand
# to 8 significant bits (a product is off by up to 2^-8), and the two paths
# do not round the same operands: the batch-of-one per-request dots need not
# take that pass at all.  Three layers compound to about 3 * 2^-8 of the
# logit scale; the tolerance allows 2^-5
SERVE_TOL = 2.0 ** -5
# final accuracy, four chips vs one: a prediction flipped by a last-bit
# difference moves it by 1/(eval clients x examples) per flip
MESH_ACC_TOL = 0.02


class SmokeFailure(RuntimeError):
    """A check of the smoke test failed."""


def require(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def _spec(seed: int, **train) -> api.ExperimentSpec:
    return api.ExperimentSpec(train=api.TrainSpec(rounds=ROUNDS, **train),
                              seed=seed)


def _check_run(name: str, res) -> dict[str, int]:
    """Chain and compile-count checks every federation run must pass."""
    m = res.manifest
    require(m["chain_valid"], f"{name}: chain invalid")
    require(m["ledger_conserved"], f"{name}: ledger not conserved")
    require(m["rounds_run"] == ROUNDS,
            f"{name}: ran {m['rounds_run']} of {ROUNDS}")
    used = {k: v for k, v in m["engine_compile_counts"].items() if v}
    require(all(v == 1 for v in used.values()),
            f"{name}: an engine entry recompiled: {used}")
    return used


def _last_cohort_args(sim):
    """The last round's sync_step arguments (the arena is not consumed:
    these are only lowered)."""
    rec = next(r for r in reversed(sim.history) if r.arrived.any())
    return (sim.arena.data, rec.cohort, *sim.step_data,
            rec.arrived.astype(np.float32)), rec.cohort


def _kernel_rows(hlo: str) -> list[int]:
    """Rows of each fingerprint kernel's (rows, 256) lane-accumulator output
    in a compiled module — per device on a mesh, padded to the 8-row block."""
    return [int(r) for r in re.findall(
        r"= u32\[(\d+),256\]\S* custom-call\([^)]*\), "
        r"custom_call_target=\"tpu_custom_call\"", hlo)]


def sync_phase(seed: int):
    t0 = time.perf_counter()
    res = api.run(_spec(seed))
    wall = time.perf_counter() - t0
    used = _check_run("sync", res)
    require("sync_step" in used, "sync: sync_step never ran")
    sim = res.sim
    devs = sim.arena.data.devices()
    require(len(devs) == 1 and next(iter(devs)).platform == "tpu",
            f"sync: arena not on one TPU device: {devs}")

    args, cohort = _last_cohort_args(sim)
    hlo = sim.engine.lower_entry("sync_step", *args).compile().as_text()
    rows = _kernel_rows(hlo)
    require(len(rows) == 1, f"sync: {len(rows)} Mosaic kernels in sync_step")

    # kernel on the chip vs the jnp oracle on the chip, same device rows
    u32 = bitcast_u32(sim.arena.data[jnp.asarray(cohort)])
    kern = np.asarray(jax.jit(fingerprint_pallas)(u32))
    ref = np.asarray(jax.jit(fingerprint_ref)(
        u32, jnp.asarray(poly_weights(u32.shape[1]))))
    require(np.array_equal(kern, ref),
            "sync: kernel residues differ from fingerprint_ref")
    m = res.manifest
    print(f"sync: wall_s={wall:.3f} rounds={m['rounds_run']} "
          f"compiles={used} chain_valid={m['chain_valid']} "
          f"ledger_conserved={m['ledger_conserved']} "
          f"final_accuracy={m['final_accuracy']:.4f} "
          f"arena={tuple(sim.arena.data.shape)}@{next(iter(devs))} "
          f"mosaic_kernels_in_sync_step={len(rows)} "
          f"residues_equal_ref={kern.shape[0]}x2 bitwise", flush=True)
    return res


def async_phase(seed: int) -> None:
    t0 = time.perf_counter()
    res = api.run(_spec(seed, mode="async"))
    wall = time.perf_counter() - t0
    used = _check_run("async", res)
    require("async_step" in used, "async: async_step never ran")
    m = res.manifest
    print(f"async: wall_s={wall:.3f} flushes={m['rounds_run']} "
          f"compiles={used} chain_valid={m['chain_valid']} "
          f"ledger_conserved={m['ledger_conserved']} "
          f"final_accuracy={m['final_accuracy']:.4f}", flush=True)


def serve_phase(res, seed: int) -> None:
    t0 = time.perf_counter()
    chain = res.sim.trainer.chain
    bank = snapshot(res)
    engine = ServingEngine(bank, chain)
    fe = ServeFrontend(engine, clock=VirtualClock())
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((SERVE_REQUESTS, bank.mcfg.in_dim)
                            ).astype(np.float32)
    cids = np.arange(SERVE_REQUESTS) % bank.n_models
    for i in range(SERVE_REQUESTS):
        fe.submit(int(cids[i]), x[i])
    fe.drain()
    done = sorted(fe.take_completed(), key=lambda c: c.req_id)
    require(len(done) == SERVE_REQUESTS
            and all(c.status == "ok" for c in done),
            "serve: not every request was answered")
    fused = np.stack([c.logits for c in done])
    ref = np.asarray(engine.forward_per_request(x, cids))
    require(np.all(np.isfinite(fused)), "serve: non-finite logits")
    diff = np.abs(fused - ref)
    scale = max(1.0, float(np.abs(ref).max()))
    require(float(diff.max()) <= SERVE_TOL * scale,
            f"serve: fused vs per-request max |diff| {diff.max()} exceeds "
            f"{SERVE_TOL} x {scale}")
    # which path departs from full f32: both against HIGHEST precision
    with jax.default_matmul_precision("highest"):
        exact = np.asarray(engine.forward_per_request(x, cids))
    agree = float(np.mean(fused.argmax(1) == ref.argmax(1)))
    # one compile per batch bucket served: the full 32 flush and the drain's 8
    compiles = engine.cache_sizes()
    require(compiles == {"forward": 2}, f"serve: compiles {compiles}")
    wall = time.perf_counter() - t0

    bad = tampered(bank, cluster_id=1)
    try:
        verify_bank(bad, chain)
    except ProvenanceError:
        pass
    else:
        raise SmokeFailure("serve: tampered bank verified")
    try:
        ServingEngine(bad, chain)
    except ProvenanceError:
        pass
    else:
        raise SmokeFailure("serve: engine loaded a tampered bank")
    print(f"serve: wall_s={wall:.3f} requests={len(done)} "
          f"clusters={bank.n_models} flushes={fe.n_flushes} "
          f"compiles={compiles} max_abs_diff_vs_per_request={float(diff.max())!r} "
          f"logit_scale={scale!r} bitwise_equal="
          f"{bool(np.array_equal(fused, ref))} "
          f"argmax_agreement={agree!r} max_abs_diff_vs_highest: fused="
          f"{float(np.abs(fused - exact).max())!r} per_request="
          f"{float(np.abs(ref - exact).max())!r} "
          f"tampered_bank_refused=True", flush=True)


def mesh_phase(seed: int) -> None:
    spec1 = _spec(seed)
    spec4 = dataclasses.replace(
        spec1, mesh=api.MeshSpec(shards=4, cohort="sharded"))
    t0 = time.perf_counter()
    r4 = api.run(spec4)
    wall4 = time.perf_counter() - t0
    t0 = time.perf_counter()
    r1 = api.run(spec1)
    wall1 = time.perf_counter() - t0
    used4 = _check_run("mesh4", r4)
    used1 = _check_run("mesh1", r1)

    s4, s1 = r4.sim, r1.sim
    shard_devs = {s.device for s in s4.arena.data.addressable_shards}
    require(len(shard_devs) == 4, f"mesh4: arena on {len(shard_devs)} devices")
    require(s1.arena.data.devices() == {jax.devices()[0]},
            "mesh1: arena not on device 0")
    n, n_params = s1.arena.data.shape
    quarter = -(-n // 4) * n_params * 4          # a quarter, plus row padding
    per_dev = s4.arena.per_device_bytes()
    require(per_dev == quarter,
            f"mesh4: {per_dev} arena bytes per device, expected {quarter}")
    require(s4.engine.cohort_mode == "sharded"
            and s4.engine.cohort_shards == 4,
            f"mesh4: cohort mode {s4.engine.cohort_mode}"
            f"x{s4.engine.cohort_shards}")
    args, cohort = _last_cohort_args(s4)
    rows = _kernel_rows(
        s4.engine.lower_entry("sync_step", *args).compile().as_text())
    k_dev = -(-len(cohort) // 4)
    require(len(rows) == 1 and rows[0] == -(-k_dev // 8) * 8,
            f"mesh4: kernel rows per device {rows}, cohort {len(cohort)}")

    m4, m1 = r4.manifest, r1.manifest
    dacc = abs(m4["final_accuracy"] - m1["final_accuracy"])
    require(dacc <= MESH_ACC_TOL,
            f"mesh: final accuracy differs by {dacc} > {MESH_ACC_TOL}")
    same_events = m4["event_log_digest"] == m1["event_log_digest"]
    same_blocks = m4["block_hashes_digest"] == m1["block_hashes_digest"]
    print(f"mesh: wall_s_4={wall4:.3f} wall_s_1={wall1:.3f} "
          f"compiles_4={used4} compiles_1={used1} "
          f"chain_valid={m4['chain_valid']},{m1['chain_valid']} "
          f"final_accuracy={m4['final_accuracy']!r},{m1['final_accuracy']!r} "
          f"arena_bytes_per_device={per_dev} of {n * n_params * 4} "
          f"on {len(shard_devs)} devices "
          f"kernel_rows_per_device={rows[0]} (cohort {len(cohort)}/4 "
          f"padded to 8) event_log_digest_equal={same_events} "
          f"block_hashes_digest_equal={same_blocks}", flush=True)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip mesh phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU found (JAX platform "
                         f"{devices[0].platform!r}); nothing was run")
    if len(devices) < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} needs "
                         f"{args.chips} TPU devices, found {len(devices)}")
    use_compile_cache()
    if args.chips == 4:
        mesh_phase(args.seed)
    else:
        res = sync_phase(args.seed)
        async_phase(args.seed)
        serve_phase(res, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
