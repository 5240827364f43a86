import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any jax-touching import: jax locks the
# device count at first backend init, and the production meshes need 512
# placeholder host devices.  (Tests/benches never import this module.)

import argparse  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import ARCHS, SHAPES, get_config, shape_applicable  # noqa: E402
from repro.launch import sharding as shd  # noqa: E402
from repro.launch.flops import step_cost  # noqa: E402
from repro.launch.hlo import collective_bytes, collective_counts  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.specs import batch_pspecs, decode_inputs, train_batch_specs  # noqa: E402
from repro.models import lm  # noqa: E402
from repro.models.transformer import param_specs  # noqa: E402
from repro.optim import adamw  # noqa: E402

# TPU v5e hardware constants (roofline denominators)
PEAK_FLOPS = 197e12        # bf16 FLOP/s per chip
HBM_BW = 819e9             # bytes/s per chip
ICI_BW = 50e9              # bytes/s per link


def _ns(mesh, tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree,
                        is_leaf=lambda x: isinstance(x, P))


def lower_fl_round(*, multi_pod: bool, n_clients: int = 64,
                   agg_method: str = "mix", verbose: bool = True) -> dict:
    """Dry-run the paper's own technique (PAA aggregation) at pod scale."""
    from repro.launch.fl_target import FLTargetConfig, build

    cfg = FLTargetConfig(n_clients=n_clients, agg_method=agg_method)
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    with jax.set_mesh(mesh):
        jitted, args = build(cfg, mesh)
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    hlo = compiled.as_text()
    coll = collective_bytes(hlo)
    mem = compiled.memory_analysis()
    # analytical: per client fwd = 2·ψ·(in·h + h·h + h·rep); mix matmul 2·m²·Np
    n_params = cfg.in_dim * cfg.hidden + cfg.hidden ** 2 + cfg.hidden * cfg.rep_dim
    fwd = 2 * cfg.n_clients * cfg.psi * n_params
    mixmm = 2 * cfg.n_clients ** 2 * n_params
    flops = fwd + mixmm
    hbm = cfg.n_clients * n_params * 4 * 2  # read + write of stacked params
    n_chips = mesh.size
    result = {
        "arch": "fl-round-paa", "agg_method": agg_method,
        "shape": f"{cfg.n_clients}cl-100M",
        "mesh": "2x16x16" if multi_pod else "16x16", "n_chips": n_chips,
        "kind": "fl_round", "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "collective_bytes_per_device": coll,
        "collective_counts": collective_counts(hlo),
        "memory_analysis": {f: getattr(mem, f, None) for f in (
            "argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes")} if mem else {},
        "cost_model": {"flops_total": flops, "hbm_bytes": hbm,
                       "model_flops": mixmm, "n_params": n_params * cfg.n_clients},
        "t_compute": flops / n_chips / PEAK_FLOPS,
        "t_memory": hbm / n_chips / HBM_BW,
        "t_collective": coll.get("total", 0) / ICI_BW,
        "model_flops_ratio": mixmm / flops,
    }
    terms = {k: result[f"t_{k}"] for k in ("compute", "memory", "collective")}
    result["bottleneck"] = max(terms, key=terms.get)
    if verbose:
        print(f"[dryrun] fl-round-paa × {result['shape']} × {result['mesh']}: "
              f"lower {t_lower:.0f}s compile {t_compile:.0f}s | "
              f"t_comp {result['t_compute']*1e3:.2f}ms "
              f"t_mem {result['t_memory']*1e3:.2f}ms "
              f"t_coll {result['t_collective']*1e3:.2f}ms "
              f"-> {result['bottleneck']}")
    return result


def lower_combo(arch: str, shape_name: str, *, multi_pod: bool,
                verbose: bool = True, sharding_mode: str | None = None,
                swa_skip: bool = False, cap_factor: float | None = None,
                attn_constraint: bool = False,
                dm_shape: tuple[int, int] | None = None) -> dict:
    """Lower + compile one (arch × shape × mesh); return roofline raw terms."""
    import dataclasses

    cfg = get_config(arch)
    overrides = {}
    if sharding_mode:
        overrides["sharding_mode"] = sharding_mode
    if swa_skip:
        overrides["swa_skip"] = True
    if cap_factor is not None:
        overrides["capacity_factor"] = cap_factor
    if attn_constraint:
        overrides["attn_batch_axes"] = ("pod", "data") if multi_pod else ("data",)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod, dm_shape=dm_shape)
    n_chips = mesh.size

    pshape = param_specs(cfg)
    pspec = shd.param_pspecs(cfg, pshape, mesh)
    psh = _ns(mesh, pspec)

    t0 = time.time()
    with jax.set_mesh(mesh):
        if shape.kind in ("train", "prefill"):
            opt = adamw(1e-4)
            oshape = jax.eval_shape(opt.init, pshape)
            ospec = shd.opt_state_pspecs(oshape, pspec)
            osh = _ns(mesh, ospec)
            batch = train_batch_specs(cfg, shape)
            bspec = batch_pspecs(cfg, batch, mesh)
            bsh = _ns(mesh, bspec)
            if shape.kind == "train":
                step = lm.make_train_step(cfg, opt)
                jitted = jax.jit(step, in_shardings=(psh, osh, bsh),
                                 out_shardings=(NamedSharding(mesh, P()), psh, osh))
                lowered = jitted.lower(pshape, oshape, batch)
            else:  # prefill: forward-only loss (inference prefill cost)
                step = lm.make_eval_step(cfg)
                jitted = jax.jit(step, in_shardings=(psh, bsh),
                                 out_shardings=NamedSharding(mesh, P()))
                lowered = jitted.lower(pshape, batch)
        else:  # decode
            token, cache_shape = decode_inputs(cfg, shape)
            shard_batch = shape.global_batch > 1
            cspec = shd.cache_pspecs(cfg, cache_shape, mesh, shard_batch=shard_batch)
            csh = _ns(mesh, cspec)
            daxes = ("pod", "data") if multi_pod else ("data",)
            tok_spec = P(daxes, None) if shard_batch else P(None, None)
            tsh = NamedSharding(mesh, tok_spec)
            logits_sh = NamedSharding(mesh, P(daxes if shard_batch else None, None, "model"))
            step = lm.make_serve_step(cfg)
            jitted = jax.jit(step, in_shardings=(psh, csh, tsh),
                             out_shardings=(logits_sh, csh))
            lowered = jitted.lower(pshape, cache_shape, token["token"])

        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    cost = compiled.cost_analysis() or {}
    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)
    counts = collective_counts(hlo)

    flops_per_device = float(cost.get("flops", 0.0))
    bytes_per_device = float(cost.get("bytes accessed", 0.0))
    mem_fields = {}
    if mem is not None:
        for f in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes"):
            mem_fields[f] = getattr(mem, f, None)

    # analytical cost model (global) — see launch/flops.py for why HLO
    # cost_analysis alone is insufficient (scan bodies counted once)
    cost_model = step_cost(cfg, shape, swa_skip=cfg.swa_skip)
    t_compute = cost_model.flops_total / n_chips / PEAK_FLOPS
    t_memory = cost_model.hbm_bytes / n_chips / HBM_BW
    t_coll = coll.get("total", 0) / ICI_BW   # per-device bytes / per-link bw
    terms = {"compute": t_compute, "memory": t_memory, "collective": t_coll}
    bottleneck = max(terms, key=terms.get)

    dm = dm_shape or (16, 16)
    mesh_name = f"{dm[0]}x{dm[1]}" + ("" if not multi_pod else "")
    result = {
        "arch": arch,
        "shape": shape_name,
        "mesh": f"2x{mesh_name}" if multi_pod else mesh_name,
        "n_chips": n_chips,
        "kind": shape.kind,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        # raw XLA numbers (per-device; scan bodies counted once — cross-check only)
        "xla_flops_per_device": flops_per_device,
        "xla_bytes_per_device": bytes_per_device,
        "collective_bytes_per_device": coll,
        "collective_counts": counts,
        "memory_analysis": mem_fields,
        # analytical model (global)
        "cost_model": cost_model.as_dict(),
        # roofline terms in seconds
        "t_compute": t_compute,
        "t_memory": t_memory,
        "t_collective": t_coll,
        "bottleneck": bottleneck,
        "model_flops_ratio": (cost_model.model_flops / cost_model.flops_total
                              if cost_model.flops_total else 0.0),
    }
    if verbose:
        print(f"[dryrun] {arch} × {shape_name} × {result['mesh']}: "
              f"lower {t_lower:.0f}s compile {t_compile:.0f}s | "
              f"t_comp {t_compute*1e3:.2f}ms t_mem {t_memory*1e3:.2f}ms "
              f"t_coll {t_coll*1e3:.2f}ms -> {bottleneck} | "
              f"useful {result['model_flops_ratio']:.2f}")
        if mem_fields:
            print(f"         memory_analysis: {mem_fields}")
    return result


def main() -> None:
    ap = argparse.ArgumentParser(description="BFLN multi-pod dry-run")
    ap.add_argument("--arch", default="all", help="arch id, 'all', or 'fl-round'")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="both")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--sharding-mode", default=None,
                    choices=[None, "tp", "fsdp_tp", "ep_tp"],
                    help="override the arch's sharding mode (§Perf)")
    ap.add_argument("--swa-skip", action="store_true",
                    help="skip fully-masked attention chunks (§Perf)")
    ap.add_argument("--agg-method", default="mix", choices=["mix", "two_step", "two_step_bf16"],
                    help="fl-round aggregation schedule (§Perf)")
    ap.add_argument("--attn-constraint", action="store_true",
                    help="pin attention activations batch-sharded (§Perf)")
    ap.add_argument("--cap-factor", type=float, default=None,
                    help="override MoE capacity factor (§Perf)")
    ap.add_argument("--dm-shape", default=None,
                    help="override (data, model) mesh factorisation, e.g. 32x8")
    ap.add_argument("--tag", default="", help="artifact suffix for perf variants")
    args = ap.parse_args()

    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    os.makedirs(args.out, exist_ok=True)

    if args.arch == "fl-round":
        for multi_pod in meshes:
            res = lower_fl_round(multi_pod=multi_pod,
                                 agg_method=args.agg_method)
            tag = f"fl-round__{'multi' if multi_pod else 'single'}{args.tag}"
            with open(os.path.join(args.out, tag + ".json"), "w") as f:
                json.dump(res, f, indent=1)
        print("\nfl-round dry-run complete.")
        return

    archs = sorted(ARCHS) if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    failures = []
    for arch in archs:
        for shape_name in shapes:
            ok, reason = shape_applicable(arch, shape_name)
            if not ok:
                print(f"[dryrun] SKIP {arch} × {shape_name}: {reason}")
                continue
            for multi_pod in meshes:
                tag = (f"{arch}__{shape_name}__"
                       f"{'multi' if multi_pod else 'single'}{args.tag}")
                path = os.path.join(args.out, tag + ".json")
                if os.path.exists(path):
                    print(f"[dryrun] cached {tag}")
                    continue
                dm = (tuple(int(x) for x in args.dm_shape.split("x"))
                      if args.dm_shape else None)
                try:
                    res = lower_combo(arch, shape_name, multi_pod=multi_pod,
                                      sharding_mode=args.sharding_mode,
                                      swa_skip=args.swa_skip, dm_shape=dm,
                                      cap_factor=args.cap_factor,
                                      attn_constraint=args.attn_constraint)
                    with open(path, "w") as f:
                        json.dump(res, f, indent=1)
                except Exception as e:  # noqa: BLE001 — report, keep sweeping
                    failures.append((tag, repr(e)))
                    print(f"[dryrun] FAIL {tag}: {e}")
                    traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for tag, err in failures:
            print(" ", tag, err)
        raise SystemExit(1)
    print("\nAll dry-run combinations lowered + compiled successfully.")


if __name__ == "__main__":
    main()
