"""§Roofline report: renders the dry-run artifacts into the per-(arch × mesh)
table required by the brief — three terms in seconds, dominant bottleneck,
MODEL_FLOPS ratio, and a one-line lever per row.

Reads experiments/dryrun/*.json (written by repro.launch.dryrun).
"""
from __future__ import annotations

import glob
import json
import os

LEVERS = {
    "compute": "more chips / lower remat multiplier / skip masked attn chunks",
    "memory": "decode is weight/cache-streaming-bound: quantise KV or batch more queries",
    "collective": "pin attention layouts, shard_map EP (MoE), sequence-parallel TP",
}


def load(dir_: str = "experiments/dryrun"):
    rows = []
    for path in sorted(glob.glob(os.path.join(dir_, "*.json"))):
        with open(path) as f:
            d = json.load(f)
        tag = os.path.basename(path).replace(".json", "")
        parts = tag.split("__")
        # arch__shape__mesh[__variant]; fl-round tags are fl-round__mesh[__variant]
        base_len = 2 if tag.startswith("fl-round") else 3
        variant = parts[base_len] if len(parts) > base_len else "baseline"
        rows.append((d, variant))
    return rows


def render(rows, include_variants: bool = True) -> str:
    lines = ["| arch | shape | mesh | variant | t_comp (ms) | t_mem (ms) | "
             "t_coll (ms) | bottleneck | useful FLOPs |",
             "|---|---|---|---|---|---|---|---|---|"]
    for d, variant in rows:
        if variant != "baseline" and not include_variants:
            continue
        lines.append(
            f"| {d['arch']} | {d['shape']} | {d['mesh']} | {variant} "
            f"| {d['t_compute']*1e3:.2f} | {d['t_memory']*1e3:.2f} "
            f"| {d['t_collective']*1e3:.2f} | {d['bottleneck']} "
            f"| {d.get('model_flops_ratio', 0):.2f} |")
    return "\n".join(lines)


def main(dir_: str = "experiments/dryrun",
         out_path: str = "experiments/roofline.md"):
    rows = load(dir_)
    if not rows:
        print("roofline,none,0,no dry-run artifacts found (run repro.launch.dryrun)")
        return
    md = render(rows)
    with open(out_path, "w") as f:
        f.write("# Roofline table (from dry-run artifacts)\n\n" + md + "\n")
    n_base = sum(1 for _, v in rows if v == "baseline")
    for d, variant in rows:
        print(f"roofline,{d['arch']}|{d['shape']}|{d['mesh']}|{variant},"
              f"{d['t_collective']*1e3:.2f},"
              f"bottleneck={d['bottleneck']} useful={d.get('model_flops_ratio', 0):.2f}",
              flush=True)
    print(f"roofline,total,{len(rows)},{n_base} baselines -> {out_path}")


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
