"""Run one cell of ``BENCHMARK.json`` once, on the accelerator this process
is started on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell names a configuration (``bench/configs/<config>.json``) and a
traffic mix (``bench/traffic/<traffic>.json``); the traffic file names the
driver (``bench/drivers/<driver>.py``) that builds the set-up, runs the
measured window and checks what the window produced against the plain
reference (``bench/reference.py``).  With ``--trace 1`` the window runs under
the profiler and the program's flight recorder, and each per-layer metric of
the cell is read by its own reader, ``bench/metrics/<metric>.py``.  A reader
that finds nothing to read returns ``None`` and its metric is left out.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, ``breakdown`` (traced
runs), ``info`` (what the cell adds, such as how late its request generator
ran) and, last, ``checks``: each number compared beside its limit.  Without an
accelerator, or with fewer chips than the cell asks for, the run exits
non-zero and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(_ROOT, "src"), _ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench.harness import (  # noqa: E402
    BENCH_DIR,
    ROOT,
    BenchError,
    RunContext,
    program_precision,
)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(workload: str, root: str = ROOT) -> tuple[dict, dict, dict,
                                                        dict]:
    """(manifest, cell, configuration, traffic) for the named cell, each
    found by its name: the configuration through its manifest entry's
    ``file``, the traffic as ``bench/traffic/<traffic>.json``."""
    manifest = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise BenchError(f"no workload {workload!r} in BENCHMARK.json; "
                         f"cells: {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in manifest["configs"]}
    config = load_json(os.path.join(root, configs[cell["config"]]["file"]))
    traffic = load_json(os.path.join(root, "bench", "traffic",
                                     cell["traffic"] + ".json"))
    return manifest, cell, config, traffic


def load_driver(kind: str):
    return importlib.import_module(f"bench.drivers.{kind}")


def load_metric(name: str, bench_dir: str = BENCH_DIR):
    path = os.path.join(bench_dir, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(manifest: dict, workload: str, kind: str) -> list[dict]:
    """The manifest's metrics of ``kind`` (``end_to_end`` or ``per_layer``)
    that this cell reports."""
    return [m for m in manifest[kind]
            if workload in m.get("workloads", [workload])]


def require_accelerator(chips: int):
    """The devices to run on; no accelerator or too few is an error."""
    import jax
    devices = jax.devices()
    if devices[0].platform == "cpu":
        raise BenchError("no accelerator: JAX found only the CPU; the "
                         "benchmark does not fall back to it")
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX found "
                         f"{len(devices)}")
    return devices[:chips]


def use_compile_cache(root: str = ROOT) -> str:
    """JAX's persistent cache at a fixed path inside the checkout (or where
    ``JAX_COMPILATION_CACHE_DIR`` says), every program cached."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        root, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def device_record(devices, trace_red=None, peak: int = 0) -> dict:
    d = {"platform": devices[0].platform, "kind": devices[0].device_kind,
         "count": len(devices), "memory_peak_bytes": int(peak)}
    if trace_red is not None:
        d["busy_s"] = trace_red.busy_s
        d["window_s"] = trace_red.window_s
    return d


def read_layers(manifest: dict, workload: str, layer: dict) -> dict:
    out = {}
    for m in cell_metrics(manifest, workload, "per_layer"):
        value = load_metric(m["name"]).read(layer)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def breakdown(layer: dict) -> dict:
    from bench.trace_reduce import short_op
    by_name: dict[str, float] = {}
    for name, sec in layer["trace"].op_s.items():
        by_name[short_op(name)] = by_name.get(short_op(name), 0.0) + sec
    ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in layer.get("idle_by_host",
                                                      [])[:10]]}


def run(args, t_process: float = T_PROCESS, devices=None) -> dict:
    manifest, cell, config, traffic = load_cell(args.workload)
    t_devices = None
    if devices is None:
        devices = require_accelerator(int(cell["chips"]))
        t_devices = time.perf_counter()
    use_compile_cache()
    ctx = RunContext(workload=args.workload, seed=int(args.seed),
                     seconds=float(args.seconds), trace=bool(args.trace),
                     cell=cell, config=config, traffic=traffic,
                     t_process=t_process)
    if ctx.trace:
        ctx.trace_dir = os.path.join(ROOT, ".bench_traces",
                                     f"{args.workload}-{args.seed}")
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
    with program_precision(config):
        res = load_driver(traffic["driver"]).run(ctx)
    if t_devices is not None:
        # the part of set-up spent before JAX had found the chip
        res.info["device_init_s"] = t_devices - t_process
    wanted = {m["name"] for m in cell_metrics(manifest, args.workload,
                                              "end_to_end")}
    units = {m["name"]: m["unit"] for m in manifest["end_to_end"]}
    if ctx.trace:
        metrics = read_layers(manifest, args.workload, res.layer)
    else:
        metrics = {k: {"value": v, "unit": units[k]}
                   for k, v in res.e2e.items() if k in wanted}
    line = {
        "correct": all(c.ok for c in res.checks) and bool(res.checks),
        "attempted": res.attempted, "failed": res.failed,
        "metrics": metrics,
        "device": device_record(devices, res.layer.get("trace"),
                                res.memory_peak_bytes),
    }
    if ctx.trace and "trace" in res.layer:
        line["breakdown"] = breakdown(res.layer)
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
    if res.info:
        line["info"] = res.info
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                      for c in res.checks}
    return line


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    try:
        line = run(args)
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for name, v in line.get("info", {}).items():
        print(f"info {name} = {v!r}", file=sys.stderr)
    for name, c in line["checks"].items():
        print(f"check {name} = {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stdout.flush()
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
