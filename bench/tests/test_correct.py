"""What decides ``correct``, at a size a test run holds, on the CPU.

The control (the reference computed one step below the configuration's
precision, put in the program's place) has to fail a number of every cell, while the program passes; and a
run driven end to end with its timed path broken underneath has to come out
not correct, once for each fault the cell can have: the state returned
unchanged, half of each batch left out, an answer altered where it is
produced.
"""
import copy
import importlib

import jax.numpy as jnp
import numpy as np
import pytest

from bench import run
from bench.federation import build as real_build
from bench.harness import program_precision

SMALL = {"bfln-xdev.sync": {}, "fedavg-xdev.sync": {},
         "fedavg-xdev.async": {"async": {"concurrency": 60}},
         "bfln-xdev.serve": {"rate_per_s": 300.0}}
LOAD_CELL = run.load_cell
DRIVER = {"sync": "bench.drivers.sync", "async": "bench.drivers.async",
          "serve": "bench.drivers.serve"}


def small(workload):
    manifest, cell, config, traffic = LOAD_CELL(workload)
    config, traffic = copy.deepcopy(config), copy.deepcopy(traffic)
    config["data"]["n_clients"] = 240
    for k, v in SMALL[workload].items():
        if isinstance(v, dict):
            traffic[k].update(v)
        else:
            traffic[k] = v
    return manifest, cell, config, traffic


def limits(workload):
    from bench.federation import limits_for
    return limits_for(workload)


def fails(nums, lim):
    return [k for k, v in lim.items() if nums[k] > v]


# --------------------------------------------------------------------------- #
# the control
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("workload", ["bfln-xdev.sync", "fedavg-xdev.sync",
                                      "fedavg-xdev.async"])
def test_control_fails_and_program_passes_federation(workload):
    _, _, config, traffic = small(workload)
    drv = importlib.import_module(DRIVER[traffic["driver"]])
    with program_precision(config):
        fed = real_build(config, traffic, 2 ** 31 + 3)
        if traffic["driver"] == "sync":
            drv.warm_rounds(fed)
        else:
            drv.drive(fed, None)
        program, control = drv.numbers(fed), drv.numbers(fed, control=True)
    lim = limits(workload)
    assert fails(program, lim) == []
    assert fails(control, lim)


def test_control_fails_and_program_passes_serving():
    """At the cell's cohort of 300 (over 1,200 clients): with the cohort
    of the small size the three-pass control's bank stays within the
    limits on some seeds."""
    from bench.drivers import serve
    from bench.harness import RunContext, Window
    _, _, config, traffic = small("bfln-xdev.serve")
    config["data"]["n_clients"] = 1200
    traffic["sample_frac"] = 0.25
    traffic["checked_rounds"] = traffic["warm_rounds"]
    with program_precision(config):
        fed = real_build(config, traffic, 2 ** 31 + 4)
        bank, engine, fe = serve.setup(fed, traffic)
        due, cids, x = serve.requests(traffic, fed.pd, bank.n_models, 0.5, 4)
        ctx = RunContext("bfln-xdev.serve", 4, 0.5, False, {}, config,
                         traffic, 0.0)
        served = serve.serve_window(fe, due, cids, x, Window(ctx), 0.005,
                                    serve.sample_ids(len(due), 128, 4))
        bank = np.asarray(engine.bank.data)
        program = serve.served_numbers(fed, served, bank, cids, x)
        control = serve.served_numbers(fed, served, bank, cids, x,
                                       control=True)
    lim = limits("bfln-xdev.serve")
    assert fails(program, lim) == []
    assert fails(control, lim)


# --------------------------------------------------------------------------- #
# faults planted in the timed path
# --------------------------------------------------------------------------- #

def sync_fault(kind):
    def wrap(step):
        def broken(arena, idx, cx, cy, arrived):
            if kind == "unchanged":
                _, out = step(jnp.copy(arena), idx, cx, cy, arrived)
                return arena, out
            if kind == "half_batch":
                h = cx.shape[2] // 2
                return step(arena, idx, cx[:, :, :h], cy[:, :, :h], arrived)
            arena, out = step(arena, idx, cx, cy, arrived)
            bad = out.new_rows.at[0].add(0.01)
            return arena.at[idx[0]].set(bad[0]), out._replace(new_rows=bad)
        return broken
    return lambda fed: setattr(fed.sim.engine, "sync_step",
                               wrap(fed.sim.engine.sync_step))


def async_fault(kind):
    def wrap(step):
        def broken(base_rows, cx, cy):
            if kind == "half_batch":
                h = cx.shape[2] // 2
                return step(base_rows, cx[:, :, :h], cy[:, :, :h])
            rows, residues, loss = step(base_rows, cx, cy)
            if kind == "unchanged":
                return base_rows, residues, loss
            if kind == "residues":
                return rows, residues.at[0, 0].add(1), loss
            return rows.at[0].add(0.01), residues, loss
        return broken
    return lambda fed: setattr(fed.sim.engine, "async_step",
                               wrap(fed.sim.engine.async_step))


def serve_fault(kind):
    from repro.serve import ServingEngine
    forward = ServingEngine.forward

    def broken(self, x, cids):
        if kind == "half_batch":
            h = max(1, len(x) // 2)
            x = x.copy()
            x[h:] = x[:1]
        out = forward(self, x, cids)
        # the first answer of every batch names another class
        if kind == "altered":
            out = out.at[0].set(jnp.roll(out[0], 1))
        return out
    return broken


CASES = [(w, k) for w in ("bfln-xdev.sync", "fedavg-xdev.sync",
                          "fedavg-xdev.async")
         for k in ("unchanged", "half_batch", "altered")] + \
    [("fedavg-xdev.async", "residues")] + \
    [("bfln-xdev.serve", k) for k in ("half_batch", "altered")]


@pytest.mark.parametrize("workload,kind", CASES)
def test_a_broken_timed_path_is_not_correct(monkeypatch, workload, kind):
    monkeypatch.setattr(run, "load_cell", lambda w: small(w))
    monkeypatch.setattr(run, "use_compile_cache", lambda: None)
    _, _, _, traffic = small(workload)
    drv = importlib.import_module(DRIVER[traffic["driver"]])
    if traffic["driver"] == "serve":
        from repro.serve import ServingEngine
        monkeypatch.setattr(ServingEngine, "forward", serve_fault(kind))
    else:
        plant = (sync_fault if traffic["driver"] == "sync"
                 else async_fault)(kind)

        def build(*a, **kw):
            fed = real_build(*a, **kw)
            plant(fed)
            return fed
        monkeypatch.setattr(drv, "build", build)
    args = run.parse_args(["--workload", workload, "--seed",
                           str(2 ** 31 + 11), "--seconds", "0.5"])
    import jax
    line = run.run(args, devices=jax.devices())
    assert line["correct"] is False, line["checks"]
