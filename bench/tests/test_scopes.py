"""The program's names read out of a trace: host spans, op scopes from the
compiled text, device time per scope, and the four readers that use them,
on planes and text built by hand, on the CPU-compiled round step, and on
recorded chip traces."""
import os
import re
import shutil
from types import SimpleNamespace as NS

import numpy as np
import pytest

from bench import readers, scopes
from bench import trace_reduce as tr
from bench.run import load_metric

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def ev(name, start, dur):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur))


def planes():
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev("bench.window", 1000, 9000),
        ev("round.total", 500, 4000),          # starts before the window
        ev("round.schedule", 600, 1400),       # clipped to [1000, 2000]
        ev("round.total", 5000, 5500),         # ends after it
        ev("round.schedule", 5100, 400),
        ev("PjitFunction(add)", 5200, 100)])])  # not a flight-recorder span
    op_while = "%while.143 = (f32[8]) while(f32[8] %p), body=%region_0"
    op_body = "%fusion.7 = f32[8] fusion(f32[8] %x), kind=kLoop"
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[ev("jit__sync_step(12)", 1500, 3000),
                                       ev("jit__sync_step(12)", 6000, 2000),
                                       ev("jit_other(3)", 8500, 1000)]),
        NS(name="XLA Ops", events=[
            ev(op_while, 1600, 1000),          # paa loop ...
            ev(op_body, 1700, 300),            # ... and its body, nested
            ev(op_body, 2200, 200),
            ev("%copy.1 = f32[8] copy(f32[8] %y)", 3000, 500),
            ev(op_while, 6100, 500),
            ev(op_body, 8600, 100)])])         # a paa op of another program
    return [host, dev], op_while, op_body


def test_host_spans_are_registered_names_clipped_to_the_window():
    pl, _, _ = planes()
    got = scopes.host_spans(pl, (1000.0, 10000.0))
    assert got == [("round.total", 1000.0, 4500.0),
                   ("round.schedule", 1000.0, 2000.0),
                   ("round.total", 5000.0, 10000.0),
                   ("round.schedule", 5100.0, 5500.0)]


@pytest.mark.parametrize("op_name, scope", [
    ("jit(_sync_step)/cohort_combine/paa/while/body/dot_general", "paa"),
    ("jit(_sync_step)/cohort_combine/cluster_means/add", "cluster_means"),
    ("jit(_sync_step)/local_train/while/body/jvp(dot_general)",
     "local_train"),
    ("jit(_sync_step)/local_train/vmap()/while/body/gather", "local_train"),
    ("jit(_sync_step)/gather/gather:", "gather"),          # a trace's tf_op
    ("jit(_sync_step)/cohort_combine/add", None),
    ("jit(_sync_step)/cohort_combine/paa", None),         # paa is the op
    ("jit(paa_round)/dot_general", None),
])
def test_innermost_scope(op_name, scope):
    assert scopes.innermost_scope(op_name) == scope


def test_op_scopes_from_compiled_text():
    text = "\n".join([
        "%region_0 (p: f32[8]) -> f32[8] {",
        '  %fusion.7 = f32[8]{0} fusion(f32[8]{0} %x), kind=kLoop, '
        'metadata={op_name="jit(_sync_step)/cohort_combine/paa/while/body/'
        'mul" stack_frame_id=4}',
        '  ROOT %copy.1 = f32[8]{0} copy(%y)',
        "}",
        '  %while.143 = (f32[8]{0}) while(%t), condition=%c, body=%region_0,'
        ' metadata={op_name="jit(_sync_step)/cohort_combine/paa/while"}',
        '  ROOT %scatter.2 = f32[9,8]{1,0} scatter(%a, %i, %u), '
        'metadata={op_name="jit(_sync_step)/scatter_back/scatter"}',
        '  %add.3 = f32[] add(%a, %b), metadata={op_name="jit(f)/add"}'])
    assert scopes.op_scopes(text) == {"%fusion.7": "paa",
                                      "%while.143": "paa",
                                      "%scatter.2": "scatter_back"}


def test_scope_time_is_the_union_inside_the_module_runs():
    pl, op_while, op_body = planes()
    by_name = {op_while: "paa", op_body: "paa"}
    by_instr = {"%while.143": "paa", "%fusion.7": "paa"}
    for table in (by_name, by_instr):
        seconds, runs = scopes.scope_time(pl, (1000.0, 10000.0), table,
                                          "jit__sync_step", "paa")
        # [1600, 2600] holds both body runs; [6100, 6600]; the op at 8600
        # is another program's
        assert seconds == pytest.approx(1500e-9)
        assert runs == 2
    assert scopes.scope_time(pl, (1000.0, 10000.0), by_name,
                             "jit__sync_step", "scatter_back")[0] == 0.0


@pytest.mark.parametrize("table, want_ns", [
    # the body ops alone are tagged: the first loop takes their scope,
    # [1600, 2600]; the second holds no tagged op and stays unscoped
    ({"%fusion.7": "paa"}, 1000),
    # an op of another scope nested in the first loop: it stays unscoped,
    # and only the body ops count, [1700, 2000] and [2200, 2400]
    ({"%fusion.7": "paa", "%copy.1": "gather"}, 500),
])
def test_control_flow_ops_take_their_bodys_scope(table, want_ns):
    pl, _, _ = planes()
    dev = pl[1].lines[1].events
    dev.insert(3, ev("%copy.1 = f32[8] copy(f32[8] %y)", 2450, 100))
    seconds, _ = scopes.scope_time(pl, (1000.0, 10000.0), table,
                                   "jit__sync_step", "paa")
    assert seconds == pytest.approx(want_ns * 1e-9)


def _layer(red, units, spans=()):
    return {"trace": red, "units": units, "spans": list(spans),
            "window_s": red.window_s}


@pytest.mark.parametrize("flushes, want", [
    ([{"n": 32, "bucket": 32, "wait_sum_us": 64000.0, "wait_max_us": 4e3},
      {"n": 8, "bucket": 8, "wait_sum_us": 16000.0, "wait_max_us": 5e3}],
     2.0),
    ([{"n": 32, "bucket": 32}], None),      # a program without the attrs
    ([], None),
])
def test_serve_queue_ms(flushes, want):
    spans = [{"name": "serve.flush", "dur_us": 1700.0, "attrs": a}
             for a in flushes]
    got = load_metric("serve_queue_ms").read({"spans": spans})
    assert got == (None if want is None else pytest.approx(want))


@pytest.mark.parametrize("metric", ["paa_device_ms.sync",
                                    "schedule_ms.sync",
                                    "dispatch_ms.async"])
def test_trace_readers_find_nothing_without_their_trace(metric, tmp_path,
                                                        monkeypatch):
    """With no trace file under ``.bench_traces/`` for the window (or no
    traced window at all), a reader returns None and does not raise."""
    monkeypatch.setattr(scopes, "TRACES_DIR", str(tmp_path))
    red = tr.reduce_planes(planes()[0])
    mod = load_metric(metric)
    assert mod.read(_layer(red, 2)) is None
    assert mod.read({"spans": [], "units": 2}) is None


def _sync_step_text(strategy: str) -> str:
    """The CPU-compiled text of a small federation's ``sync_step``."""
    import jax.numpy as jnp

    import repro.api as api
    from repro.sim import ClientPopulation, SimulatedFederation
    spec = api.ExperimentSpec(
        data=api.DataSpec(n_clients=24, dataset="synth10", n_batches=1,
                          batch_size=8),
        train=api.TrainSpec(strategy=strategy, rounds=1, sample_frac=0.25,
                            n_clusters=2, local_epochs=1),
        seed=1)
    pop = ClientPopulation.from_spec(spec.population_spec())
    sim = SimulatedFederation(pop, spec)
    k = 6
    cx, cy = pop.cohort_data(np.arange(k))
    args = (sim.arena.data, jnp.arange(k, dtype=jnp.int32), cx, cy,
            jnp.ones((k,), jnp.float32))
    return sim.engine.lower_entry("sync_step", *args).compile().as_text()


@pytest.mark.parametrize("strategy", ["bfln", "fedavg"])
def test_compiled_sync_step_carries_the_scopes(strategy):
    found = set(scopes.op_scopes(_sync_step_text(strategy)).values())
    assert {"gather", "local_train", "cluster_means", "fingerprint",
            "scatter_back"} <= found
    assert ("paa" in found) == (strategy == "bfln")


def test_parent_chip_trace_has_no_step_scopes():
    """The recorded ``fedavg-xdev.sync`` window predates the scopes: its
    ops carry ``cohort_combine`` at most, so nothing maps to a scope."""
    path = os.path.join(DATA, "fedavg-xdev.sync.xplane.pb")
    assert scopes.trace_op_scopes(path) == {}


# ---------------------------------------------------------------------- #
# a recorded chip window with the spans as annotations and the scopes
# ---------------------------------------------------------------------- #

CHIP = os.path.join(DATA, "bfln-xdev.sync.scoped.xplane.pb")


@pytest.fixture(scope="module")
def chip():
    from jax.profiler import ProfileData
    pl = list(ProfileData.from_file(CHIP).planes)
    red = tr.reduce_planes(pl)
    _, runs = red.module_time("jit__sync_step")
    return pl, red, runs


def test_chip_trace_host_spans_nest_in_each_round(chip):
    pl, red, runs = chip
    window = (red.window_start_ns, red.window_start_ns + red.window_s * 1e9)
    names = [n for n, _, _ in scopes.host_spans(pl, window)]
    for name in ("round.total", "round.schedule", "round.step",
                 "round.chain", "round.record"):
        assert names.count(name) == runs, name


def test_chip_trace_scopes_and_their_time(chip):
    pl, red, runs = chip
    table = scopes.trace_op_scopes(CHIP)
    assert {"gather", "local_train", "paa", "cluster_means", "fingerprint",
            "scatter_back"} <= set(table.values())
    # the fingerprint kernel, found by its text, is in its scope
    kernel = [n for n in table if re.search(readers.FINGERPRINT_OP, n)]
    assert kernel and all(table[n] == "fingerprint" for n in kernel)
    window = (red.window_start_ns, red.window_start_ns + red.window_s * 1e9)
    step_s, n = red.module_time("jit__sync_step")
    paa_s, paa_runs = scopes.scope_time(pl, window, table,
                                        "jit__sync_step", "paa")
    assert paa_runs == n == runs
    assert 0 < paa_s <= step_s


def test_chip_trace_idle_goes_to_named_host_work(chip):
    """Put down from the trace's own annotations, the window's idle time
    falls under named spans: at most a tenth under ``host.other`` or the
    self time of ``round.total``."""
    pl, red, _ = chip
    window = (red.window_start_ns, red.window_start_ns + red.window_s * 1e9)
    idle = dict(tr.attribute_gaps(red.gaps, scopes.host_spans(pl, window)))
    unnamed = idle.get("host.other", 0.0) + idle.get("round.total", 0.0)
    assert unnamed <= 0.1 * sum(idle.values())


def test_chip_trace_readers(chip, tmp_path, monkeypatch):
    """The readers find the trace by its window under ``.bench_traces/``
    and read it: PAA's device time within the step's."""
    pl, red, runs = chip
    run_dir = tmp_path / "bfln-xdev.sync-7" / "plugins" / "profile" / "x"
    run_dir.mkdir(parents=True)
    shutil.copy(CHIP, run_dir / "host.xplane.pb")
    monkeypatch.setattr(scopes, "TRACES_DIR", str(tmp_path))
    layer = _layer(red, runs)
    paa = load_metric("paa_device_ms.sync").read(layer)
    step = readers.module_ms_per_unit(layer, "jit__sync_step")
    assert paa is not None and 0 < paa <= step
    schedule = load_metric("schedule_ms.sync").read(layer)
    assert schedule is not None and schedule > 0
