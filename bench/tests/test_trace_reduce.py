"""The trace reduction on planes built by hand and on a recorded trace."""
import os
from types import SimpleNamespace as NS

import pytest

from bench import readers
from bench import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def ev(name, start, dur):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur))


def planes():
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev("bench.window", 1000, 9000)])])
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[ev("jit__sync_step(12)", 1500, 3000),
                                       ev("jit__sync_step(12)", 6000, 2000),
                                       ev("jit_other(3)", 500, 1000)]),
        NS(name="XLA Ops", events=[
            ev("fusion.1", 500, 1000),     # half before the window
            ev("fusion.1", 1500, 1000),
            ev("_fingerprint_kernel", 2000, 2500),   # overlaps fusion.1
            ev("fusion.2", 6000, 2000),
            ev("fusion.2", 9500, 1000)])])  # half after the window
    return [host, dev]


def test_busy_is_the_union_of_ops_inside_the_window():
    red = tr.reduce_planes(planes())
    assert red.window_s == pytest.approx(9e-6)
    # [1000,1500] + [1500,4500] + [6000,8000] + [9500,10000]
    assert red.busy_s == pytest.approx((500 + 3000 + 2000 + 500) / 1e9)
    assert red.n_devices == 1


def test_module_and_op_times():
    red = tr.reduce_planes(planes())
    assert red.module_time("jit__sync_step") == (pytest.approx(5e-6), 2)
    assert red.module_time("jit_other")[0] == pytest.approx(0.5e-6)
    assert red.ops_matching("fingerprint") == (pytest.approx(2.5e-6), 1)


def test_idle_gaps_go_to_the_innermost_host_span():
    red = tr.reduce_planes(planes())
    assert red.gaps == [(4500.0, 6000.0), (8000.0, 9500.0)]
    spans = [("round.total", 0, 20000), ("round.chain", 4000, 7000)]
    got = dict(tr.attribute_gaps(red.gaps, spans))
    assert got == {"round.chain": pytest.approx(1.5e-6),
                   "round.total": pytest.approx(1.5e-6)}


def test_missing_window_is_an_error():
    with pytest.raises(ValueError):
        tr.reduce_planes(planes()[1:])


def test_recorded_chip_trace():
    """A 0.1 s window of ``fedavg-xdev.sync`` recorded on one v5e chip
    (4 rounds)."""
    red = tr.reduce_file(os.path.join(DATA, "fedavg-xdev.sync.xplane.pb"))
    assert red.n_devices == 1
    assert red.window_s == pytest.approx(0.1005, abs=1e-3)
    assert 0 < red.busy_s < red.window_s
    seconds, runs = red.module_time("jit__sync_step")
    assert runs == 4 and 0 < seconds < red.busy_s
    # one fingerprint kernel call per round
    assert red.ops_matching(readers.FINGERPRINT_OP)[1] == runs
    assert tr.short_op(max(red.op_s, key=red.op_s.get)).startswith("%")
