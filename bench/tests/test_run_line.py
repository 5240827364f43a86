"""The result line's schema, and the refusal to run without a chip."""
import json
import os
import subprocess
import sys
from types import SimpleNamespace as NS

import pytest

from bench import harness, run


class FakeDevice:
    platform = "tpu"
    device_kind = "TPU v5 lite"


def fake_driver(trace_red=None):
    def drive(ctx):
        layer = {}
        if ctx.trace:
            layer = {"trace": trace_red, "spans": [], "window_s": 2.0,
                     "units": 4, "idle_by_host": [("round.chain", 0.5)],
                     "arrived": 40, "cohort": 12, "n_params": 28,
                     "samples_per_client": 16,
                     "model": {"in_dim": 4, "hidden": [3], "rep_dim": 2,
                               "num_classes": 5},
                     "device_kind": "TPU v5 lite"}
        return harness.RunResult(
            e2e={"round_ms": 5.0, "setup_s": 9.0},
            checks=[harness.Check("loss_gap", 1e-4, 1e-2),
                    harness.Check("link_breaks", 0.0, 0.0)],
            attempted=4, failed=0, layer=layer, memory_peak_bytes=123)
    return NS(run=drive)


@pytest.mark.parametrize("trace", [0, 1])
def test_last_line_schema(monkeypatch, trace):
    red = NS(busy_s=0.5, window_s=2.0, op_s={"fusion.1": 0.4},
             module_time=lambda name: (0.2, 4),
             ops_matching=lambda p: (0.0, 0), n_devices=1)
    monkeypatch.setattr(run, "load_driver", lambda kind: fake_driver(red))
    monkeypatch.setattr(run, "use_compile_cache", lambda: None)
    args = run.parse_args(["--workload", "bfln-xdev.sync", "--seed",
                           str(2 ** 31 + 5), "--seconds", "2", "--trace",
                           str(trace)])
    line = run.run(args, devices=[FakeDevice()])
    line = json.loads(json.dumps(line))
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "checks"
    assert line["correct"] is True
    assert line["device"] == {"platform": "tpu", "kind": "TPU v5 lite",
                              "count": 1, "memory_peak_bytes": 123,
                              **({"busy_s": 0.5, "window_s": 2.0}
                                 if trace else {})}
    assert line["checks"]["loss_gap"] == {"value": 1e-4, "limit": 1e-2}
    if trace:
        assert line["metrics"]["step_device_ms.sync"] == {"value": 50.0,
                                                          "unit": "ms"}
        assert line["metrics"]["idle_share.sync"]["value"] == \
            pytest.approx(75.0)
        # 10 clients x 16 samples x 3 x 56 FLOPs per 0.5 s round
        assert line["metrics"]["mfu.sync"]["value"] == \
            pytest.approx(100 * 10 * 16 * 168 / (0.5 * 197e12))
        assert "fingerprint_us.sync" not in line["metrics"]   # no kernel op
        assert line["breakdown"] == {"device_ops": [["fusion.1", 0.4]],
                                     "idle_gaps": [["round.chain", 0.5]]}
    else:
        assert line["metrics"] == {"round_ms": {"value": 5.0, "unit": "ms"},
                                   "setup_s": {"value": 9.0, "unit": "s"}}


def test_a_failed_check_makes_the_run_incorrect(monkeypatch):
    def drive(ctx):
        return harness.RunResult({"round_ms": 1.0, "setup_s": 1.0},
                                 [harness.Check("change_gap", 0.5, 0.1)],
                                 1, 0)
    monkeypatch.setattr(run, "load_driver", lambda kind: NS(run=drive))
    monkeypatch.setattr(run, "use_compile_cache", lambda: None)
    args = run.parse_args(["--workload", "bfln-xdev.sync", "--seed", "1",
                           "--seconds", "1"])
    assert run.run(args, devices=[FakeDevice()])["correct"] is False


def test_too_few_chips_is_refused(monkeypatch):
    import jax
    monkeypatch.setattr(jax, "devices", lambda *a: [FakeDevice()])
    with pytest.raises(harness.BenchError):
        run.require_accelerator(4)
    assert run.require_accelerator(1)[0].platform == "tpu"


def test_no_accelerator_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(harness.BENCH_DIR, "run.py"),
         "--workload", "bfln-xdev.sync", "--seed", "3", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=120, cwd=harness.ROOT)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no accelerator" in out.stderr
