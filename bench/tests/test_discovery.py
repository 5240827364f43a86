"""A configuration, a traffic mix and a per-layer metric added as new files,
with no edit to an existing harness file, are found by their names."""
import json
import os

import pytest

from bench import harness, run

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture
def grown_root(tmp_path):
    """A copy of the manifest plus one new configuration, traffic mix and
    metric, each written as a file of its own."""
    root = tmp_path
    (root / "bench" / "configs").mkdir(parents=True)
    (root / "bench" / "traffic").mkdir()
    (root / "bench" / "metrics").mkdir()
    manifest = json.load(open(os.path.join(harness.ROOT, "BENCHMARK.json")))
    cfg = json.load(open(os.path.join(harness.ROOT,
                                      "bench/configs/bfln-xdev.json")))
    cfg["name"] = "bfln-small"
    cfg["data"]["n_clients"] = 400
    (root / "bench/configs/bfln-small.json").write_text(json.dumps(cfg))
    traffic = json.load(open(os.path.join(harness.ROOT,
                                          "bench/traffic/sync.json")))
    traffic["sample_frac"] = 0.05
    (root / "bench/traffic/sync-small.json").write_text(json.dumps(traffic))
    (root / "bench/metrics/rounds_seen.py").write_text(
        "def read(layer):\n    return float(layer['units'])\n")
    manifest["configs"].append({"name": "bfln-small", "source": "x",
                                "file": "bench/configs/bfln-small.json",
                                "reduced": [], "why": "test"})
    manifest["workloads"].append({"name": "bfln-small.sync",
                                  "config": "bfln-small",
                                  "traffic": "sync-small", "chips": 1,
                                  "why": "test"})
    manifest["per_layer"].append({"name": "rounds_seen", "unit": "count",
                                  "better": "higher",
                                  "source": "program_span", "layer": "t",
                                  "moves": "round_ms",
                                  "workloads": ["bfln-small.sync"]})
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    return root


def test_new_cell_is_found_by_name(grown_root):
    manifest, cell, config, traffic = run.load_cell("bfln-small.sync",
                                                    root=str(grown_root))
    assert cell["traffic"] == "sync-small"
    assert config["data"]["n_clients"] == 400
    assert traffic["sample_frac"] == 0.05
    assert run.load_driver(traffic["driver"]).run is not None


def test_new_metric_is_found_by_name(grown_root):
    manifest = json.load(open(grown_root / "BENCHMARK.json"))
    names = [m["name"] for m in run.cell_metrics(manifest, "bfln-small.sync",
                                                 "per_layer")]
    assert "rounds_seen" in names and "loop_ms.sync" not in names
    mod = run.load_metric("rounds_seen", str(grown_root / "bench"))
    assert mod.read({"units": 7}) == 7.0


def test_every_manifest_entry_has_its_files():
    manifest = json.load(open(os.path.join(harness.ROOT, "BENCHMARK.json")))
    for c in manifest["configs"]:
        assert os.path.isfile(os.path.join(harness.ROOT, c["file"]))
    for w in manifest["workloads"]:
        _, _, _, traffic = run.load_cell(w["name"])
        assert run.load_driver(traffic["driver"]).run
        assert os.path.isfile(os.path.join(harness.BENCH_DIR, "limits",
                                           w["name"] + ".json"))
    for m in manifest["per_layer"]:
        assert callable(run.load_metric(m["name"]).read)


def test_unknown_cell_is_refused():
    with pytest.raises(harness.BenchError):
        run.load_cell("no-such.cell")


