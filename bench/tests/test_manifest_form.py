"""BENCHMARK.json keeps to the form its readers accept: the keys of each
entry, the names, units and lines of text, the bounds and the run length."""
import json
import os
import re

import pytest

from bench import harness

PATH = os.path.join(harness.ROOT, "BENCHMARK.json")
MANIFEST = json.load(open(PATH))

NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.-]{1,16}")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}

# (section, keys every entry has, keys an entry may add)
SECTIONS = [
    ("configs", {"name", "source", "file", "reduced", "why"}, set()),
    ("workloads", {"name", "config", "traffic", "chips", "why"}, set()),
    ("end_to_end", {"name", "unit", "better", "bound", "source"},
     {"workloads"}),
    ("per_layer", {"name", "unit", "better", "source", "layer", "moves"},
     {"workloads"}),
]


def _line(text):
    return (isinstance(text, str) and 1 <= len(text) <= 200
            and "\n" not in text and "\t" not in text)


def test_top_level_keys_and_size():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(PATH) <= 64 * 1024
    assert 1 <= len(MANIFEST["command"]) <= 32
    assert all(_line(w) for w in MANIFEST["command"])
    assert 1 <= len(MANIFEST["paths"]) <= 16
    for p in MANIFEST["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p)
        assert not p.startswith("/") and ".." not in p.split("/")


@pytest.mark.parametrize("section,required,optional", SECTIONS,
                         ids=[s[0] for s in SECTIONS])
def test_section_entries(section, required, optional):
    entries = MANIFEST[section]
    assert entries
    names = [e["name"] for e in entries]
    assert len(names) == len(set(names))
    for e in entries:
        assert required <= set(e) <= required | optional, e["name"]
        assert NAME.fullmatch(e["name"]), e["name"]
        if "unit" in e:
            assert UNIT.fullmatch(e["unit"]), e["name"]
            assert e["better"] in ("lower", "higher")
            assert e["source"] in SOURCES
        for key in ("why", "layer", "source"):
            if key in e:
                assert _line(e[key]), (e["name"], key)


def test_configs_and_cells():
    paths = MANIFEST["paths"]
    configs = {c["name"] for c in MANIFEST["configs"]}
    used = set()
    pairs = set()
    for c in MANIFEST["configs"]:
        assert any(c["file"].startswith(p + "/") for p in paths)
        assert len(c["reduced"]) <= 16
        assert all(NAME.fullmatch(k) for k in c["reduced"])
    files = [c["file"] for c in MANIFEST["configs"]]
    assert len(files) == len(set(files))
    for w in MANIFEST["workloads"]:
        assert w["config"] in configs and NAME.fullmatch(w["traffic"])
        assert w["chips"] in (1, 4)
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        used.add(w["config"])
    assert used == configs
    four = sum(w["chips"] == 4 for w in MANIFEST["workloads"])
    assert four <= max(1, len(MANIFEST["workloads"]) // 2)


def test_metrics_reach_every_cell():
    cells = {w["name"] for w in MANIFEST["workloads"]}
    e2e = {m["name"]: m for m in MANIFEST["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for m in e2e.values():
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        assert set(m.get("workloads", cells)) <= cells
    for m in MANIFEST["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", cells)) <= cells
    for cell in cells:
        reported = {n for n, m in e2e.items()
                    if cell in m.get("workloads", cells)}
        assert "setup_s" in reported and len(reported) >= 2, cell
        assert any(cell in m.get("workloads", cells)
                   and cell in e2e[m["moves"]].get("workloads", cells)
                   for m in MANIFEST["per_layer"]), cell


def test_run_length_fits_a_full_check_of_24_cells():
    secs = MANIFEST["run_seconds"]
    assert isinstance(secs, int) and 1 <= secs <= 51
    runs = 2 + 14 * 24
    assert runs * (secs + 60) + 24 * 2 * 90 + 1200 <= 43200
