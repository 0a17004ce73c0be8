"""The benchmark is data: every configuration, traffic mix and per-layer
metric that BENCHMARK.json names is found by that name in a file of its
own, and the file keeps to the declared schema."""
from __future__ import annotations

import json
import re
from pathlib import Path

import pytest

from bench import deploy, harness, metrics, roofline, traffic

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_top_level_keys_and_paths():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"]
    for p in SPEC["paths"]:
        assert (ROOT / p).is_dir() and not p.startswith("/") and ".." not in p
    assert 1 <= SPEC["run_seconds"] <= 51
    assert len(json.dumps(SPEC)) < 64 * 1024


def test_names_units_and_keys():
    seen = set()
    for group, keys in (("configs", {"name", "source", "file", "reduced",
                                     "why"}),
                        ("workloads", {"name", "config", "traffic", "chips",
                                       "why"}),
                        ("end_to_end", {"name", "unit", "better", "bound",
                                        "source", "workloads"}),
                        ("per_layer", {"name", "unit", "better", "source",
                                       "layer", "moves", "workloads"})):
        for e in SPEC[group]:
            assert set(e) <= keys, (group, e)
            assert NAME.match(e["name"]) and e["name"] not in seen
            seen.add(e["name"])
            if "unit" in e:
                assert UNIT.match(e["unit"]) and e["better"] in (
                    "lower", "higher")
            for k in ("why", "layer", "source"):
                if k in e:
                    assert 1 <= len(e[k]) <= 200 and "\n" not in e[k]
    for e in SPEC["end_to_end"]:
        assert 0.01 <= e["bound"] <= 0.25
        assert e["source"] in ("host_clock", "device_trace")
    assert "setup_s" in {e["name"] for e in SPEC["end_to_end"]}


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda c: c["name"])
def test_cell_files_found_by_name(cell):
    cfg = {c["name"]: c for c in SPEC["configs"]}[cell["config"]]
    assert (ROOT / cfg["file"]).is_file()
    assert deploy.load_config(cell["config"])["name"] == cell["config"]
    mix = traffic.load_mix(cell["traffic"])
    assert mix["process"] == "poisson" and mix["max_wave"] >= 1
    assert cell["chips"] == 1
    e2e = [m["name"] for m in harness.cell_metrics(SPEC, cell, "end_to_end")]
    per = harness.cell_metrics(SPEC, cell, "per_layer")
    assert "setup_s" in e2e and len(e2e) >= 2 and per
    for m in per:
        assert callable(metrics.reader(m["name"]))
        assert m["moves"] in e2e


def test_every_metric_reader_is_declared_and_found():
    declared = {m["name"] for m in SPEC["per_layer"]}
    assert set(metrics.names()) == declared
    with pytest.raises(KeyError):
        metrics.reader("no_such_metric")


def test_lists_what_is_found_by_name():
    configs = sorted(p.stem for p in (ROOT / "bench" / "configs").glob(
        "*.json"))
    mixes = sorted(p.stem for p in (ROOT / "bench" / "traffic").glob(
        "*.json"))
    kernels = sorted(p.stem for p in (ROOT / "bench" / "roofline").glob(
        "*.py") if p.stem != "__init__")
    assert configs == ["azure-region-16k", "paper-divimp-2zone"]
    assert mixes == ["azure-poisson-steady", "divimp-poisson-steady"]
    assert kernels == ["affinity_valid_kernel", "bulk_decide_kernel"]
    # the flat deployment waits, as data, for a later cell (PERF.md)
    assert {c["config"] for c in SPEC["workloads"]} == set(configs) - {
        "azure-region-16k"}
    for k in kernels:
        assert roofline.bound_s(k, 1, 128, 8, roofline.peaks("TPU v5 lite"))


@pytest.mark.parametrize("name", ["azure-region-16k", "paper-divimp-2zone"])
def test_configuration_states_source_and_cuts(name):
    cfg = deploy.load_config(name)
    entry = {c["name"]: c for c in SPEC["configs"]}.get(name, {"reduced": []})
    assert cfg["reduced"] == entry["reduced"]
    assert cfg["source"] and cfg["assumed"] and cfg["guarantees"]
