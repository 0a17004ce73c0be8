"""The benchmark is data: every configuration, traffic mix and per-layer
metric that BENCHMARK.json names is found by that name in a file of its
own, and the file keeps to the declared schema.

The checks are invariants over what BENCHMARK.json names and what is
found under ``bench/``, one case per entry or file, so a cell, a
configuration, a mix, a roofline module or a per-layer metric is added by
new files and entries alone: a rehearsal stages such a cell in a copy of
the tree and runs these checks there.  Each check is also shown to catch
the fault it is for, on a mutated copy of the spec or the tree."""
from __future__ import annotations

import copy
import functools
import importlib
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import deploy, harness, metrics, roofline, traffic

import bench_tiny

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SEED = 2**33 + 7  # seeds may exceed 32 bits


def _found(sub: str, suffix: str):
    return sorted(p.stem for p in (BENCH / sub).glob(f"*{suffix}")
                  if p.stem != "__init__")


CONFIG_FILES = _found("configs", ".json")
MIX_FILES = _found("traffic", ".json")
KERNELS = _found("roofline", ".py")


# ---- the checks, each applied to the tree and to a mutated copy ----------- #

def check_cell(spec: dict, cell: dict) -> None:
    configs = {c["name"]: c for c in spec["configs"]}
    assert cell["config"] in configs, (
        f"cell {cell['name']}: no configs entry {cell['config']!r}")
    assert (ROOT / configs[cell["config"]]["file"]).is_file()
    assert deploy.load_config(cell["config"])["name"] == cell["config"]
    mix = traffic.BENCH / "traffic" / f"{cell['traffic']}.json"
    assert mix.is_file(), f"cell {cell['name']}: no mix {cell['traffic']!r}"
    assert traffic.load_mix(cell["traffic"])["max_wave"] >= 1
    assert cell["chips"] in (1, 4)
    e2e = [m["name"] for m in harness.cell_metrics(spec, cell, "end_to_end")]
    per = harness.cell_metrics(spec, cell, "per_layer")
    assert "setup_s" in e2e and len(e2e) >= 2 and per
    for m in per:
        assert callable(metrics.reader(m["name"]))
        assert m["moves"] in e2e


def check_readers(spec: dict) -> None:
    declared = {m["name"] for m in spec["per_layer"]}
    found = set(metrics.names())
    assert found == declared, (
        f"reader files without a declaration: {sorted(found - declared)}; "
        f"declared metrics without a reader file: {sorted(declared - found)}")


def check_roofline_metric(metric: dict) -> None:
    kernel = metric["name"][:-len("_roofline")]
    assert (Path(roofline.__file__).parent / f"{kernel}.py").is_file(), (
        f"{metric['name']}: no roofline module {kernel!r}")
    assert metric["unit"] == "%"


def check_configuration(spec: dict, name: str) -> None:
    cfg = deploy.load_config(name)
    assert cfg["name"] == name
    for key in ("source", "assumed", "guarantees"):
        assert cfg.get(key), f"configuration {name}: states no {key!r}"
    entry = {c["name"]: c for c in spec["configs"]}.get(name)
    if entry is not None:
        assert cfg["reduced"] == entry["reduced"]


def check_chips(spec: dict) -> None:
    chips = [c["chips"] for c in spec["workloads"]]
    assert set(chips) <= {1, 4}
    four, most = chips.count(4), max(1, len(chips) // 2)
    assert four <= most, f"{four} four-chip cells of {len(chips)}, most {most}"


# ---- what BENCHMARK.json names -------------------------------------------- #

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


@pytest.mark.parametrize("entry", SPEC["configs"], ids=lambda c: c["name"])
def test_config_entry_found_by_name(entry):
    path = ROOT / entry["file"]
    assert path.stem == entry["name"]
    assert path.resolve() == (deploy.BENCH / "configs" /
                              f"{entry['name']}.json").resolve()


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda c: c["name"])
def test_cell_files_found_by_name(cell):
    check_cell(SPEC, cell)


def test_lists_what_is_found_by_name():
    """Every configuration entry serves a cell, each pair of configuration
    and mix makes one cell, and at most half the cells (or one) take four
    chips."""
    used = {c["config"] for c in SPEC["workloads"]}
    assert {c["name"] for c in SPEC["configs"]} == used
    pairs = [(c["config"], c["traffic"]) for c in SPEC["workloads"]]
    assert len(pairs) == len(set(pairs))
    check_chips(SPEC)


def test_every_metric_reader_is_declared_and_found():
    check_readers(SPEC)
    with pytest.raises(KeyError):
        metrics.reader("no_such_metric")


@pytest.mark.parametrize(
    "metric", [m for m in SPEC["per_layer"]
               if m["name"].endswith("_roofline")],
    ids=lambda m: m["name"])
def test_roofline_metric_has_its_module(metric):
    check_roofline_metric(metric)


# ---- every file found under bench/ ---------------------------------------- #

@pytest.mark.parametrize("name", CONFIG_FILES)
def test_configuration_states_source_and_cuts(name):
    check_configuration(SPEC, name)


@functools.cache
def _deployment(name: str) -> deploy.Deployment:
    # the configuration as it is run: a tiny copy exists only for the
    # shapes bench_tiny knows, and a new configuration may have another
    return deploy.build(deploy.load_config(name), SEED)


def _draws(dep: deploy.Deployment, mix: dict) -> bool:
    rs = traffic.roots(dep, mix, SEED, 0.5)
    return bool(rs) and all(r.function in dep.functions and 0 < r.due < 0.5
                            for r in rs)


@pytest.mark.parametrize("name", MIX_FILES)
def test_mix_loads_and_draws(name):
    """Every mix draws arrivals of known functions on the configuration of
    each cell that runs it or, where no cell does, on one found."""
    mix = traffic.load_mix(name)
    assert mix["max_wave"] >= 1 and mix["rate_per_s"] > 0
    on = [c["config"] for c in SPEC["workloads"] if c["traffic"] == name]
    if on:
        assert all(_draws(_deployment(c), mix) for c in on)
    else:
        assert any(_draws(_deployment(c), mix) for c in CONFIG_FILES)


@pytest.mark.parametrize("kernel", KERNELS)
def test_roofline_module_bounds_a_small_shape(kernel):
    ops, nbytes = importlib.import_module(
        f"bench.roofline.{kernel}").ops_bytes(2, 128, 8)
    assert ops > 0 and nbytes > 0
    t, roof = roofline.bound_s(kernel, 2, 128, 8,
                               roofline.peaks("TPU v5 lite"))
    assert t > 0 and roof in ("compute", "memory")


# ---- each check catches the fault it is for ------------------------------- #

def _add(path: Path, text: str) -> None:
    assert not path.exists(), f"{path} would be edited, not added"
    path.write_text(text)


def _first_cell(spec: dict) -> dict:
    return spec["workloads"][0]


def _drop_source(spec, bench):
    path = bench / "configs" / f"{spec['configs'][0]['name']}.json"
    cfg = json.loads(path.read_text())
    del cfg["source"]
    path.write_text(json.dumps(cfg))


def _two_of_three_on_four_chips(spec, bench):
    cell = dict(_first_cell(spec), chips=1)
    spec["workloads"] = [cell] + [dict(cell, name=f"four-{n}", chips=4)
                                  for n in "ab"]


def _check_configurations(spec):
    for p in sorted((deploy.BENCH / "configs").glob("*.json")):
        check_configuration(spec, p.stem)


def _check_roofline_metrics(spec):
    for m in spec["per_layer"]:
        if m["name"].endswith("_roofline"):
            check_roofline_metric(m)


# fault: (mutation of copies of the spec and bench/, check, what it says)
FAULTS = {
    "cell-names-a-missing-configuration": (
        lambda spec, bench: _first_cell(spec).update(config="no-such-config"),
        lambda spec: check_cell(spec, _first_cell(spec)),
        "no configs entry 'no-such-config'"),
    "cell-names-a-missing-mix": (
        lambda spec, bench: _first_cell(spec).update(traffic="no-such-mix"),
        lambda spec: check_cell(spec, _first_cell(spec)),
        "no mix 'no-such-mix'"),
    "declared-metric-without-a-reader": (
        lambda spec, bench: spec["per_layer"].append(
            dict(spec["per_layer"][0], name="no_reader_ms")),
        check_readers,
        r"without a reader file: \['no_reader_ms'\]"),
    "reader-without-a-declaration": (
        lambda spec, bench: _add(
            bench / "metrics" / "undeclared_ms.py",
            "from bench.readers import wave_ms as read  # noqa: F401\n"),
        check_readers,
        r"without a declaration: \['undeclared_ms'\]"),
    "roofline-metric-without-a-module": (
        lambda spec, bench: spec["per_layer"].append(
            dict(spec["per_layer"][0], name="no_such_kernel_roofline",
                 unit="%")),
        _check_roofline_metrics,
        "no roofline module 'no_such_kernel'"),
    "configuration-without-source": (
        _drop_source, _check_configurations, "states no 'source'"),
    "two-four-chip-cells-of-three": (
        _two_of_three_on_four_chips, check_chips,
        "2 four-chip cells of 3, most 1"),
}


@pytest.fixture
def tree_copy(tmp_path, monkeypatch) -> Path:
    """A copy of ``bench/`` that the loaders read in place of the tree."""
    bench = tmp_path / "bench"
    shutil.copytree(BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__", "fixtures"))
    monkeypatch.setattr(deploy, "BENCH", bench)
    monkeypatch.setattr(traffic, "BENCH", bench)
    monkeypatch.setattr(metrics, "HERE", bench / "metrics")
    return bench


@pytest.mark.parametrize("fault", FAULTS)
def test_layout_check_catches(fault, tree_copy):
    mutate, check, says = FAULTS[fault]
    spec = copy.deepcopy(SPEC)
    check(spec)  # the copy as it is passes
    mutate(spec, tree_copy)
    with pytest.raises(AssertionError, match=says):
        check(spec)


# ---- a cell added by new files and entries alone -------------------------- #

AZURE = "azure-region-16k"
AZURE_ENTRY = {
    "name": AZURE,
    "source": "https://www.usenix.org/conference/atc20/presentation/shahrad",
    "file": f"bench/configs/{AZURE}.json", "reduced": [],
    "why": "a 16k-invoker region with the source's popularity, memory and "
           "duration fits"}
# the flat deployment's cell as it would land, on the files already there
LANDED_CELL = {"name": "azure16k-steady", "config": AZURE,
               "traffic": "azure-poisson-steady", "chips": 1,
               "why": "the flat 16k-invoker region at a steady Poisson rate, "
                      "waves of at most 16: the fused bulk pass does the "
                      "work"}


def _add_cell(spec: dict, cell: dict, readers: dict) -> dict:
    """Adds ``cell`` to ``spec`` as entries only: the cell, its name in the
    decision latency's list, and one per-layer metric for each of
    ``readers`` (metric name -> function of ``bench.readers``).  Gives the
    reader files the metrics need, by path under ``bench/``."""
    spec["workloads"].append(dict(cell))
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    e2e["decision_p50_ms"]["workloads"].append(cell["name"])
    files = {}
    for metric, reader in readers.items():
        roof = metric.endswith("_roofline")
        spec["per_layer"].append({
            "name": metric, "unit": "%" if roof else "ms",
            "better": "higher" if roof else "lower",
            "source": "device_trace" if roof else "program_span",
            "layer": "kernels" if roof else "facade and session wave",
            "moves": "decision_p50_ms", "workloads": [cell["name"]]})
        files[f"metrics/{metric}.py"] = (
            f'"""Staged reader."""\nfrom bench.readers import {reader} '
            f'as read  # noqa: F401\n')
    return files


def _land_azure(spec: dict) -> dict:
    """The flat deployment's cell added to ``spec`` as the next PR would
    add it, where ``spec`` has not got it yet: a configs entry for the file
    that is there, the cell on the mix that is there, and readers of the
    bulk kernel and the wave.  Gives the files that this needs."""
    if any(c["name"] == LANDED_CELL["name"] for c in spec["workloads"]):
        return {}
    spec["configs"].append(dict(AZURE_ENTRY))
    return _add_cell(spec, LANDED_CELL, {
        "bulk_decide_kernel_roofline": "bulk_roofline_pct",
        "wave_ms": "wave_ms"})


def _staged_names(spec: dict, bench: Path) -> dict:
    """Names of a cell, configuration, mix, roofline module and per-layer
    metrics that neither ``spec`` nor a file under ``bench`` has, so that
    no cell a later PR adds can clash with the rehearsal."""
    taken = {e["name"] for group in ("configs", "workloads", "end_to_end",
                                     "per_layer") for e in spec[group]}
    taken |= {p.stem for p in bench.rglob("*") if p.is_file()}
    for n in range(1, 1000):
        tag = "" if n == 1 else f"_{n}"
        names = {"cell": f"staged-steady{tag}",
                 "config": f"staged-region{tag}",
                 "mix": f"staged-poisson{tag}",
                 "kernel": f"staged_kernel{tag}",
                 "wave": f"staged_wave_ms{tag}"}
        names["roofline"] = f"{names['kernel']}_roofline"
        if taken.isdisjoint(names.values()):
            return names
    raise AssertionError("no free staged names")


def _stage(spec: dict, bench: Path) -> dict:
    """A new cell added to ``spec`` and ``bench`` by new entries and new
    files alone: a configuration (the flat deployment's under a new name),
    a mix, a roofline module, and readers for its roofline and its wave.
    Gives the names it took."""
    names = _staged_names(spec, bench)
    cfg = json.loads((bench / "configs" / f"{AZURE}.json").read_text())
    spec["configs"].append(dict(
        AZURE_ENTRY, name=names["config"],
        file=f"bench/configs/{names['config']}.json",
        reduced=cfg["reduced"]))
    files = _add_cell(
        spec, dict(LANDED_CELL, name=names["cell"], config=names["config"],
                   traffic=names["mix"]),
        {names["roofline"]: "bulk_roofline_pct", names["wave"]: "wave_ms"})
    files[f"configs/{names['config']}.json"] = json.dumps(
        dict(cfg, name=names["config"]))
    files[f"traffic/{names['mix']}.json"] = (
        bench / "traffic" / "azure-poisson-steady.json").read_text()
    files[f"roofline/{names['kernel']}.py"] = (
        "def ops_bytes(R, W, T):\n"
        "    return 4 * R * W * T, W * T + R * T + 2 * R * W\n")
    for rel, text in files.items():
        _add(bench / rel, text)
    return names


def _only_added(old: dict, new: dict, cell: str) -> None:
    """``new`` is ``old`` with entries appended, and ``cell`` appended to
    metrics' lists of cells: no entry of ``old`` is edited."""
    assert set(new) == set(old)
    for group, entries in old.items():
        if not isinstance(entries, list) or group in ("command", "paths"):
            assert new[group] == entries, group
            continue
        kept = [{k: [w for w in v if w != cell] if k == "workloads" else v
                 for k, v in e.items()} for e in new[group][:len(entries)]]
        assert kept == entries, group


@pytest.mark.parametrize("landed", [False, True],
                         ids=["on-the-tree", "on-the-azure-cell-landed"])
def test_a_cell_is_added_by_new_files_alone(tmp_path, landed):
    """The layout checks pass on a copy of the tree with a cell, its
    configuration, mix, roofline module and readers added, and no file or
    entry the benchmark has edited: on the tree as it is, and on the tree
    once the flat deployment's cell has landed."""
    tree = tmp_path / "tree"
    skip = shutil.ignore_patterns("__pycache__")
    shutil.copytree(BENCH, tree / "bench", ignore=skip)
    shutil.copytree(ROOT / "tests" / "bench", tree / "tests" / "bench",
                    ignore=skip)
    spec = copy.deepcopy(SPEC)
    if landed:
        for rel, text in _land_azure(spec).items():
            _add(tree / "bench" / rel, text)
    before = {p: p.read_bytes() for p in tree.rglob("*") if p.is_file()}
    base = copy.deepcopy(spec)
    names = _stage(spec, tree / "bench")
    _only_added(base, spec, names["cell"])
    assert all(p.read_bytes() == b for p, b in before.items())
    (tree / "BENCHMARK.json").write_text(json.dumps(spec, indent=1))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONDONTWRITEBYTECODE="1",
               PYTHONPATH=str(ROOT / "src"))
    p = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-rA", "-p", "no:cacheprovider",
         "--basetemp", str(tmp_path / "inner"), "-k", "not new_files_alone",
         "tests/bench/test_bench_layout.py"],
        cwd=tree, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stdout[-6000:] + p.stderr[-2000:]
    cases = [f"test_cell_files_found_by_name[{names['cell']}]",
             f"test_config_entry_found_by_name[{names['config']}]",
             f"test_configuration_states_source_and_cuts[{names['config']}]",
             f"test_mix_loads_and_draws[{names['mix']}]",
             f"test_roofline_module_bounds_a_small_shape[{names['kernel']}]",
             f"test_roofline_metric_has_its_module[{names['roofline']}]"]
    if landed:
        cases += [f"test_cell_files_found_by_name[{LANDED_CELL['name']}]",
                  f"test_config_entry_found_by_name[{AZURE}]",
                  "test_roofline_metric_has_its_module"
                  "[bulk_decide_kernel_roofline]"]
    for case in cases:
        assert f"PASSED tests/bench/test_bench_layout.py::{case}" in p.stdout


def test_tiny_cell_follows_the_spec():
    """The CPU rehearsals run the spec's entry for the flat deployment's
    cell once BENCHMARK.json has one, and a stand-in while it has not."""
    name = bench_tiny.AZURE_CELL["name"]
    landed = copy.deepcopy(SPEC)
    _land_azure(landed)
    assert bench_tiny.cell(landed, name) == harness.find_cell(landed, name)
    if any(c["name"] == name for c in SPEC["workloads"]):
        assert bench_tiny.cell(SPEC, name) == harness.find_cell(SPEC, name)
    else:
        assert bench_tiny.cell(SPEC, name) == bench_tiny.AZURE_CELL
