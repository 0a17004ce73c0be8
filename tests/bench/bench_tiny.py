"""Tiny copies of the benchmark's configurations and mixes for CPU tests:
the same policies, functions and traffic shape, with the cluster cut to a
few dozen workers and durations shortened so activations complete inside a
one-second window."""
from __future__ import annotations

import copy
import math

from bench import deploy, harness, traffic

# The flat deployment's cell as the CPU tests run it where BENCHMARK.json
# has no entry of that name; where it has one, ``cell`` returns that.
AZURE_CELL = {"name": "azure16k-steady", "config": "azure-region-16k",
              "traffic": "azure-poisson-steady", "chips": 1}

_REAL_CONFIG = deploy.load_config
_REAL_MIX = traffic.load_mix


def tiny_config(name: str) -> dict:
    cfg = copy.deepcopy(_REAL_CONFIG(name))
    if "list" in cfg["functions"]:  # the zoned paper deployment
        for z in cfg["zones"]:
            z["replicate"] = 8
        for f in cfg["functions"]["list"]:
            f["duration_s"] *= 0.05
        return cfg
    T = 8
    cfg["zones"][0]["groups"][0]["count"] = 64
    cfg["functions"]["count"] = 48
    cfg["functions"]["tags"] = T
    cfg["functions"]["duration_s"]["mu"] = math.log(0.05)
    cfg["policies"] = cfg["policies"][:T]
    for p in cfg["policies"]:
        for b in p["blocks"]:
            if "anti_affinity" in b:
                b["anti_affinity"] = [f"t{(int(p['tag'][1:]) + 1) % T:03d}"]
    return cfg


def tiny_mix(name: str) -> dict:
    mix = dict(_REAL_MIX(name))
    mix["rate_per_s"] = 30.0 if "origins" in mix else 150.0
    mix["max_wave"] = 4
    return mix


def cell(spec: dict, name: str) -> dict:
    """The cell ``name`` of ``spec``, or, where ``spec`` has no such cell,
    the flat deployment's."""
    if name == AZURE_CELL["name"] and all(
            c["name"] != name for c in spec["workloads"]):
        return AZURE_CELL
    return harness.find_cell(spec, name)


def install(monkeypatch) -> None:
    monkeypatch.setattr(deploy, "load_config", tiny_config)
    monkeypatch.setattr(traffic, "load_mix", tiny_mix)


def tiny_variant_config(name: str) -> dict:
    """The tiny flat configuration with the rest of the policy vocabulary
    the reference knows: ``followup: fail`` and a concurrency limit."""
    cfg = tiny_config(name)
    cfg["policies"][3]["followup"] = "fail"
    cfg["policies"][4]["blocks"][0]["max_concurrent_invocations"] = 6
    cfg["policies"][6]["blocks"][0]["capacity_used"] = 50
    return cfg
