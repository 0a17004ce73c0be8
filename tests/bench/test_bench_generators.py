"""Deployments and arrivals come from the seed alone: one seed gives the same
inputs, another seed the same sizes and arrivals in another order."""
from __future__ import annotations

from collections import Counter

import numpy as np
import pytest

from bench import deploy, traffic

from bench_tiny import tiny_config

BIG = 2**33 + 12345  # seeds may exceed 32 bits


@pytest.mark.parametrize("name", ["azure-region-16k", "paper-divimp-2zone"])
def test_deployment_is_a_function_of_the_seed(name):
    cfg = tiny_config(name)
    a, b, c = (deploy.build(cfg, s) for s in (BIG, BIG, BIG + 1))
    for x in ("workers", "zone", "wclass", "preload", "policies"):
        assert getattr(a, x) == getattr(b, x)
    assert a.functions == b.functions and a.popularity == b.popularity
    # another seed: the same function sizes, shuffled
    assert sorted(f.memory for f in a.functions.values()) == sorted(
        f.memory for f in c.functions.values())
    assert sorted(f.duration for f in a.functions.values()) == sorted(
        f.duration for f in c.functions.values())
    assert a.workers == c.workers


def test_full_size_flat_deployment():
    dep = deploy.build(deploy.load_config("azure-region-16k"), BIG)
    assert len(dep.workers) == 16384 and len(dep.functions) == 4096
    mems = np.array([f.memory for f in dep.functions.values()])
    durs = np.array([f.duration for f in dep.functions.values()])
    # the source's fits: Burr XII memory, median about 140 MB, every
    # function fits an empty invoker; log-normal durations, median e^-0.38 s
    assert 50 <= mems.min() and np.median(mems) == 140
    assert mems.max() < dep.memory.min()
    assert abs(np.median(durs) - np.exp(-0.38)) < 1e-3
    used = np.zeros(len(dep.workers))
    for f, j in dep.preload:
        used[j] += dep.functions[f].memory
    assert (used <= dep.memory).all()
    assert 0.35 < used.sum() / dep.memory.sum() < 0.6
    assert {dep.rows(f) for f in dep.functions} == {2, 3}


def test_full_size_zoned_deployment_and_script():
    dep = deploy.build(deploy.load_config("paper-divimp-2zone"), BIG)
    assert len(dep.workers) == 6144 and dep.zones == ["eu", "us"]
    small_eu = dep.select({"zone": "eu", "class": "small"})
    assert len(small_eu) == 1024
    assert all(dep.memory[j] == 1024 for j in small_eu)
    text = deploy.script_text(dep)
    assert "topology: local_first" in text and "affinity: [d, !h_eu, !h_us]" \
        in text
    assert dep.workers[small_eu[0]] in text


@pytest.mark.parametrize("name,mix", [
    ("azure-region-16k", "azure-poisson-steady"),
    ("paper-divimp-2zone", "divimp-poisson-steady")])
def test_arrivals_are_a_function_of_the_seed(name, mix):
    dep = deploy.build(tiny_config(name), 3)
    m = traffic.load_mix(mix)
    a = traffic.roots(dep, m, BIG, 2.0)
    b = traffic.roots(dep, m, BIG, 2.0)
    c = traffic.roots(dep, m, BIG + 1, 2.0)
    key = lambda rs: [(r.due, r.function, r.origin) for r in rs]  # noqa: E731
    assert key(a) == key(b) and key(a) != key(c)
    assert len(a) == len(c) == round(m["rate_per_s"] * 2.0)
    assert all(0 < r.due < 2.0 for r in a)
    assert [r.due for r in a] == sorted(r.due for r in a)
    # the same set of functions and origins, in another order
    assert Counter((r.function, r.origin) for r in a) == Counter(
        (r.function, r.origin) for r in c)


def test_children_follow_their_parent():
    dep = deploy.build(tiny_config("paper-divimp-2zone"), 1)
    parent = traffic.Arrival(7, 0.5, "divide", "eu")
    kids = traffic.children(dep, parent, 0.75, "us", 100)
    assert [(k.id, k.function, k.origin, k.parent) for k in kids] == [
        (100, "impera", "us", 7), (101, "impera", "us", 7)]
    assert all(k.due == pytest.approx(0.8) for k in kids)
