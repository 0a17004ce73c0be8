"""The pluggable strategy registry + the new least_loaded / warmest rules.

Acceptance contract: every registered strategy is honoured *identically* by
the scalar Listing-1 reference and the vectorized ``SchedulerSession`` —
hypothesis-property-tested over random scripts / clusters / warmth tables
(plus a seeded hypothesis-free sweep for minimal environments), with
deterministic pin-downs of each rule's semantics.
"""
import random

import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAS_HYPOTHESIS = True
except ImportError:
    HAS_HYPOTHESIS = False

from repro.core import (
    AAppScript,
    Block,
    ClusterState,
    CompiledPolicies,
    Registry,
    SchedulerSession,
    get_strategy,
    parse,
    register_strategy,
    schedule_wave,
    strategy_names,
    try_schedule,
)
from repro.core.scheduler import rejection_reason, valid
from repro.core.strategies import Strategy
from tests.test_batched_equivalence import (
    TAGS,
    clone_state,
    random_cluster,
    random_script,
    random_warmth,
)


# --------------------------------------------------------------------------- #
# registry surface
# --------------------------------------------------------------------------- #


def test_registry_has_the_four_builtins():
    names = strategy_names()
    for n in ("best_first", "any", "least_loaded", "warmest"):
        assert n in names
    assert get_strategy("random") is get_strategy("any")  # paper alias
    assert get_strategy("platform") is get_strategy("best_first")  # APP alias
    assert get_strategy("least-loaded") is get_strategy("least_loaded")


def test_custom_strategy_registers_and_schedules_everywhere():
    """One class + one register_strategy call: the new rule is honoured by
    the parser, the scalar reference, and the session alike."""

    class LastResort(Strategy):
        name = "last_resort"
        narrow_warmth = False

        def select(self, candidates, ctx, rng):
            return candidates[-1]

    register_strategy(LastResort(), "last-resort")
    try:
        script = parse("t:\n  workers: *\n  strategy: last-resort\n")
        assert script["t"].blocks[0].strategy == "last_resort"

        state = ClusterState()
        reg = Registry()
        reg.register("fn", memory=1.0, tag="t")
        for w in ("w0", "w1", "w2"):
            state.add_worker(w, max_memory=10.0)
        assert try_schedule("fn", state.conf(), script, reg) == "w2"
        session = SchedulerSession(state, reg, script)
        assert session.try_schedule("fn") == "w2"
        session.close()
        res = schedule_wave(["fn"], state.conf(),
                            CompiledPolicies(script, reg), reg, backend="ref")
        assert res.assignments == ["w2"]
    finally:
        # the registry is process-global: drop the test strategy again
        from repro.core import strategies as S
        S._REGISTRY.pop("last_resort", None)
        S._ALIASES.pop("last_resort", None)
        S._ALIASES.pop("last-resort", None)


# --------------------------------------------------------------------------- #
# semantics pin-downs
# --------------------------------------------------------------------------- #


def _three_workers(loads=(0, 0, 0)):
    state = ClusterState()
    reg = Registry()
    reg.register("fn", memory=1.0, tag="t")
    reg.register("filler", memory=1.0, tag="x")
    for i, w in enumerate(("w0", "w1", "w2")):
        state.add_worker(w, max_memory=100.0)
        for _ in range(loads[i]):
            state.allocate("filler", w, reg)
    return state, reg


def _script(strategy):
    from repro.core import TagPolicy

    return AAppScript(policies=(
        TagPolicy(tag="t", blocks=(Block(workers=("*",), strategy=strategy),)),))


def test_least_loaded_picks_emptiest_first_on_tie():
    state, reg = _three_workers(loads=(2, 1, 1))
    script = _script("least_loaded")
    # w1 and w2 tie at load 1 -> first in conf order wins
    assert try_schedule("fn", state.conf(), script, reg) == "w1"
    session = SchedulerSession(state, reg, script)
    assert session.try_schedule("fn") == "w1"
    session.close()


def test_least_loaded_ignores_warmth_narrowing():
    """best_first with a warmth source jumps to the warm worker; the
    least_loaded author asked for load, so warmth must not pre-narrow."""
    state, reg = _three_workers(loads=(2, 0, 2))
    warmth = lambda f, w: {"w2": 2}.get(w, 0)
    assert try_schedule("fn", state.conf(), _script("best_first"), reg,
                        warmth=warmth) == "w2"
    assert try_schedule("fn", state.conf(), _script("least_loaded"), reg,
                        warmth=warmth) == "w1"


def test_warmest_prefers_rank_then_load_then_order():
    state, reg = _three_workers(loads=(0, 2, 0))
    script = _script("warmest")
    warmth = lambda f, w: {"w1": 2, "w2": 2}.get(w, 0)
    # w1/w2 tie on rank 2; w2 carries less load
    assert try_schedule("fn", state.conf(), script, reg, warmth=warmth) == "w2"
    session = SchedulerSession(state, reg, script)
    assert session.try_schedule("fn", warmth=warmth) == "w2"
    session.close()
    # without any warmth source all ranks are 0 -> load, then conf order
    assert try_schedule("fn", state.conf(), script, reg) == "w0"


def test_min_cost_weighs_lifecycle_against_congestion():
    """min_cost minimises `LIFECYCLE_S[warmth] + CONGESTION_S x load` — a hot
    but loaded worker can beat a cold idle one, unlike warmest's
    lexicographic (rank, load) order."""
    state, reg = _three_workers(loads=(0, 2, 0))
    script = _script("min_cost")
    # w1 hot (0.0 + 2*0.05 = 0.1) vs w2 warm idle (0.1 + 0 = 0.1): tie ->
    # first in conf order wins (w1); w0 cold idle loses at 0.5
    warmth = lambda f, w: {"w1": 2, "w2": 1}.get(w, 0)
    assert try_schedule("fn", state.conf(), script, reg, warmth=warmth) == "w1"
    session = SchedulerSession(state, reg, script)
    assert session.try_schedule("fn", warmth=warmth) == "w1"
    session.close()
    # no warmth source: every worker is cold, congestion decides -> w0
    assert try_schedule("fn", state.conf(), script, reg) == "w0"
    # eleven invocations of load beat one cold start: warmest would stay on
    # the hot worker, min_cost spills to the cold idle one
    state2, reg2 = _three_workers(loads=(11, 0, 0))
    warmth2 = lambda f, w: {"w0": 2}.get(w, 0)
    assert try_schedule("fn", state2.conf(), _script("warmest"), reg2,
                        warmth=warmth2) == "w0"
    assert try_schedule("fn", state2.conf(), script, reg2,
                        warmth=warmth2) == "w1"


def test_incremental_cost_clamps_warmth_rank():
    from repro.core.strategies import CONGESTION_S, LIFECYCLE_S, \
        incremental_cost

    assert incremental_cost(0, 0) == LIFECYCLE_S[0]
    assert incremental_cost(2, 3) == LIFECYCLE_S[2] + 3 * CONGESTION_S
    assert incremental_cost(-1, 0) == LIFECYCLE_S[0]  # clamped low
    assert incremental_cost(9, 0) == LIFECYCLE_S[2]  # clamped high


def test_min_cost_registers_with_alias():
    names = strategy_names()
    assert "min_cost" in names
    from repro.core import get_strategy
    assert get_strategy("min-cost") is get_strategy("min_cost")
    assert get_strategy("min_cost").narrow_warmth is False


# --------------------------------------------------------------------------- #
# valid() <-> rejection_reason() agreement (the explain-trace twin)
# --------------------------------------------------------------------------- #


def test_rejection_reason_agrees_with_valid():
    for seed in range(40):
        rng = random.Random(seed)
        script = random_script(rng)
        state, reg = random_cluster(rng)
        conf = state.conf()
        for tag in TAGS:
            f = f"fn_{tag}"
            for p in script.policies:
                for b in p.blocks:
                    for w in list(conf) + ["ghost"]:
                        reason = rejection_reason(f, w, conf, reg, b)
                        assert (reason is None) == valid(f, w, conf, reg, b), (
                            seed, f, w, reason)


# --------------------------------------------------------------------------- #
# scalar vs session bit-equality over the new strategies
# --------------------------------------------------------------------------- #

NEW_STRATEGIES = ("least_loaded", "warmest", "min_cost")


def new_strategy_script(rng: random.Random) -> AAppScript:
    """random_script, but every block draws from the new strategy pair (the
    legacy pair is covered by tests/test_batched_equivalence.py)."""
    from repro.core import Affinity, Invalidate, TagPolicy

    policies = []
    for tag in TAGS:
        blocks = []
        for _ in range(rng.randint(1, 3)):
            workers = (("*",) if rng.random() < 0.5 else
                       tuple(rng.sample([f"w{i}" for i in range(8)] + ["ghost"],
                                        rng.randint(1, 4))))
            aff, anti = [], []
            for t in TAGS:
                r = rng.randint(0, 5)
                if r == 0:
                    aff.append(t)
                elif r == 1:
                    anti.append(t)
            blocks.append(Block(
                workers=workers,
                strategy=rng.choice(NEW_STRATEGIES),
                invalidate=Invalidate(
                    capacity_used=rng.choice([None, 40.0, 80.0]),
                    max_concurrent_invocations=rng.choice([None, 1, 4]),
                ),
                affinity=Affinity(affine=tuple(aff), anti_affine=tuple(anti)),
            ))
        policies.append(TagPolicy(tag=tag, blocks=tuple(blocks),
                                  followup=rng.choice(["default", "fail"])))
    return AAppScript(policies=tuple(policies))


def _check_equivalence(seed: int, with_warmth: bool) -> None:
    rng = random.Random(seed)
    script = new_strategy_script(rng)
    state, reg = random_cluster(rng)
    fs = [f"fn_{rng.choice(TAGS)}" for _ in range(rng.randint(1, 12))]
    warmth = random_warmth(rng) if with_warmth else None

    ref_state = clone_state(state, reg)
    ref_rng = random.Random(seed * 7 + 1)
    expected = []
    for f in fs:
        w = try_schedule(f, ref_state.conf(), script, reg, rng=ref_rng,
                         warmth=warmth)
        expected.append(w)
        if w is not None:
            ref_state.allocate(f, w, reg)

    session = SchedulerSession(state, reg, script)
    res = session.schedule_wave(fs, rng=random.Random(seed * 7 + 1),
                                warmth=warmth, apply_to=state)
    session.close()
    assert res.assignments == expected, (
        f"seed={seed} warmth={with_warmth}: {res.assignments} != {expected}")


if HAS_HYPOTHESIS:
    @settings(max_examples=60, deadline=None)
    @given(seed=st.integers(0, 2**16), with_warmth=st.booleans())
    def test_new_strategies_session_equals_scalar_hypothesis(seed, with_warmth):
        _check_equivalence(seed, with_warmth)


def test_new_strategies_session_equals_scalar_seeded_sweep():
    """hypothesis-free fallback for minimal environments."""
    for seed in range(40):
        _check_equivalence(seed, with_warmth=bool(seed % 2))


def test_new_strategies_wave_equals_scalar():
    """The one-shot batched wave honours the new strategies too."""
    for seed in range(30):
        rng = random.Random(seed)
        script = new_strategy_script(rng)
        state, reg = random_cluster(rng)
        fs = [f"fn_{rng.choice(TAGS)}" for _ in range(rng.randint(1, 12))]
        warmth = random_warmth(rng) if seed % 2 else None

        ref_state = clone_state(state, reg)
        ref_rng = random.Random(seed * 7 + 1)
        expected = []
        for f in fs:
            w = try_schedule(f, ref_state.conf(), script, reg, rng=ref_rng,
                             warmth=warmth)
            expected.append(w)
            if w is not None:
                ref_state.allocate(f, w, reg)

        res = schedule_wave(fs, state.conf(), CompiledPolicies(script, reg),
                            reg, rng=random.Random(seed * 7 + 1),
                            backend="ref", warmth=warmth)
        assert res.assignments == expected, (seed, res.assignments, expected)
