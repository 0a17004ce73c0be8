"""Fused bulk decide kernels: three-backend agreement (pure-numpy twin,
jnp reference, Pallas interpret mode), tile-boundary padding edges, and
the strategy-constant lock-step promised by ``bulk_np``'s docstring.

The numpy twin scores in float64 and the accelerated backends in float32,
so cross-backend sweeps draw memory values on a 0.25 grid — exactly
representable in both widths — which makes validity *and* winner selection
bit-comparable across all three.  The jnp-vs-Pallas comparison asserts the
full (valid, score, winner) triple exactly: both compute the identical
float32 encoding.
"""
import numpy as np
import pytest

from repro.kernels.affinity import (
    CONGESTION_S,
    HAS_JAX,
    LIFECYCLE_S,
    NO_CAP,
    NO_CONC,
    STRATEGY_CODES,
    affinity_valid_np,
    bulk_decide_np,
)

try:
    from hypothesis import given, settings, strategies as hyp_st
    HAS_HYPOTHESIS = True
except ImportError:
    HAS_HYPOTHESIS = False

needs_jax = pytest.mark.skipif(not HAS_JAX, reason="needs jax")
needs_hyp = pytest.mark.skipif(not HAS_HYPOTHESIS, reason="needs hypothesis")


# --------------------------------------------------------------------------- #
# constants lock-step
# --------------------------------------------------------------------------- #


def test_strategy_constants_lock_step():
    """bulk_np duplicates the min_cost constants (importing strategies would
    be circular); its docstring promises this test keeps them in step."""
    from repro.core import strategies

    assert LIFECYCLE_S == strategies.LIFECYCLE_S
    assert CONGESTION_S == strategies.CONGESTION_S


def test_strategy_codes_cover_the_vectorizable_builtins():
    assert STRATEGY_CODES == {
        "best_first": 0, "least_loaded": 1, "warmest": 2, "min_cost": 3}


# --------------------------------------------------------------------------- #
# backend agreement
# --------------------------------------------------------------------------- #


def _case(W, T, R, seed):
    """Random bulk-decide inputs with float32-exact memory values."""
    rng = np.random.default_rng(seed)
    occ = rng.integers(0, 3, (W, T)).astype(np.int32)
    aff = rng.integers(-1, 2, (R, T)).astype(np.int8)
    wmask = rng.random((R, W)) > 0.2
    mem_used = (rng.integers(0, 200, W) * 0.25).astype(np.float32)
    max_mem = np.full(W, 64.0, np.float32)
    n_funcs = occ.sum(1).astype(np.int32)
    f_mem = (rng.integers(1, 64, R) * 0.25).astype(np.float32)
    cap = np.where(rng.random(R) > 0.5, 0.75, NO_CAP).astype(np.float32)
    conc = np.where(rng.random(R) > 0.5, 3, NO_CONC).astype(np.int32)
    strat = rng.integers(0, 4, R).astype(np.int32)
    warm = rng.integers(0, 3, (R, W)).astype(np.int32)
    return (occ, aff, wmask, mem_used, max_mem, n_funcs, f_mem,
            cap, conc, strat, warm)


def _np_oracle(args):
    return bulk_decide_np(*args, backend="np")


# shapes straddle the Pallas tile boundaries (BF/BW/T_ALIGN) on purpose:
# (130, 5, 257) exercises padding rows, a ragged tag axis, and a worker
# count one past a tile edge simultaneously
SHAPES = [(1, 1, 1), (7, 3, 5), (37, 19, 23),
          (128, 128, 128), (130, 5, 257), (256, 8, 64)]


@needs_jax
@pytest.mark.parametrize("W,T,R", SHAPES)
def test_bulk_backends_agree(W, T, R):
    args = _case(W, T, R, seed=W * 100003 + T * 101 + R)
    v_np, _s_np, w_np = _np_oracle(args)
    v_rf, s_rf, w_rf = bulk_decide_np(*args, backend="ref")
    v_pl, s_pl, w_pl = bulk_decide_np(*args, backend="pallas", interpret=True)
    np.testing.assert_array_equal(v_np, v_rf)
    np.testing.assert_array_equal(v_rf, v_pl)
    np.testing.assert_array_equal(np.asarray(w_np), np.asarray(w_rf))
    np.testing.assert_array_equal(np.asarray(w_rf), np.asarray(w_pl))
    # ref and pallas share one float32 encoding — bit-exact scores
    np.testing.assert_array_equal(np.asarray(s_rf), np.asarray(s_pl))


@needs_jax
def test_bulk_winner_is_first_valid_minimum():
    """Cross-check the fused argmin against a brute-force row scan."""
    args = _case(33, 7, 29, seed=9)
    valid, score, winner = _np_oracle(args)
    for r in range(29):
        row = np.where(valid[r], score[r], np.inf)
        if not np.isfinite(row).any():
            assert winner[r] == -1
        else:
            assert winner[r] == int(np.argmin(row))
            # first-minimum: no earlier worker ties the winner
            assert not (row[:winner[r]] == row[winner[r]]).any()


@needs_jax
@pytest.mark.parametrize("entry", ["bulk", "valid"])
def test_pallas_off_tpu_needs_interpret_and_auto_is_gone(entry):
    """No silent fallback: ``pallas`` on a non-TPU backend raises unless the
    caller asks for the interpreter, and no backend is picked for the
    caller."""
    import jax

    assert jax.default_backend() != "tpu"
    args = _case(5, 3, 4, seed=1)
    if entry == "bulk":
        call = bulk_decide_np
    else:
        args = args[:9]
        call = affinity_valid_np
    with pytest.raises(RuntimeError, match="interpret=True"):
        call(*args, backend="pallas")
    with pytest.raises(ValueError, match="unknown backend 'auto'"):
        call(*args, backend="auto")
    with pytest.raises(TypeError):
        call(*args)  # the backend has no default


def test_bulk_np_twin_runs_without_jax_guard():
    """The numpy twin is the minimal-environment path: force it explicitly
    and sanity-check shapes/dtypes (float64 scores, int winners)."""
    args = _case(11, 4, 6, seed=3)
    valid, score, winner = bulk_decide_np(*args, backend="np")
    assert valid.shape == (6, 11) and valid.dtype == bool
    assert score.shape == (6, 11) and score.dtype == np.float64
    assert winner.shape == (6,)
    placed = winner >= 0
    assert np.isfinite(score[np.arange(6)[placed], winner[placed]]).all()


if HAS_HYPOTHESIS:
    @needs_jax
    @needs_hyp
    @settings(max_examples=25, deadline=None)
    @given(hyp_st.integers(0, 2**31 - 1),
           hyp_st.integers(-1, 1), hyp_st.integers(-1, 1),
           hyp_st.integers(-1, 1))
    def test_affinity_valid_backend_agreement_at_tile_edges(
            seed, dW, dT, dR):
        """affinity_valid: numpy twin vs jnp ref vs Pallas interpret agree
        bit-for-bit, with shapes jittered around the kernel tile boundaries
        so the padding lanes (masked-off workers / tags / rows) are
        exercised, not just interior tiles."""
        from repro.kernels.affinity import affinity_valid
        from repro.kernels.affinity.kernel import BW, T_ALIGN

        W = max(1, BW + dW)
        T = max(1, T_ALIGN + dT)
        R = max(1, 8 + dR)
        occ, aff, wmask, mem_used, max_mem, n_funcs, f_mem, cap, conc, \
            _strat, _warm = _case(W, T, R, seed)
        args = (occ, aff, wmask, mem_used, max_mem, n_funcs, f_mem,
                cap, conc)
        v_np = affinity_valid_np(*args, backend="np")
        v_rf = np.asarray(affinity_valid(*args, backend="ref"))
        v_pl = np.asarray(affinity_valid(*args, backend="pallas",
                                          interpret=True))
        np.testing.assert_array_equal(v_np, v_rf)
        np.testing.assert_array_equal(v_rf, v_pl)

    @needs_jax
    @needs_hyp
    @settings(max_examples=20, deadline=None)
    @given(hyp_st.integers(1, 40), hyp_st.integers(1, 12),
           hyp_st.integers(1, 40), hyp_st.integers(0, 2**31 - 1))
    def test_bulk_backend_agreement_property(W, T, R, seed):
        args = _case(W, T, R, seed)
        v_np, _s, w_np = _np_oracle(args)
        v_rf, _s, w_rf = bulk_decide_np(*args, backend="ref")
        v_pl, _s, w_pl = bulk_decide_np(*args, backend="pallas", interpret=True)
        np.testing.assert_array_equal(v_np, v_rf)
        np.testing.assert_array_equal(v_rf, v_pl)
        np.testing.assert_array_equal(np.asarray(w_np), np.asarray(w_rf))
        np.testing.assert_array_equal(np.asarray(w_rf), np.asarray(w_pl))
