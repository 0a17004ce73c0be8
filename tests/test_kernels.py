"""Per-kernel shape/dtype sweeps: Pallas (interpret mode on CPU) vs ref.py."""
import contextlib

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.kernels.affinity import affinity_valid
from repro.kernels.flash_attention import flash_attention, flash_attention_ref
from repro.kernels.mamba_scan import selective_scan, selective_scan_ref

# --------------------------------------------------------------------------- #
# affinity
# --------------------------------------------------------------------------- #


# divimp's per-item calls: a zone hop (W = 3072) and a delegated heavy
# decision (W = 6144), one or two blocks of 4-5 tag columns; then shapes
# where no axis is a tile multiple
@pytest.mark.parametrize("W,T,F", [(1, 1, 1), (7, 3, 5), (37, 19, 23),
                                   (128, 128, 128), (130, 5, 257),
                                   (3072, 5, 2), (6144, 4, 2), (3072, 5, 1),
                                   (6144, 5, 1), (1000, 37, 3)])
def test_affinity_kernel_matches_ref(W, T, F):
    """The Pallas program equals the jnp reference and the numpy twin bit
    for bit: ``[F, W]`` bool."""
    from repro.kernels.affinity import affinity_valid_np

    rng = np.random.default_rng(W * 1000 + T * 10 + F)
    occ = rng.integers(0, 3, (W, T)).astype(np.int32)
    aff = rng.integers(-1, 2, (F, T)).astype(np.int8)
    wmask = rng.random((F, W)) > 0.2
    mem_used = (rng.random(W) * 100).astype(np.float32)
    max_mem = np.full(W, 120, np.float32)
    n_funcs = occ.sum(1).astype(np.int32)
    f_mem = (rng.random(F) * 30).astype(np.float32)
    cap = np.where(rng.random(F) > 0.5, 80.0, 1e9).astype(np.float32)
    conc = np.where(rng.random(F) > 0.5, 10, 2**30).astype(np.int32)
    args = (occ, aff, wmask, mem_used, max_mem, n_funcs, f_mem, cap, conc)
    ref = np.asarray(affinity_valid(*args, backend="ref"))
    out = np.asarray(affinity_valid(*args, backend="pallas", interpret=True))
    twin = affinity_valid_np(*args, backend="np")
    assert out.dtype == bool and out.shape == (F, W)
    np.testing.assert_array_equal(ref, out)
    np.testing.assert_array_equal(twin, out)


def test_affinity_wrapper_stages_and_bytes():
    """With stage timers, the Pallas path records one each of its host
    packing, launch and fetch spans, counts the launch and the bytes of the
    unpadded inputs it ships, and returns what it returns untimed."""
    from repro.kernels.affinity import affinity_valid_np
    from repro.obs import MetricsRegistry, StageTimers

    rng = np.random.default_rng(3)
    W, T, F = 37, 19, 5
    occ = rng.integers(0, 3, (W, T)).astype(np.int32)
    aff = rng.integers(-1, 2, (F, T)).astype(np.int8)
    wmask = rng.random((F, W)) > 0.2
    mem_used = rng.random(W) * 100  # float64, as the session keeps it
    max_mem = np.full(W, 120.0)
    n_funcs = occ.sum(1).astype(np.int32)
    f_mem = (rng.random(F) * 30).astype(np.float32)
    cap = np.full(F, 1e9, np.float32)
    conc = np.full(F, 2**30, np.int32)
    args = (occ, aff, wmask, mem_used, max_mem, n_funcs, f_mem, cap, conc)
    names = []

    def annotate(name, **_meta):
        names.append(name)
        return contextlib.nullcontext()

    reg = MetricsRegistry()
    tm = StageTimers(reg, sample=1, annotate=annotate)
    timed = affinity_valid_np(*args, backend="pallas", interpret=True,
                              timers=tm)
    plain = affinity_valid_np(*args, backend="pallas", interpret=True)
    assert isinstance(timed, np.ndarray) and timed.dtype == bool
    assert timed.shape == (F, W)
    np.testing.assert_array_equal(timed, plain)
    assert names == ["sched.stage.valid_pad", "sched.stage.valid_launch",
                     "sched.stage.valid_fetch"]
    snap = reg.snapshot()
    for stage in ("valid_pad", "valid_launch", "valid_fetch"):
        assert snap[f"sched.stage.{stage}_s.count"] == 1
    assert snap["sched.stage.valid_launches"] == 1
    # one int32 buffer of the inputs at their real shapes, nothing padded
    # to the kernel's tiles: per worker T tag counts, n_funcs and the two
    # memory figures; per row the T affinities, three limits, W mask bits
    shipped = 4 * (W * (T + 3) + F * (T + 3 + W))
    assert snap["sched.stage.valid_h2d_bytes"] == shipped


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 40), st.integers(1, 12), st.integers(1, 40),
       st.integers(0, 2**31 - 1))
def test_affinity_kernel_property(W, T, F, seed):
    rng = np.random.default_rng(seed)
    occ = rng.integers(0, 2, (W, T)).astype(np.int32)
    aff = rng.integers(-1, 2, (F, T)).astype(np.int8)
    wmask = np.ones((F, W), bool)
    mem_used = np.zeros(W, np.float32)
    max_mem = np.ones(W, np.float32)
    n_funcs = np.zeros(W, np.int32)
    f_mem = np.zeros(F, np.float32)
    out = np.asarray(affinity_valid(occ, aff, wmask, mem_used, max_mem, n_funcs,
                                    f_mem, backend="pallas", interpret=True))
    # brute-force oracle
    for f in range(F):
        for w in range(W):
            ok = True
            for t in range(T):
                if aff[f, t] == 1 and occ[w, t] == 0:
                    ok = False
                if aff[f, t] == -1 and occ[w, t] > 0:
                    ok = False
            assert out[f, w] == ok


# --------------------------------------------------------------------------- #
# flash attention
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize("B,Sq,Skv,H,K,hd,causal,window,dt,tol", [
    (2, 128, 128, 4, 2, 64, True, None, jnp.float32, 2e-5),
    (1, 256, 256, 8, 8, 32, True, 64, jnp.float32, 2e-5),
    (2, 200, 200, 4, 1, 64, True, None, jnp.bfloat16, 5e-2),
    (1, 128, 384, 4, 2, 64, False, None, jnp.float32, 2e-5),
    (1, 384, 384, 2, 2, 128, True, 100, jnp.float32, 2e-5),
])
def test_flash_attention_sweep(B, Sq, Skv, H, K, hd, causal, window, dt, tol):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, Sq, H, hd), dt)
    k = jax.random.normal(ks[1], (B, Skv, K, hd), dt)
    v = jax.random.normal(ks[2], (B, Skv, K, hd), dt)
    ref = flash_attention_ref(q, k, v, causal=causal, window=window)
    out = flash_attention(q, k, v, causal=causal, window=window, bq=128, bk=128)
    err = np.max(np.abs(np.asarray(ref, np.float32) - np.asarray(out, np.float32)))
    assert err < tol, err


# --------------------------------------------------------------------------- #
# mamba selective scan
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize("B,S,D,N,chunk,bd", [
    (2, 64, 32, 4, 16, 16), (1, 100, 48, 16, 32, 16), (2, 128, 64, 8, 64, 64),
    (1, 48, 16, 2, 48, 16),
])
def test_mamba_scan_sweep(B, S, D, N, chunk, bd):
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    dt = jax.nn.softplus(jax.random.normal(ks[0], (B, S, D))).astype(jnp.float32) * 0.1
    x = jax.random.normal(ks[1], (B, S, D), jnp.float32)
    b = jax.random.normal(ks[2], (B, S, N), jnp.float32)
    c = jax.random.normal(ks[3], (B, S, N), jnp.float32)
    a = -jnp.exp(jax.random.normal(ks[4], (D, N), jnp.float32))
    ref = selective_scan_ref(dt, x, b, c, a)
    out = selective_scan(dt, x, b, c, a, chunk=chunk, bd=bd)
    assert float(jnp.max(jnp.abs(ref - out))) < 1e-4
