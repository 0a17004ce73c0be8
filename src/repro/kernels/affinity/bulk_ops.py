"""Public wrapper around the fused bulk decide kernel: padding, backend
dispatch, unpadding — the bulk twin of :mod:`.ops`, with the same rule:
the caller names the backend, and ``pallas`` off the TPU needs
``interpret=True``.  Without JAX the host entry still serves
``backend="np"``, the pure-numpy twin, so the group-commit batching front
end stays fully functional in minimal environments.
"""
from __future__ import annotations

import numpy as np

from .bulk_np import bulk_decide_ref_np
from .ref_np import NO_CAP, NO_CONC

try:
    import jax
    import jax.numpy as jnp

    from .bulk_kernel import bulk_decide_kernel
    from .bulk_ref import bulk_decide_ref
    from .kernel import BF, BW, T_ALIGN
    from .ops import round_up, check_pallas, pad_to

    # steady-state entry: one traced XLA program per (R, W, T) shape class
    # instead of ~30 eager op dispatches per wave
    _bulk_ref_jit = jax.jit(bulk_decide_ref)

    HAS_JAX = True
except ImportError:  # minimal environment: numpy twin only
    HAS_JAX = False


def _fill(R: int, W: int, strat, warm):
    if strat is None:
        strat = np.zeros((R,), np.int32)
    if warm is None:
        warm = np.zeros((R, W), np.int32)
    return strat, warm


def bulk_decide(
    occ,
    aff,
    wmask,
    mem_used,
    max_mem,
    n_funcs,
    f_mem,
    cap_pct=None,
    max_conc=None,
    strat=None,
    warm=None,
    *,
    backend: str,
    interpret: bool = False,
):
    """Fused bulk decide: returns ``(valid[R, W] bool, score[R, W] f32,
    winner[R] i32)`` with ``winner == -1`` when a row has no valid worker.

    ``backend``: ``pallas`` (the TPU kernel; ``interpret=True`` runs it in
    the Pallas interpreter on any backend) or ``ref`` (the jnp reference).
    """
    if not HAS_JAX:
        raise ImportError(
            "bulk_decide requires JAX; use bulk_decide_np for the numpy "
            "fallback")
    occ = np.asarray(occ, np.int32)
    aff = np.asarray(aff, np.int8)
    W, T = occ.shape
    R = aff.shape[0]
    if aff.shape[1] != T:
        raise ValueError(f"tag axes differ: occ {T}, aff {aff.shape[1]}")

    if cap_pct is None:
        cap_pct = np.full((R,), NO_CAP, np.float32)
    if max_conc is None:
        max_conc = np.full((R,), NO_CONC, np.int32)
    strat, warm = _fill(R, W, strat, warm)

    if backend == "ref":
        return _bulk_ref_jit(
            occ, aff, wmask, mem_used, max_mem, n_funcs, f_mem,
            cap_pct, max_conc, strat, warm)
    if backend != "pallas":
        raise ValueError(f"unknown backend {backend!r}")
    check_pallas(interpret)

    Rp = round_up(max(R, 1), BF)
    Wp = round_up(max(W, 1), BW)
    Tp = round_up(max(T, 1), T_ALIGN)

    def rows(x, dtype, fill=0):  # per-row column [R] -> [Rp, 1]
        return pad_to(np.reshape(x, (R, 1)), (Rp, 1), dtype, fill)

    def lanes(x, dtype):  # per-worker vector [W] -> lane-major [1, Wp]
        return pad_to(np.reshape(x, (1, W)), (1, Wp), dtype)

    valid, score, minval, minidx = bulk_decide_kernel(
        pad_to(aff, (Rp, Tp), np.int8),
        rows(f_mem, np.float32),
        rows(cap_pct, np.float32, NO_CAP),
        rows(max_conc, np.int32, NO_CONC),
        rows(strat, np.int32),
        pad_to(occ, (Wp, Tp), np.int32),
        lanes(mem_used, np.float32),
        lanes(max_mem, np.float32),
        lanes(n_funcs, np.int32),
        pad_to(wmask, (Rp, Wp), np.int8),
        pad_to(warm, (Rp, Wp), np.int32),
        interpret=interpret)
    winner = jnp.where(jnp.isinf(minval[:R, 0]), -1,
                       minidx[:R, 0]).astype(jnp.int32)
    return valid[:R, :W].astype(bool), score[:R, :W], winner


def bulk_decide_np(
    occ,
    aff,
    wmask,
    mem_used,
    max_mem,
    n_funcs,
    f_mem,
    cap_pct=None,
    max_conc=None,
    strat=None,
    warm=None,
    *,
    backend: str,
    interpret: bool = False,
):
    """Host-side convenience: numpy in/out.  ``backend="np"`` runs the
    pure-numpy twin — the exact-arithmetic (float64 score) path the
    incremental session uses, which also stands in for ``ref`` without
    JAX; ``ref``/``pallas`` go to :func:`bulk_decide`."""
    if backend == "pallas" or (backend != "np" and HAS_JAX):
        if not HAS_JAX:
            raise ImportError("backend 'pallas' requires JAX")
        valid, score, winner = bulk_decide(
            occ, aff, wmask, mem_used, max_mem, n_funcs, f_mem,
            cap_pct, max_conc, strat, warm, backend=backend,
            interpret=interpret)
        return np.asarray(valid), np.asarray(score), np.asarray(winner)
    if backend not in ("np", "ref"):
        raise ValueError(f"unknown backend {backend!r}")
    R = np.asarray(aff).shape[0]
    W = np.asarray(occ).shape[0]
    if cap_pct is None:
        cap_pct = np.full((R,), NO_CAP, np.float32)
    if max_conc is None:
        max_conc = np.full((R,), NO_CONC, np.int32)
    strat, warm = _fill(R, W, strat, warm)
    return bulk_decide_ref_np(
        occ, aff, wmask, mem_used, max_mem, n_funcs, f_mem, cap_pct,
        max_conc, strat, warm)
