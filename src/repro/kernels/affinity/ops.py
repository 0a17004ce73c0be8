"""Public wrapper around the affinity kernel: padding, backend dispatch,
unpadding.  The caller names the backend; nothing is picked for it.
``backend="pallas"`` compiles the kernel for the TPU and raises on any other
JAX backend unless the caller asks for the Pallas interpreter
(``interpret=True``, the tests' CPU path).  Without JAX installed at all
(minimal CI environments), ``affinity_valid_np`` still serves
``backend="np"``, the pure-numpy twin the scheduling session uses by default.
"""
from __future__ import annotations

import numpy as np

from .ref_np import NO_CAP, NO_CONC, affinity_valid_ref_np

try:
    import jax

    from .kernel import BF, BW, T_ALIGN, affinity_valid_kernel
    from .ref import affinity_valid_ref

    HAS_JAX = True
except ImportError:  # minimal environment: numpy reference only
    HAS_JAX = False


def round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def pad_to(x, shape, dtype, fill=0) -> np.ndarray:
    """Host-side zero (or ``fill``) padding of ``x`` into ``shape``, so the
    kernel only ever sees tile-aligned shapes."""
    x = np.asarray(x, dtype)
    out = np.full(shape, fill, dtype)
    out[tuple(slice(0, n) for n in x.shape)] = x
    return out


def check_pallas(interpret: bool) -> None:
    """``backend="pallas"`` runs compiled on a TPU, or in the interpreter
    when the caller asks; it never drops to the interpreter on its own."""
    if not interpret and jax.default_backend() != "tpu":
        raise RuntimeError(
            "backend='pallas' compiles for a TPU, but JAX's backend is "
            f"{jax.default_backend()!r}; pass interpret=True to run the "
            "kernel body in the Pallas interpreter")


def affinity_valid(
    occ,
    aff,
    wmask,
    mem_used,
    max_mem,
    n_funcs,
    f_mem,
    cap_pct=None,
    max_conc=None,
    *,
    backend: str,
    interpret: bool = False,
):
    """Batched Listing-1 ``valid()``: returns ``valid[F, W]`` (bool).

    ``backend``: ``pallas`` (the TPU kernel; ``interpret=True`` runs it in
    the Pallas interpreter on any backend) or ``ref`` (the jnp reference).
    """
    if not HAS_JAX:
        raise ImportError(
            "affinity_valid requires JAX; use affinity_valid_np for the "
            "numpy fallback")
    F, W, cap_pct, max_conc = _checked(occ, aff, cap_pct, max_conc)
    if backend == "ref":
        return affinity_valid_ref(
            occ, aff, wmask, mem_used, max_mem, n_funcs, f_mem, cap_pct, max_conc
        )
    if backend != "pallas":
        raise ValueError(f"unknown backend {backend!r}")
    check_pallas(interpret)
    valid = affinity_valid_kernel(
        *_padded(occ, aff, wmask, mem_used, max_mem, n_funcs, f_mem,
                 cap_pct, max_conc),
        interpret=interpret,
    )
    return valid[:F, :W].astype(bool)


def _checked(occ, aff, cap_pct, max_conc):
    """``F``, ``W`` and the per-row limits, unlimited where not given."""
    W, T = np.shape(occ)
    F = np.shape(aff)[0]
    if np.shape(aff)[1] != T:
        raise ValueError(f"tag axes differ: occ {T}, aff {np.shape(aff)[1]}")
    if cap_pct is None:
        cap_pct = np.full((F,), NO_CAP, np.float32)
    if max_conc is None:
        max_conc = np.full((F,), NO_CONC, np.int32)
    return F, W, cap_pct, max_conc


def _padded(occ, aff, wmask, mem_used, max_mem, n_funcs, f_mem, cap_pct,
            max_conc):
    """The kernel's nine inputs, padded on the host to tile-aligned
    shapes."""
    W, T = np.shape(occ)
    F = np.shape(aff)[0]
    Fp, Wp, Tp = round_up(max(F, 1), BF), round_up(max(W, 1), BW), round_up(max(T, 1), T_ALIGN)
    return (
        pad_to(aff, (Fp, Tp), np.int8),
        pad_to(np.reshape(f_mem, (F, 1)), (Fp, 1), np.float32),
        pad_to(np.reshape(cap_pct, (F, 1)), (Fp, 1), np.float32, NO_CAP),
        pad_to(np.reshape(max_conc, (F, 1)), (Fp, 1), np.int32, NO_CONC),
        pad_to(occ, (Wp, Tp), np.int32),
        pad_to(np.reshape(mem_used, (1, W)), (1, Wp), np.float32),
        pad_to(np.reshape(max_mem, (1, W)), (1, Wp), np.float32),
        pad_to(np.reshape(n_funcs, (1, W)), (1, Wp), np.int32),
        pad_to(wmask, (Fp, Wp), np.int8),
    )


def _timed_pallas(occ, aff, wmask, mem_used, max_mem, n_funcs, f_mem,
                  cap_pct, max_conc, interpret: bool, tm) -> np.ndarray:
    """:func:`affinity_valid`'s Pallas path to a host array, its stages
    timed by ``tm`` (a :class:`repro.obs.StageTimers`): ``valid_pad`` (host
    padding), ``valid_launch`` (the kernel call up to its return: transfers
    of the padded inputs and dispatch), ``valid_fetch`` (device wait, unpad,
    copy to the host); counters ``valid_launches`` and ``valid_h2d_bytes``
    (the padded inputs' bytes)."""
    F, W, cap_pct, max_conc = _checked(occ, aff, cap_pct, max_conc)
    check_pallas(interpret)
    with tm.span("valid_pad"):
        args = _padded(occ, aff, wmask, mem_used, max_mem, n_funcs, f_mem,
                       cap_pct, max_conc)
    tm.count("valid_launches")
    tm.count("valid_h2d_bytes", sum(a.nbytes for a in args))
    with tm.span("valid_launch"):
        valid = affinity_valid_kernel(*args, interpret=interpret)
    with tm.span("valid_fetch"):
        return np.asarray(valid[:F, :W].astype(bool))


def affinity_valid_np(
    occ,
    aff,
    wmask,
    mem_used,
    max_mem,
    n_funcs,
    f_mem,
    cap_pct=None,
    max_conc=None,
    *,
    backend: str,
    interpret: bool = False,
    timers=None,
) -> np.ndarray:
    """Host-side convenience: numpy in/out.  ``backend="np"`` runs the
    pure-numpy reference — the zero-dispatch CPU hot path the incremental
    scheduling session uses (bit-identical to the jnp reference), which
    also stands in for ``ref`` without JAX; ``ref``/``pallas`` go to
    :func:`affinity_valid`.  ``timers`` (a :class:`repro.obs.StageTimers`)
    times the Pallas path's padding, launch and fetch."""
    if backend == "pallas" or (backend != "np" and HAS_JAX):
        if not HAS_JAX:
            raise ImportError("backend 'pallas' requires JAX")
        if timers is not None and backend == "pallas":
            return _timed_pallas(occ, aff, wmask, mem_used, max_mem, n_funcs,
                                 f_mem, cap_pct, max_conc, interpret, timers)
        return np.asarray(affinity_valid(
            occ, aff, wmask, mem_used, max_mem, n_funcs, f_mem,
            cap_pct, max_conc, backend=backend, interpret=interpret))
    if backend not in ("np", "ref"):
        raise ValueError(f"unknown backend {backend!r}")
    F = np.asarray(aff).shape[0]
    if cap_pct is None:
        cap_pct = np.full((F,), NO_CAP, np.float32)
    if max_conc is None:
        max_conc = np.full((F,), NO_CONC, np.int32)
    return affinity_valid_ref_np(
        occ, aff, wmask, mem_used, max_mem, n_funcs, f_mem, cap_pct, max_conc)
