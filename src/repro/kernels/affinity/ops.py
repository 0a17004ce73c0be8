"""Public wrapper around the affinity kernel: backend dispatch, and for
``pallas`` one jitted program per call that pads, runs the kernel and
unpads on the device.  The caller names the backend; nothing is picked for it.
``backend="pallas"`` compiles the kernel for the TPU and raises on any other
JAX backend unless the caller asks for the Pallas interpreter
(``interpret=True``, the tests' CPU path).  Without JAX installed at all
(minimal CI environments), ``affinity_valid_np`` still serves
``backend="np"``, the pure-numpy twin the scheduling session uses by default.
"""
from __future__ import annotations

import functools

import numpy as np

from .ref_np import NO_CAP, NO_CONC, affinity_valid_ref_np

try:
    import jax
    import jax.numpy as jnp

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
    cap_pct, max_conc = _checked(occ, aff, cap_pct, max_conc)
    if backend == "ref":
        return affinity_valid_ref(
            occ, aff, wmask, mem_used, max_mem, n_funcs, f_mem, cap_pct, max_conc
        )
    if backend != "pallas":
        raise ValueError(f"unknown backend {backend!r}")
    check_pallas(interpret)
    return _valid_program(
        *_inputs(occ, aff, wmask, mem_used, max_mem, n_funcs, f_mem,
                 cap_pct, max_conc),
        interpret=interpret,
    )


def _checked(occ, aff, cap_pct, max_conc):
    """The per-row limits, unlimited where not given."""
    T = np.shape(occ)[1]
    F = np.shape(aff)[0]
    if np.shape(aff)[1] != T:
        raise ValueError(f"tag axes differ: occ {T}, aff {np.shape(aff)[1]}")
    if cap_pct is None:
        cap_pct = np.full((F,), NO_CAP, np.float32)
    if max_conc is None:
        max_conc = np.full((F,), NO_CONC, np.int32)
    return cap_pct, max_conc


def _blocks(buf, F, W, T):
    """The two blocks of the packed input ``buf`` (numpy views on the host,
    slices in the program): per worker ``[T + 3, W]`` (the tag counts,
    ``n_funcs``, the float32 bits of ``mem_used`` and ``max_mem``) and per
    row ``[F, T + 3 + W]`` (the affinity row, the float32 bits of ``f_mem``
    and ``cap_pct``, ``max_conc``, the worker mask)."""
    n = (T + 3) * W
    return buf[:n].reshape(T + 3, W), buf[n:].reshape(F, T + 3 + W)


def _bits(x):
    return np.ascontiguousarray(x, np.float32).view(np.int32)


def _inputs(occ, aff, wmask, mem_used, max_mem, n_funcs, f_mem, cap_pct,
            max_conc):
    """The kernel's nine inputs at their real shapes, packed into one int32
    host array so that a call ships one buffer (each array shipped is a
    host-device round trip of its own), and its ``(F, W, T)``.  int8 and
    bool values are widened and float32 values carried as their bits, both
    exact; the only casts are the kernel's dtypes (float64 memory to
    float32).  The padding is :func:`_valid_program`'s."""
    W, T = np.shape(occ)
    F = np.shape(aff)[0]
    buf = np.empty((T + 3) * W + F * (T + 3 + W), np.int32)
    per_w, per_r = _blocks(buf, F, W, T)
    per_w[:T] = np.transpose(occ)
    per_w[T] = n_funcs
    per_w[T + 1] = _bits(mem_used)
    per_w[T + 2] = _bits(max_mem)
    per_r[:, :T] = aff
    per_r[:, T] = _bits(f_mem)
    per_r[:, T + 1] = _bits(cap_pct)
    per_r[:, T + 2] = max_conc
    per_r[:, T + 3:] = wmask
    return buf, (F, W, T)


if HAS_JAX:

    @functools.partial(jax.jit, static_argnames=("shape", "interpret"))
    def _valid_program(buf, shape, *, interpret):
        """One device program per validity call: unpack ``buf``, pad to
        tile-aligned shapes, run the kernel, slice back to ``[F, W]`` bool.
        Compiled once per ``shape`` (F, W, T)."""
        F, W, T = shape
        per_w, per_r = _blocks(buf, F, W, T)
        Fp, Wp, Tp = (round_up(max(F, 1), BF), round_up(max(W, 1), BW),
                      round_up(max(T, 1), T_ALIGN))

        def f32(x):
            return jax.lax.bitcast_convert_type(x, jnp.float32)

        def pad(x, to, fill=0):
            return jnp.pad(x, [(0, n - m) for m, n in zip(x.shape, to)],
                           constant_values=fill)

        valid = affinity_valid_kernel(
            pad(per_r[:, :T].astype(jnp.int8), (Fp, Tp)),
            pad(f32(per_r[:, T:T + 1]), (Fp, 1)),
            pad(f32(per_r[:, T + 1:T + 2]), (Fp, 1), NO_CAP),
            pad(per_r[:, T + 2:T + 3], (Fp, 1), NO_CONC),
            pad(per_w[:T].T, (Wp, Tp)),
            pad(f32(per_w[T + 1:T + 2]), (1, Wp)),
            pad(f32(per_w[T + 2:T + 3]), (1, Wp)),
            pad(per_w[T:T + 1], (1, Wp)),
            pad(per_r[:, T + 3:].astype(jnp.int8), (Fp, Wp)),
            interpret=interpret)
        return valid[:F, :W].astype(bool)


def _timed_pallas(occ, aff, wmask, mem_used, max_mem, n_funcs, f_mem,
                  cap_pct, max_conc, interpret: bool, tm) -> np.ndarray:
    """:func:`affinity_valid`'s Pallas path to a host array, its stages
    timed by ``tm`` (a :class:`repro.obs.StageTimers`): ``valid_pad`` (the
    host packing, :func:`_inputs`), ``valid_launch`` (the program call up
    to its return: the one transfer and dispatch), ``valid_fetch`` (device
    wait and the one copy of the ``[F, W]`` bools to the host); counters
    ``valid_launches`` and ``valid_h2d_bytes`` (the bytes shipped)."""
    cap_pct, max_conc = _checked(occ, aff, cap_pct, max_conc)
    check_pallas(interpret)
    with tm.span("valid_pad"):
        buf, shape = _inputs(occ, aff, wmask, mem_used, max_mem, n_funcs,
                             f_mem, cap_pct, max_conc)
    tm.count("valid_launches")
    tm.count("valid_h2d_bytes", buf.nbytes)
    with tm.span("valid_launch"):
        valid = _valid_program(buf, shape, interpret=interpret)
    with tm.span("valid_fetch"):
        return np.asarray(valid)


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
    times the Pallas path's host packing, launch and fetch."""
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
