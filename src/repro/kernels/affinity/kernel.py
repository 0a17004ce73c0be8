"""Pallas TPU kernel for the batched ``valid()`` matrix.

Grid: (F / BF, W / BW).  Per grid cell the kernel holds in VMEM:

* ``aff``   block  [BF, T]   int8   (the pending functions' affinity rows)
* ``occ``   block  [BW, T]   int32  (the workers' tag occupancy)
* per-function ``[BF, 1]`` columns and lane-major per-worker ``[1, BW]``
  rows for the memory/concurrency terms
* ``valid`` output [BF, BW]  int8

int8 operands are widened to int32 before any compare: Mosaic has no int8
vector comparison on TPU.  The per-worker vectors arrive as ``[1, W]`` so
their blocks need no in-kernel transpose (a ``[BW, 1]`` -> ``[1, BW]``
reshape blows the scoped VMEM limit).

The affinity check is MXU work: with ``pos = (aff==1)`` and ``neg = (aff==-1)``
as f32 masks, ``violations = pos @ empty.T + neg @ present.T`` is two
[BF,T]x[T,BW] matmuls; a worker passes iff its violation count is exactly 0.
Capacity / concurrency / worker-list masks fuse into the same cell on the VPU.

Tag-count tensors are tiny (T <= a few thousand), so the whole T extent stays
resident per block; with BF = BW = 128 and T = 1024 the working set is
128*1024*(1+4)B + 2*128*1024*4B (f32 casts) + small vectors ~= 1.7 MiB, well
inside the ~16 MiB VMEM budget.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BF = 128  # function-block tile
BW = 128  # worker-block tile
T_ALIGN = 128  # tag axis padded to lane width


def _affinity_kernel(
    aff_ref,  # [BF, T] int8
    fmem_ref,  # [BF, 1] f32
    cap_ref,  # [BF, 1] f32 (percent, NO_CAP sentinel when absent)
    conc_ref,  # [BF, 1] i32
    occ_ref,  # [BW, T] i32
    mem_ref,  # [1, BW] f32 (memory_used)
    maxm_ref,  # [1, BW] f32 (max_memory)
    nfn_ref,  # [1, BW] i32
    wmask_ref,  # [BF, BW] int8
    valid_ref,  # [BF, BW] int8 out
):
    aff = aff_ref[...].astype(jnp.int32)
    occ = occ_ref[...]

    empty = (occ == 0).astype(jnp.float32)  # [BW, T]
    present = 1.0 - empty
    pos = (aff == 1).astype(jnp.float32)  # [BF, T]
    neg = (aff == -1).astype(jnp.float32)

    violations = jax.lax.dot_general(
        pos,
        empty,
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) + jax.lax.dot_general(
        neg,
        present,
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [BF, BW]
    ok_aff = violations == 0.0

    mem_used = mem_ref[...]  # [1, BW]
    max_mem = maxm_ref[...]
    n_funcs = nfn_ref[...]
    f_mem = fmem_ref[...]  # [BF, 1]
    cap = cap_ref[...]
    conc = conc_ref[...]

    ok_fit = mem_used + f_mem <= max_mem
    ok_cap = mem_used < cap * 0.01 * max_mem
    ok_conc = n_funcs < conc
    ok_w = wmask_ref[...].astype(jnp.int32) != 0

    valid = ok_aff & ok_fit & ok_cap & ok_conc & ok_w
    valid_ref[...] = valid.astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("interpret",))
def affinity_valid_kernel(
    aff, f_mem, cap_pct, max_conc, occ, mem_used, max_mem, n_funcs, wmask, *, interpret=False
):
    """Padded-shape entry point: F, W multiples of (BF, BW); T multiple of 128.

    Shapes: aff[F,T] i8, f_mem/cap_pct[F,1] f32, max_conc[F,1] i32,
    occ[W,T] i32, mem_used/max_mem[1,W] f32, n_funcs[1,W] i32,
    wmask[F,W] i8 -> valid[F,W] i8.
    """
    F, T = aff.shape
    W = occ.shape[0]
    assert mem_used.shape == max_mem.shape == n_funcs.shape == (1, W)
    assert F % BF == 0 and W % BW == 0 and T % T_ALIGN == 0, (F, W, T)
    grid = (F // BF, W // BW)

    return pl.pallas_call(
        _affinity_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((BF, T), lambda i, j: (i, 0)),  # aff
            pl.BlockSpec((BF, 1), lambda i, j: (i, 0)),  # f_mem
            pl.BlockSpec((BF, 1), lambda i, j: (i, 0)),  # cap_pct
            pl.BlockSpec((BF, 1), lambda i, j: (i, 0)),  # max_conc
            pl.BlockSpec((BW, T), lambda i, j: (j, 0)),  # occ
            pl.BlockSpec((1, BW), lambda i, j: (0, j)),  # mem_used
            pl.BlockSpec((1, BW), lambda i, j: (0, j)),  # max_mem
            pl.BlockSpec((1, BW), lambda i, j: (0, j)),  # n_funcs
            pl.BlockSpec((BF, BW), lambda i, j: (i, j)),  # wmask
        ],
        out_specs=pl.BlockSpec((BF, BW), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((F, W), jnp.int8),
        interpret=interpret,
    )(aff, f_mem, cap_pct, max_conc, occ, mem_used, max_mem, n_funcs, wmask)
