"""Pallas TPU kernel for the fused bulk decide pass.

Extends the per-term validity kernel (:mod:`.kernel`) into one launch that
emits the candidate mask, the strategy score matrix, *and* the per-row
argmin winner.  Grid: (R / BF, W / BW) with the worker axis minor-most —
TPU grids iterate the minor dimension sequentially, so the [BF, 1] winner
accumulators are initialised at ``j == 0`` and combined across worker tiles
with a strict ``<`` (an earlier tile keeps a tied minimum, which together
with the in-tile first-minimum scan reproduces ``np.argmin``'s
first-min-index rule — the strategies' first-candidate-on-tie semantics).

Scores use the float32 encoding of :mod:`.bulk_ref` (``warmest`` packs with
base ``2**22``); invalid cells score ``+inf`` so padded workers (``wmask``
padded with 0) can never win, and an all-``inf`` row surfaces as winner
``-1`` in the host wrapper.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .bulk_np import STRAT_BEST_FIRST, STRAT_LEAST_LOADED, STRAT_WARMEST
from .bulk_ref import (MIN_COST_LIFE_F32, MIN_COST_LOAD_CLAMP,
                       WARMEST_BASE_F32)
from .kernel import BF, BW, T_ALIGN


def _bulk_decide_kernel(
    aff_ref,  # [BF, T] int8
    fmem_ref,  # [BF, 1] f32
    cap_ref,  # [BF, 1] f32
    conc_ref,  # [BF, 1] i32
    strat_ref,  # [BF, 1] i32 strategy code
    occ_ref,  # [BW, T] i32
    mem_ref,  # [1, BW] f32
    maxm_ref,  # [1, BW] f32
    nfn_ref,  # [1, BW] i32
    wmask_ref,  # [BF, BW] int8
    warm_ref,  # [BF, BW] i32 warmth rank
    valid_ref,  # [BF, BW] int8 out
    score_ref,  # [BF, BW] f32 out
    minval_ref,  # [BF, 1] f32 out (accumulated across worker tiles)
    minidx_ref,  # [BF, 1] i32 out
):
    j = pl.program_id(1)
    aff = aff_ref[...].astype(jnp.int32)  # no int8 vector compare on TPU
    occ = occ_ref[...]

    empty = (occ == 0).astype(jnp.float32)  # [BW, T]
    present = 1.0 - empty
    pos = (aff == 1).astype(jnp.float32)  # [BF, T]
    neg = (aff == -1).astype(jnp.float32)

    violations = jax.lax.dot_general(
        pos, empty, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) + jax.lax.dot_general(
        neg, present, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [BF, BW]
    ok_aff = violations == 0.0

    mem_used = mem_ref[...]  # [1, BW], lane-major
    max_mem = maxm_ref[...]
    n_funcs = nfn_ref[...]
    f_mem = fmem_ref[...]  # [BF, 1]

    ok_fit = mem_used + f_mem <= max_mem
    ok_cap = mem_used < cap_ref[...] * 0.01 * max_mem
    ok_conc = n_funcs < conc_ref[...]
    ok_w = wmask_ref[...].astype(jnp.int32) != 0
    valid = ok_aff & ok_fit & ok_cap & ok_conc & ok_w

    rank = jnp.clip(warm_ref[...], 0, 2)  # [BF, BW]
    rankf = rank.astype(jnp.float32)
    loadf = n_funcs.astype(jnp.float32)  # [1, BW]
    strat = strat_ref[...]  # [BF, 1]

    s_wm = ((2.0 - rankf) * WARMEST_BASE_F32
            + jnp.minimum(loadf, WARMEST_BASE_F32 - 1.0))
    life = jnp.where(rank >= 2, MIN_COST_LIFE_F32[2],
                     jnp.where(rank >= 1, MIN_COST_LIFE_F32[1],
                               MIN_COST_LIFE_F32[0]))
    s_mc = life + jnp.minimum(loadf, MIN_COST_LOAD_CLAMP)
    score = jnp.where(
        strat == STRAT_BEST_FIRST, 2.0 - rankf,
        jnp.where(strat == STRAT_LEAST_LOADED, loadf + 0.0 * rankf,
                  jnp.where(strat == STRAT_WARMEST, s_wm, s_mc)))
    score = jnp.where(valid, score, jnp.inf)

    valid_ref[...] = valid.astype(jnp.int8)
    score_ref[...] = score

    # In-tile first-minimum, then strict-< combine across worker tiles.
    tile_min = jnp.min(score, axis=1, keepdims=True)  # [BF, 1]
    lane = jax.lax.broadcasted_iota(jnp.int32, score.shape, 1)
    tile_idx = jnp.min(jnp.where(score == tile_min, lane, BW),
                       axis=1, keepdims=True) + j * BW

    @pl.when(j == 0)
    def _init():
        minval_ref[...] = tile_min
        minidx_ref[...] = tile_idx

    @pl.when(j > 0)
    def _combine():
        better = tile_min < minval_ref[...]
        minval_ref[...] = jnp.where(better, tile_min, minval_ref[...])
        minidx_ref[...] = jnp.where(better, tile_idx, minidx_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret",))
def bulk_decide_kernel(
    aff, f_mem, cap_pct, max_conc, strat, occ, mem_used, max_mem, n_funcs,
    wmask, warm, *, interpret=False,
):
    """Padded-shape entry point: R, W multiples of (BF, BW); T multiple
    of 128.

    Shapes: aff[R,T] i8, f_mem/cap_pct[R,1] f32, max_conc/strat[R,1] i32,
    occ[W,T] i32, mem_used/max_mem[1,W] f32, n_funcs[1,W] i32,
    wmask[R,W] i8, warm[R,W] i32 -> (valid[R,W] i8, score[R,W] f32,
    minval[R,1] f32, minidx[R,1] i32).
    """
    R, T = aff.shape
    W = occ.shape[0]
    assert mem_used.shape == max_mem.shape == n_funcs.shape == (1, W)
    assert R % BF == 0 and W % BW == 0 and T % T_ALIGN == 0, (R, W, T)
    grid = (R // BF, W // BW)

    return pl.pallas_call(
        _bulk_decide_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((BF, T), lambda i, j: (i, 0)),  # aff
            pl.BlockSpec((BF, 1), lambda i, j: (i, 0)),  # f_mem
            pl.BlockSpec((BF, 1), lambda i, j: (i, 0)),  # cap_pct
            pl.BlockSpec((BF, 1), lambda i, j: (i, 0)),  # max_conc
            pl.BlockSpec((BF, 1), lambda i, j: (i, 0)),  # strat
            pl.BlockSpec((BW, T), lambda i, j: (j, 0)),  # occ
            pl.BlockSpec((1, BW), lambda i, j: (0, j)),  # mem_used
            pl.BlockSpec((1, BW), lambda i, j: (0, j)),  # max_mem
            pl.BlockSpec((1, BW), lambda i, j: (0, j)),  # n_funcs
            pl.BlockSpec((BF, BW), lambda i, j: (i, j)),  # wmask
            pl.BlockSpec((BF, BW), lambda i, j: (i, j)),  # warm
        ],
        out_specs=[
            pl.BlockSpec((BF, BW), lambda i, j: (i, j)),  # valid
            pl.BlockSpec((BF, BW), lambda i, j: (i, j)),  # score
            pl.BlockSpec((BF, 1), lambda i, j: (i, 0)),  # minval
            pl.BlockSpec((BF, 1), lambda i, j: (i, 0)),  # minidx
        ],
        out_shape=[
            jax.ShapeDtypeStruct((R, W), jnp.int8),
            jax.ShapeDtypeStruct((R, W), jnp.float32),
            jax.ShapeDtypeStruct((R, 1), jnp.float32),
            jax.ShapeDtypeStruct((R, 1), jnp.int32),
        ],
        interpret=interpret,
        name="bulk_decide_kernel",  # the device trace's op name, which bench/ reads
    )(aff, f_mem, cap_pct, max_conc, strat, occ, mem_used, max_mem, n_funcs,
      wmask, warm)
