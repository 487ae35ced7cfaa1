"""Pallas TPU kernel: block-max pruned text probe (probe → score → select).

The text-side twin of ``kernels/sweep_score``'s pruned sweep.  TEXT-FIRST
walks the driver term's posting list; unpruned it streams every posting.
This kernel walks the driver's 128-posting *blocks* and tests each block's
precomputed score upper bound

    ub[b] = w_text · blk_max_impact[b] + rest_ub

(``rest_ub`` = the query-constant bound on everything a posting's final
score can gain beyond its own impact: the other query terms' max impacts,
the geo contribution, and pagerank) against a running threshold θ.  Blocks
that cannot beat θ are *skipped before their bytes move*: the impact plane
stays in ``ANY`` memory space and the kernel issues one manual
``make_async_copy`` per surviving block under ``pl.when``, so a skipped
block truly streams zero bytes — the same DMA-elision discipline as the
spatial pruned sweep.

θ approximates the partial top-``max_candidates`` optimistic score: a
persistent VMEM scratch buffer of ``cb·TILE ≥ max_candidates`` slots, each
holding the max over a disjoint cyclically-assigned subset of the streamed
candidates (seeded with the select floor).  The θ read (``slot_theta``)
takes the C-th largest slot value — attained by C distinct candidates,
so provably ≤ the true C-th largest streamed optimistic score: a skipped
block can never contain a candidate the top-C select stage would keep.

One planar row = one posting block (LANES = 128 postings), so the DMA
unit is a single ``[1, 128]`` row and no tile alignment of the driver's
first block is needed.  The plane is f32 whatever the stored impact
dtype: a row slice of a 16-bit HBM array is not tile-aligned on the TPU
(found by compiling for a v5e), and the widening is exact.
Grid = (n_win // BLOCK_ROWS,) walked sequentially; under ``vmap`` the
batch axis becomes the outer grid dimension and the ``j == 0`` re-init
gives every query a fresh θ.

``monotone=True`` (the impact-ordered layout, whose ``blk_max_impact`` is
a per-term suffix-max envelope — non-increasing along the block run)
additionally keeps an early-exit *cut flag* in SMEM across grid steps:
the first block whose bound fails θ proves every later block fails too
(θ only ever rises), so the rest of the term is cut without testing —
and, as always, a skipped block issues no DMA.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128  # postings per block = one planar row
BLOCK_ROWS = 8  # blocks fetched per grid step
TILE = BLOCK_ROWS * LANES


def slot_theta(bv, floor, c_sel: int):
    """θ = the C-th largest slot value of the partial top-C buffer.

    Each slot holds the max over a disjoint subset of the streamed
    candidates (or its floor seed, if no candidate ever folded there).
    The top-C slot values are attained by C *distinct* candidates (one
    per slot; a floor seed among them collapses θ to the floor, which is
    always sound), so the C-th largest slot value can never exceed the
    C-th largest streamed optimistic score: a block skipped against it
    cannot contain a candidate the top-C select stage would keep.

    This is the tightest sound threshold the slot lattice offers.  A
    plain ``min(buffer)`` (the previous rule) is badly loose at both
    ends: slots no candidate ever reaches (lanes past a ragged block's
    length, rows past a short driver's block count) pin the min at the
    floor forever, while for C ≪ 1024 streamed-heavy buffers approximate
    the stream *minimum* rather than the C-th best.  ``ref.py`` reads θ
    through this function; the kernel computes the same value by
    bisection (:func:`_kth_largest`), since Pallas TPU has no ``top_k``.
    """
    vals = jax.lax.top_k(bv.reshape(-1), c_sel)[0]
    return jnp.maximum(vals[c_sel - 1], floor)


def _kth_largest(bv, c_sel: int):
    """The ``c_sel``-th largest value of ``bv``, by bisection on bits.

    Every slot value is ≥ 0 (slots start at the non-negative floor and
    only take maxima), and the bit patterns of non-negative floats order
    like the floats (``& 0x7FFFFFFF`` maps -0.0 to +0.0), so the largest
    bit pattern ``t`` with ``count(bits >= t) >= c_sel`` is exactly the
    c_sel-th largest value — equal to ``top_k(bv, c_sel)[0][-1]``.
    """
    bits = jax.lax.bitcast_convert_type(bv, jnp.int32) & 0x7FFFFFFF

    def body(_, lh):
        lo, hi = lh
        mid = lo + (hi - lo + 1) // 2
        ok = jnp.sum((bits >= mid).astype(jnp.int32)) >= c_sel
        return jnp.where(ok, mid, lo), jnp.where(ok, hi, mid - 1)

    # 31 halvings cover every non-negative bit pattern up to +inf
    lo, _ = jax.lax.fori_loop(
        0, 31, body, (jnp.int32(0), jnp.int32(0x7F800000))
    )
    # ``lo`` is the bit pattern of an element of bv; read that element back
    # (Mosaic cannot bitcast a scalar)
    return jnp.max(jnp.where(bits == lo, bv, -jnp.inf))


def _pruned_kernel(
    start_ref,  # scalar prefetch: i32[1] driver's first block (plane row)
    ub_ref,  # SMEM f32[n_win] per-window-block optimistic upper bounds
    len_ref,  # SMEM i32[n_win] valid postings per window block
    wb_ref,  # SMEM f32[2]: (w_text, rest_ub) — the optimistic-score affine
    floor_ref,  # SMEM f32[1]: select-stage score floor
    imp_hbm,  # ANY-space impact plane f32[rows, LANES]
    out_ref,  # VMEM f32[BLOCK_ROWS, LANES] tile of optimistic scores;
    # rows of skipped blocks are -inf (the per-block scored flags)
    buf_ref,  # VMEM scratch f32[cb*BLOCK_ROWS, LANES]: partial top-C heap
    imp_s,  # VMEM scratch f32[BLOCK_ROWS, LANES]: fetched rows
    copy_sem,  # DMA semaphore for the per-block copies
    cut_ref,  # SMEM scratch i32[1]: early-exit cut flag (monotone only)
    *,
    cb: int,
    c_sel: int,
    monotone: bool,
):
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _init():
        # seed every slot with the selection floor: θ never drops below it,
        # so blocks whose bound cannot clear the floor are skipped — their
        # candidates would be dropped by the select stage regardless
        buf_ref[...] = jnp.full_like(buf_ref, floor_ref[0])
        cut_ref[0] = jnp.int32(0)

    theta = jnp.maximum(_kth_largest(buf_ref[...], c_sel), floor_ref[0])
    # under a monotone (non-increasing) bound run the first failing block
    # proves every later block fails too (θ only ever rises): once the cut
    # flag is set, the whole remainder of the term is skipped without even
    # testing its bounds — zero DMA after the cut
    cut = cut_ref[0] > 0 if monotone else False
    rows = jax.lax.broadcasted_iota(jnp.int32, (BLOCK_ROWS, LANES), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (BLOCK_ROWS, LANES), 1)
    mask = jnp.zeros((BLOCK_ROWS, LANES), dtype=bool)
    row_scored = jnp.zeros((BLOCK_ROWS, LANES), dtype=bool)
    any_scored = False
    any_fail = False
    for b in range(BLOCK_ROWS):  # static unroll over the tile's blocks
        w = j * BLOCK_ROWS + b
        sb = ub_ref[w] > theta  # -inf beyond the driver's blocks
        any_fail = jnp.logical_not(sb) | any_fail
        if monotone:
            sb = sb & jnp.logical_not(cut)
        row_scored = row_scored | (sb & (rows == b))
        mask = mask | (sb & (rows == b) & (cols < len_ref[w]))
        any_scored = sb | any_scored

        # a θ-skipped block issues NO copy: zero bytes move for it.  Its
        # scratch row keeps stale data, which is safe — everything below
        # selects through ``mask``, so garbage cannot propagate.
        @pl.when(sb)
        def _fetch(b=b, w=w):
            cp = pltpu.make_async_copy(
                imp_hbm.at[pl.ds(start_ref[0] + w, 1), :],
                imp_s.at[pl.ds(b, 1), :],
                copy_sem,
            )
            cp.start()
            cp.wait()

    @pl.when(any_scored)
    def _score():
        # the optimistic affine: every posting's best possible final score
        opt = imp_s[...] * wb_ref[0] + wb_ref[1]
        sc = jnp.where(mask, opt, 0.0)
        out_ref[...] = jnp.where(row_scored, sc, -jnp.inf)
        # cyclic top-C approximation: fold this tile into its buffer slice
        r0 = (j % cb) * BLOCK_ROWS
        sl = buf_ref[pl.ds(r0, BLOCK_ROWS), :]
        buf_ref[pl.ds(r0, BLOCK_ROWS), :] = jnp.maximum(sl, sc)

    @pl.when(jnp.logical_not(any_scored))
    def _skip():
        out_ref[...] = jnp.full_like(out_ref, -jnp.inf)

    if monotone:
        cut_ref[0] = jnp.where(any_fail | cut, 1, 0).astype(jnp.int32)


@functools.partial(
    jax.jit, static_argnames=("n_win", "max_candidates", "interpret", "monotone")
)
def text_probe_pruned_planar(
    start: jax.Array,  # i32[1] driver's first block (plane row)
    ub: jax.Array,  # f32[n_win] per-window-block bounds (-inf padded)
    lens: jax.Array,  # i32[n_win] valid postings per window block
    wb: jax.Array,  # f32[2]: (w_text, rest_ub)
    floor: jax.Array,  # f32[1] select-stage score floor
    imp_plane: jax.Array,  # f32[rows, LANES] impact plane
    n_win: int,  # window blocks; multiple of BLOCK_ROWS
    max_candidates: int,  # C of the partial top-C threshold buffer
    interpret: bool,
    monotone: bool = False,  # bounds non-increasing → early-exit cut flag
) -> tuple[jax.Array, jax.Array]:
    """Pruned driver-block walk: (opt f32[n_tiles, BLOCK_ROWS, LANES],
    scored i32[n_tiles, BLOCK_ROWS] per-block flags).

    The kernel marks a skipped block by writing its row as -inf (a
    streamed block's lane 0 is a genuine posting, so its score is
    finite); the flags are read back from that here, because a
    ``(1, BLOCK_ROWS)`` SMEM output block breaks the TPU's (8, 128)
    block rule and a whole-array one overflows SMEM at real widths.
    """
    assert n_win % BLOCK_ROWS == 0
    n_tiles = n_win // BLOCK_ROWS
    # C rounded up to whole tiles: θ is the c_sel-th largest slot value
    # of the buffer, and each slot max is attained by a distinct
    # candidate, so any buffer ≥ C slots yields a sound (under-) estimate
    cb = max(1, -(-max_candidates // TILE))
    # the select stage can keep at most the whole window; the θ read
    # must use the same effective C
    c_sel = max(1, min(max_candidates, n_win * LANES))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((n_win,), lambda j, s: (0,), memory_space=pltpu.SMEM),
            pl.BlockSpec((n_win,), lambda j, s: (0,), memory_space=pltpu.SMEM),
            pl.BlockSpec((2,), lambda j, s: (0,), memory_space=pltpu.SMEM),
            pl.BlockSpec((1,), lambda j, s: (0,), memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pl.ANY),  # impact plane
        ],
        out_specs=pl.BlockSpec((1, BLOCK_ROWS, LANES), lambda j, s: (j, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((cb * BLOCK_ROWS, LANES), jnp.float32),
            pltpu.VMEM((BLOCK_ROWS, LANES), jnp.float32),
            pltpu.SemaphoreType.DMA,
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    kernel = functools.partial(
        _pruned_kernel, cb=cb, c_sel=c_sel, monotone=monotone
    )
    raw = pl.pallas_call(
        lambda s_ref, ub_r, ln_r, wb_r, fl_r, plane, o, *rest: kernel(
            s_ref, ub_r, ln_r, wb_r, fl_r, plane, o.at[0], *rest
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_tiles, BLOCK_ROWS, LANES), jnp.float32),
        interpret=interpret,
    )(start, ub, lens, wb, floor, imp_plane)
    skipped = raw == -jnp.inf
    return jnp.where(skipped, 0.0, raw), 1 - skipped[:, :, 0].astype(jnp.int32)
