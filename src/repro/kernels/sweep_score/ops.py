"""jit'd wrappers for the fused sweep_score kernels.

Handles: planarization of the toe-print store, block alignment of sweep
starts (the kernels DMA tile-aligned blocks; we align the window down and
enlarge the in-kernel budget by one tile so the true [start, end) range is
always covered), masking back to exact sweep bounds, and — for the pruned
variant — computing the per-tile block-max upper bounds that drive the
in-kernel skip test from the ``SpatialIndex`` block columns.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.spatial_index import SCALE_BLOCK
from repro.kernels.sweep_score.kernel import (
    LANES,
    PLAIN_TILE,
    Q_MAX,
    TILE,
    sweep_score_planar,
    sweep_score_pruned_planar,
)

# plain int (not a jnp scalar): this module is imported lazily from inside
# jit-traced code, and creating a jax array at import time would leak a tracer
INVALID = 2**31 - 1


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _planarize(tp_rects, tp_amps, tp_amp_scale, budget, tile):
    """Planar [rows, 128] views of the store in its STORED dtype, padded
    for alignment slop, plus a per-row f32 amp-scale plane [rows, 1].

    One planar row is exactly one amp-scale block (SCALE_BLOCK == LANES);
    stores without a scale column get an all-ones plane, and ×1.0 keeps
    the uncompressed path bit-identical.  Callers adapt the planes to
    their kernel (:func:`_f16_as_bits`).

    Returns (planes, pad_budget): 6 planes (x0, y0, x1, y1, amp, scale)
    and the per-sweep in-kernel budget (the requested budget rounded up to
    whole tiles plus one tile of alignment slop).
    """
    assert SCALE_BLOCK == LANES
    T = tp_rects.shape[0]
    pad_budget = (budget + tile - 1) // tile * tile + tile
    Tp = (T + tile - 1) // tile * tile + pad_budget  # tail room for last sweep
    rows = Tp // LANES

    def plane(v, fill):
        v = jnp.pad(v, (0, Tp - T), constant_values=fill)
        return v.reshape(rows, LANES)

    ns = tp_amp_scale.shape[0] if tp_amp_scale is not None else 0
    scale = jnp.ones((rows, 1), jnp.float32)
    if ns:
        scale = scale.at[:ns, 0].set(tp_amp_scale.astype(jnp.float32))
    planes = (
        plane(tp_rects[:, 0], 1.0),  # empty-rect padding
        plane(tp_rects[:, 1], 1.0),
        plane(tp_rects[:, 2], 0.0),
        plane(tp_rects[:, 3], 0.0),
        plane(tp_amps, 0),
        scale,
    )
    return planes, pad_budget


def pruned_store_planes(tp_rects, tp_amps, tp_amp_scale, block_size: int):
    """The pruned kernel's copy of the toe-print store, i32[P, rows, LANES].

    The kernel copies single 128-toe-print rows, which must be 32-bit on
    the TPU, so the store is re-laid once (with the index, as
    ``SpatialIndex.tp_planes``): f32 coordinates as their 4 bit-pattern
    planes (P = 5), or f16 coordinates packed two per word as
    ``x0 | y0 << 16`` and ``x1 | y1 << 16`` (P = 3); the last plane is the
    amp decoded to f32 (× the int8 row scale).  Rows cover the store
    rounded up to whole metadata blocks — only blocks overlapping a sweep
    are ever copied.  Every value decodes exactly to the kernels' f32.
    """
    T = tp_rects.shape[0]
    rows = -(-T // block_size) * (block_size // LANES)

    def plane(v, fill):
        return jnp.pad(v, (0, rows * LANES - T), constant_values=fill).reshape(
            rows, LANES
        )

    amp = tp_amps.astype(jnp.float32)
    if tp_amp_scale is not None and tp_amp_scale.shape[0]:
        amp = amp * tp_amp_scale[jnp.arange(T) // SCALE_BLOCK]
    rect = [plane(tp_rects[:, i], f) for i, f in enumerate((1.0, 1.0, 0.0, 0.0))]
    if tp_rects.dtype == jnp.float16:
        bits = [
            jax.lax.bitcast_convert_type(v, jnp.uint16).astype(jnp.int32)
            for v in rect
        ]
        words = [bits[0] | (bits[1] << 16), bits[2] | (bits[3] << 16)]
    else:
        words = [
            jax.lax.bitcast_convert_type(v.astype(jnp.float32), jnp.int32)
            for v in rect
        ]
    amp_bits = jax.lax.bitcast_convert_type(plane(amp, 0.0), jnp.int32)
    return jnp.stack([*words, amp_bits])


def _f16_as_bits(v):
    """f16 planes as their int16 bit patterns: the v5e cannot load f16
    vectors, so the unpruned kernel decodes the bits in-register."""
    if v.dtype == jnp.float16:
        return jax.lax.bitcast_convert_type(v, jnp.int16)
    return v


def _pad_query(q_rects, q_amps):
    Q = q_rects.shape[0]
    assert Q <= Q_MAX
    qr = jnp.zeros((Q_MAX, 4), jnp.float32).at[:Q].set(q_rects.astype(jnp.float32))
    qa = jnp.zeros((Q_MAX,), jnp.float32).at[:Q].set(q_amps.astype(jnp.float32))
    return qr, qa


def sweep_window_offsets(sweep_starts, sweep_ends, T):
    """Shared pruned-sweep window prologue (used by ops AND ref so their
    skip decisions stay bit-identical): INVALID-safe starts, TILE-aligned
    window origins (in elements and TILE units), and the exact candidate
    [start, end) bounds clamped to the store."""
    safe = jnp.where(sweep_starts == INVALID, 0, sweep_starts)
    aligned = (safe // TILE) * TILE
    block_starts = (aligned // TILE).astype(jnp.int32)
    ends = jnp.where(sweep_starts == INVALID, 0, jnp.minimum(sweep_ends, jnp.int32(T)))
    bounds = jnp.stack([safe, ends], axis=1)
    return safe, aligned, block_starts, bounds


def rewindow_outputs(
    flat, scored, safe, aligned, sweep_starts, sweep_ends, T, budget, block_size
):
    """Shared pruned-sweep epilogue: re-window the padded per-tile outputs
    to exactly [start, start+budget), rebuild the valid mask, and gather
    the per-position streamed (block-scored) mask."""
    offs = safe - aligned  # [k] in [0, TILE)
    idx = offs[:, None] + jnp.arange(budget, dtype=jnp.int32)[None, :]
    scores = jnp.take_along_axis(flat, idx, axis=1)
    pos = safe[:, None] + jnp.arange(budget, dtype=jnp.int32)[None, :]
    valid = (
        (sweep_starts[:, None] != INVALID)
        & (pos >= sweep_starts[:, None])
        & (pos < sweep_ends[:, None])
        & (pos < T)
    )
    streamed = jnp.take_along_axis(scored.astype(bool), idx // block_size, axis=1)
    return jnp.where(valid & streamed, scores, 0.0), valid, streamed


def block_upper_bounds(
    blk_mbr: jax.Array,  # f32[NB, 4]
    blk_max_amp: jax.Array,  # f32[NB]
    blk_max_mass: jax.Array,  # f32[NB]
    q_rects: jax.Array,  # [Q, 4]
    q_amps: jax.Array,  # [Q]
) -> jax.Array:
    """Safe per-block upper bound on any toe print's partial geo score.

    ``score_t = amp_t * Σ_q area(t ∩ q) · amp_q`` is bounded by both
    ``blk_max_amp · Σ_q area(blk_mbr ∩ q) · amp_q`` (every toe print lies
    inside the block MBR) and ``blk_max_mass · Σ_q amp_q`` (the
    intersection never exceeds the toe print's own area).  Returns the
    min of the two, f32[NB]; exactly 0 for blocks disjoint from the query.
    """
    qr = q_rects.astype(jnp.float32)
    qa = q_amps.astype(jnp.float32)
    w = jnp.maximum(
        jnp.minimum(blk_mbr[:, None, 2], qr[None, :, 2])
        - jnp.maximum(blk_mbr[:, None, 0], qr[None, :, 0]),
        0.0,
    )
    h = jnp.maximum(
        jnp.minimum(blk_mbr[:, None, 3], qr[None, :, 3])
        - jnp.maximum(blk_mbr[:, None, 1], qr[None, :, 1]),
        0.0,
    )
    bound_mbr = blk_max_amp * jnp.sum(w * h * qa[None, :], axis=1)
    bound_mass = blk_max_mass * jnp.sum(qa)
    return jnp.minimum(bound_mbr, bound_mass)


def window_block_bounds(
    ub_blocks: jax.Array,  # f32[NB] per-metadata-block bounds
    block_starts: jax.Array,  # i32[k] aligned sweep starts in TILE units
    bounds: jax.Array,  # i32[k, 2] exact [start, end) element offsets
    n_tiles: int,
    block_size: int,
) -> tuple[jax.Array, jax.Array]:
    """Per (sweep, window-block) upper bound and overlap mask, both
    f32/bool[k, n_tiles * (TILE // block_size)].

    The bound is zeroed for blocks with no overlap with the sweep's exact
    [start, end) range (they hold no candidates, so scoring them could
    only pollute the θ buffer).  ``overlap`` marks the blocks an
    *unpruned* sweep would stream — the baseline for the skipped-block
    counters."""
    nb = ub_blocks.shape[0]
    bpt = TILE // block_size
    w = jnp.arange(n_tiles * bpt, dtype=jnp.int32)
    b0 = (
        block_starts[:, None] * bpt + w[None, :]
    )  # metadata-block id per window slot
    ub = jnp.where(b0 < nb, ub_blocks[jnp.clip(b0, 0, nb - 1)], 0.0)
    e0 = b0 * block_size  # element offset of the block
    overlap = (e0 + block_size > bounds[:, None, 0]) & (e0 < bounds[:, None, 1])
    return jnp.where(overlap, ub, 0.0), overlap


@functools.partial(jax.jit, static_argnames=("budget", "interpret"))
def sweep_score(
    tp_rects: jax.Array,  # [T, 4] toe-print store (any float dtype)
    tp_amps: jax.Array,  # [T]
    sweep_starts: jax.Array,  # i32[k] element offsets (INVALID padded)
    sweep_ends: jax.Array,  # i32[k]
    q_rects: jax.Array,  # [Q, 4], Q <= Q_MAX
    q_amps: jax.Array,  # [Q]
    budget: int,
    interpret: bool | None = None,
    tp_amp_scale: jax.Array | None = None,  # f32[ceil(T/SCALE_BLOCK)] (int8 store)
) -> tuple[jax.Array, jax.Array]:
    """Fused fetch+score: (scores f32[k, budget], valid bool[k, budget])."""
    if interpret is None:
        interpret = _default_interpret()
    T = tp_rects.shape[0]
    k = sweep_starts.shape[0]
    qr, qa = _pad_query(q_rects, q_amps)
    planes, pad_budget = _planarize(
        tp_rects, tp_amps, tp_amp_scale, budget, PLAIN_TILE
    )
    x0, y0, x1, y1, am, sc = (_f16_as_bits(p) for p in planes)

    safe = jnp.where(sweep_starts == INVALID, 0, sweep_starts)
    aligned = (safe // PLAIN_TILE) * PLAIN_TILE  # align down to tile
    block_starts = (aligned // PLAIN_TILE).astype(jnp.int32)  # tile units

    out = sweep_score_planar(
        block_starts,
        qr,
        qa,
        x0,
        y0,
        x1,
        y1,
        am,
        sc,
        n_sweeps=k,
        budget=pad_budget,
        interpret=interpret,
    )  # [k, pad_budget/LANES, LANES]
    flat = out.reshape(k, pad_budget)
    # re-window to exactly [start, start+budget) and mask to [start, end)
    offs = safe - aligned  # [k] in [0, PLAIN_TILE)
    idx = offs[:, None] + jnp.arange(budget, dtype=jnp.int32)[None, :]
    scores = jnp.take_along_axis(flat, idx, axis=1)
    pos = safe[:, None] + jnp.arange(budget, dtype=jnp.int32)[None, :]
    valid = (
        (sweep_starts[:, None] != INVALID)
        & (pos >= sweep_starts[:, None])
        & (pos < sweep_ends[:, None])
        & (pos < T)
    )
    return jnp.where(valid, scores, 0.0), valid


@functools.partial(
    jax.jit, static_argnames=("budget", "max_candidates", "block_size", "interpret")
)
def sweep_score_pruned(
    tp_rects: jax.Array,  # [T, 4] toe-print store (any float dtype)
    tp_amps: jax.Array,  # [T]
    blk_mbr: jax.Array,  # f32[NB, 4] block-max metadata columns
    blk_max_amp: jax.Array,  # f32[NB]
    blk_max_mass: jax.Array,  # f32[NB]
    sweep_starts: jax.Array,  # i32[k] element offsets (INVALID padded)
    sweep_ends: jax.Array,  # i32[k]
    q_rects: jax.Array,  # [Q, 4], Q <= Q_MAX
    q_amps: jax.Array,  # [Q]
    budget: int,
    max_candidates: int,
    block_size: int,
    floor: jax.Array | float = 0.0,  # select-stage score floor (scalar)
    interpret: bool | None = None,
    tp_amp_scale: jax.Array | None = None,  # f32[ceil(T/SCALE_BLOCK)] (int8 store)
    planes: jax.Array | None = None,  # SpatialIndex.tp_planes of this store
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """Fused fetch+score+select with block-max pruning.

    The kernel reads the store through ``planes``, the index's resident
    ``tp_planes`` (:func:`pruned_store_planes`).  Called without them, as
    the kernel tests call it with bare store columns, it builds them in
    the call — a pass over the whole store.

    Returns ``(scores f32[k, budget], valid bool[k, budget], streamed
    bool[k, budget], blocks_scored i32, blocks_active i32)``: ``streamed``
    marks window positions whose metadata block was actually scored (the
    pruned path's streamed-bytes accounting — on hardware the per-block
    DMA is simply not issued for skipped blocks), candidates are
    ``valid & streamed``, and the block counters feed the
    ``blocks_skipped`` stats (``blocks_active`` counts blocks overlapping
    a live [start, end) range — what an unpruned sweep would stream).
    """
    if interpret is None:
        interpret = _default_interpret()
    T = tp_rects.shape[0]
    k = sweep_starts.shape[0]
    bpt = TILE // block_size
    qr, qa = _pad_query(q_rects, q_amps)
    if planes is None:
        planes = pruned_store_planes(tp_rects, tp_amps, tp_amp_scale, block_size)
    # whole tiles plus one tile of alignment slop
    pad_budget = -(-budget // TILE) * TILE + TILE
    n_tiles = pad_budget // TILE

    safe, aligned, block_starts, bounds = sweep_window_offsets(
        sweep_starts, sweep_ends, T
    )
    ub_blocks = block_upper_bounds(blk_mbr, blk_max_amp, blk_max_mass, q_rects, q_amps)
    win_ub, overlap = window_block_bounds(
        ub_blocks, block_starts, bounds, n_tiles, block_size
    )

    out, scored = sweep_score_pruned_planar(
        block_starts,
        bounds.astype(jnp.int32),
        jnp.maximum(jnp.asarray(floor, jnp.float32), 0.0).reshape(1),
        win_ub,
        qr,
        qa,
        planes,
        n_sweeps=k,
        budget=pad_budget,
        max_candidates=max_candidates,
        bpt=bpt,
        interpret=interpret,
    )
    flat = out.reshape(k, pad_budget)
    scores, valid, streamed = rewindow_outputs(
        flat, scored, safe, aligned, sweep_starts, sweep_ends, T, budget, block_size
    )
    blocks_scored = jnp.sum((scored > 0) & overlap)
    blocks_active = jnp.sum(overlap)
    return (
        scores,
        valid,
        streamed,
        blocks_scored.astype(jnp.int32),
        blocks_active.astype(jnp.int32),
    )
