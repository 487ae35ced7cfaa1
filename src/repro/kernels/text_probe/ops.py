"""jit'd wrapper for the fused text_probe kernel.

Handles: the per-window upper bounds / lengths that drive the
in-kernel skip test, and the re-flattening of the kernel's tile outputs
into the per-position (opt, valid, streamed) contract that
``core/algorithms.text_first`` consumes.  The bound/length prologue is
shared with ``ref.py`` so the skip decisions stay bit-identical.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.text_probe.kernel import (
    BLOCK_ROWS,
    LANES,
    TILE,
    text_probe_pruned_planar,
)

# plain int (not a jnp scalar): this module is imported lazily from inside
# jit-traced code, and creating a jax array at import time would leak a tracer
INVALID = 2**31 - 1


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def window_size(max_term_blocks: int) -> int:
    """Static window-block count: max blocks of any term, whole tiles."""
    mtb = max(max_term_blocks, 1)
    return -(-mtb // BLOCK_ROWS) * BLOCK_ROWS


def window_term_bounds(
    blk_max_impact: jax.Array,  # f32[NB]
    blk_len: jax.Array,  # i32[NB]
    b0: jax.Array,  # i32 scalar: driver term's first block
    nb: jax.Array,  # i32 scalar: driver term's block count
    w_text: jax.Array,  # f32 scalar
    rest_ub: jax.Array,  # f32 scalar (≥ 0)
    n_win: int,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Shared prologue (used by ops AND ref so skip decisions stay
    bit-identical): per-window-block upper bounds ``w_text·blk_max + rest``
    (-inf past the driver's ``nb`` blocks, so they can never beat θ ≥ 0
    and move zero bytes), valid lengths, and the active-block mask —
    what an *unpruned* traversal would stream, the baseline for the
    skipped-block counters."""
    NB = blk_max_impact.shape[0]
    w = jnp.arange(n_win, dtype=jnp.int32)
    active = w < nb
    bid = jnp.clip(b0 + w, 0, NB - 1)
    ub = jnp.where(
        active,
        w_text * blk_max_impact[bid] + rest_ub,
        -jnp.inf,
    )
    lens = jnp.where(active, blk_len[bid], 0)
    return ub, lens.astype(jnp.int32), active


@functools.partial(
    jax.jit,
    static_argnames=("max_candidates", "max_term_blocks", "interpret", "monotone"),
)
def text_probe_pruned(
    imp_plane: jax.Array,  # f32[NB, LANES] impact plane (TextIndex.imp_plane)
    blk_max_impact: jax.Array,  # f32[NB]
    blk_len: jax.Array,  # i32[NB]
    b0: jax.Array,  # i32 scalar: driver term's first block
    nb: jax.Array,  # i32 scalar: driver term's block count
    w_text: jax.Array,  # f32 scalar
    rest_ub: jax.Array,  # f32 scalar: query-constant remainder bound
    floor: jax.Array | float = 0.0,  # select-stage score floor (scalar)
    max_candidates: int = 1024,  # C of the partial top-C threshold buffer
    max_term_blocks: int = 1,  # static window bound (TextIndex field)
    interpret: bool | None = None,
    monotone: bool = False,  # non-increasing bounds → early-exit cut
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """Fused probe+score+select over the driver term's posting blocks.

    Returns ``(opt f32[n_win*LANES], valid bool[n_win*LANES], streamed
    bool[n_win*LANES], blocks_scored i32, blocks_active i32)``: ``opt`` is
    each streamed posting's optimistic score (0 where skipped/invalid),
    ``valid`` marks genuine driver postings, ``streamed`` positions whose
    block was actually fetched (candidates are ``valid & streamed`` — on
    hardware the per-block DMA is simply not issued for skipped blocks),
    and the block counters feed ``text_blocks_skipped`` stats.

    ``monotone=True`` asserts the driver's bounds are non-increasing along
    its block run (layout="impact"'s suffix-max envelope): the kernel then
    early-exits the term at the first failing bound (see kernel docstring).
    """
    if interpret is None:
        interpret = _default_interpret()
    n_win = window_size(max_term_blocks)
    ub, lens, active = window_term_bounds(
        blk_max_impact, blk_len, b0, nb, w_text, rest_ub, n_win
    )
    floor_c = jnp.maximum(jnp.asarray(floor, jnp.float32), 0.0)
    wb = jnp.stack(
        [
            jnp.asarray(w_text, jnp.float32),
            jnp.asarray(rest_ub, jnp.float32),
        ]
    )
    opt, scored = text_probe_pruned_planar(
        jnp.asarray(b0, jnp.int32).reshape(1),
        ub,
        lens,
        wb,
        floor_c.reshape(1),
        imp_plane,
        n_win=n_win,
        max_candidates=max_candidates,
        interpret=interpret,
        monotone=monotone,
    )
    scored_blk = scored.reshape(n_win) > 0
    lane_ok = (
        jnp.arange(LANES, dtype=jnp.int32)[None, :] < lens[:, None]
    )  # [n_win, LANES]
    valid = active[:, None] & lane_ok
    streamed = jnp.repeat(scored_blk, LANES)
    blocks_scored = jnp.sum((scored_blk & active).astype(jnp.int32))
    blocks_active = jnp.sum(active.astype(jnp.int32))
    return (
        opt.reshape(n_win * LANES),
        valid.reshape(n_win * LANES),
        streamed,
        blocks_scored,
        blocks_active,
    )


__all__ = [
    "BLOCK_ROWS",
    "LANES",
    "TILE",
    "INVALID",
    "text_probe_pruned",
    "window_size",
    "window_term_bounds",
]
