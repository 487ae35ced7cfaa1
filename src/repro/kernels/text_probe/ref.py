"""Pure-jnp oracle for the fused text_probe kernel.

``text_probe_pruned_ref`` mirrors ``ops.text_probe_pruned`` operation for
operation — same window bounds, same one-θ-per-tile skip rule, same cyclic
partial top-C buffer, same astype-then-affine decode of the stored impact
plane — so the skip *decisions* agree with the Pallas kernel exactly, not
just approximately.  It is both the kernel's test oracle and the traversal
behind ``text_first(prune=True, fused=False)``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.partial(
    jax.jit, static_argnames=("max_candidates", "max_term_blocks", "monotone")
)
def text_probe_pruned_ref(
    imp_plane: jax.Array,  # f32[NB, LANES] impact plane (TextIndex.imp_plane)
    blk_max_impact: jax.Array,  # f32[NB]
    blk_len: jax.Array,  # i32[NB]
    b0: jax.Array,  # i32 scalar: driver term's first block
    nb: jax.Array,  # i32 scalar: driver term's block count
    w_text: jax.Array,  # f32 scalar
    rest_ub: jax.Array,  # f32 scalar
    floor: jax.Array | float = 0.0,
    max_candidates: int = 1024,
    max_term_blocks: int = 1,
    monotone: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """Block-max pruned text-probe oracle; same contract as
    ``ops.text_probe_pruned`` (opt, valid, streamed, blocks_scored,
    blocks_active).  ``monotone=True`` carries the kernel's early-exit cut
    flag through the scan — same per-tile cut semantics (the flag set by
    tile t masks tiles > t; within a tile a failing bound implies every
    later bound fails too, since bounds are non-increasing), so skip
    decisions stay bit-identical to the kernel."""
    from repro.kernels.text_probe.kernel import (
        BLOCK_ROWS,
        LANES,
        TILE,
        slot_theta,
    )
    from repro.kernels.text_probe.ops import window_size, window_term_bounds

    n_win = window_size(max_term_blocks)
    n_tiles = n_win // BLOCK_ROWS
    cb = max(1, -(-max_candidates // TILE))
    c_sel = max(1, min(max_candidates, n_win * LANES))

    ub, lens, active = window_term_bounds(
        blk_max_impact, blk_len, b0, nb, w_text, rest_ub, n_win
    )
    floor_c = jnp.maximum(jnp.asarray(floor, jnp.float32).reshape(()), 0.0)

    # all window optimistic scores on the kernel's block lattice, kernel
    # decode order (stored dtype → astype f32 → × w_text + rest_ub)
    NB = imp_plane.shape[0]
    bid = jnp.clip(b0 + jnp.arange(n_win, dtype=jnp.int32), 0, NB - 1)
    opt_all = (
        imp_plane[bid].astype(jnp.float32)
        * jnp.asarray(w_text, jnp.float32)
        + jnp.asarray(rest_ub, jnp.float32)
    )  # [n_win, LANES]
    lane_ok = jnp.arange(LANES, dtype=jnp.int32)[None, :] < lens[:, None]

    # sequential tile walk: one θ per tile (all BLOCK_ROWS decisions of a
    # tile see the θ from before any of the tile's folds — matching the
    # kernel, which reads min(buf) once per grid step), cyclic fold after
    flat_ub = ub.reshape(n_tiles, BLOCK_ROWS)
    flat_opt = opt_all.reshape(n_tiles, BLOCK_ROWS, LANES)
    flat_ok = lane_ok.reshape(n_tiles, BLOCK_ROWS, LANES)
    slots = jnp.arange(n_tiles, dtype=jnp.int32) % cb

    def step(carry, xs):
        buf, cut = carry
        ub_t, opt_t, ok_t, slot = xs
        # same C-th-largest-slot θ read as the kernel (slot_theta)
        theta = slot_theta(buf, floor_c, c_sel)
        raw = ub_t > theta  # [BLOCK_ROWS]
        scored = raw & jnp.logical_not(cut) if monotone else raw
        sc = jnp.where(scored[:, None] & ok_t, opt_t, 0.0)
        buf = buf.at[slot].set(jnp.maximum(buf[slot], sc))
        if monotone:
            cut = cut | jnp.any(jnp.logical_not(raw))
        return (buf, cut), (scored, sc)

    _, (scored, sc) = jax.lax.scan(
        step,
        (
            jnp.full((cb, BLOCK_ROWS, LANES), floor_c, jnp.float32),
            jnp.zeros((), bool),
        ),
        (flat_ub, flat_opt, flat_ok, slots),
    )
    scored_blk = scored.reshape(n_win)
    valid = active[:, None] & lane_ok
    streamed = jnp.repeat(scored_blk, LANES)
    blocks_scored = jnp.sum((scored_blk & active).astype(jnp.int32))
    blocks_active = jnp.sum(active.astype(jnp.int32))
    return (
        sc.reshape(n_win * LANES),
        valid.reshape(n_win * LANES),
        streamed,
        blocks_scored,
        blocks_active,
    )
