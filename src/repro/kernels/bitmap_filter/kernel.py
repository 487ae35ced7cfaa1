"""Pallas TPU kernel: block-bitmap conjunction (AND + popcount).

The TPU-idiomatic replacement for DAAT list intersection on frequent terms
(DESIGN.md §2, beyond-paper feature 3): given the packed u32 bitmaps of the
d query terms over ``ceil(N/32)`` words, compute

    anded[w]  = AND_i bitmaps[i, w]           (documents containing ALL terms)
    counts[w] = popcount(anded[w])            (survivor count per word)

Layout: bitmaps arrive as u32[d, rows, 128] (ops.py pads/reshapes); the term
dimension d is small and static → unrolled; each grid step ANDs a
[BLOCK_ROWS, 128] tile per term and popcounts with the SWAR bit trick —
pure VPU integer ops, no MXU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
BLOCK_ROWS = 8


def _popcount_u32(v: jax.Array) -> jax.Array:
    """SWAR popcount on uint32 lanes."""
    v = v - ((v >> jnp.uint32(1)) & jnp.uint32(0x55555555))
    v = (v & jnp.uint32(0x33333333)) + ((v >> jnp.uint32(2)) & jnp.uint32(0x33333333))
    v = (v + (v >> jnp.uint32(4))) & jnp.uint32(0x0F0F0F0F)
    return ((v * jnp.uint32(0x01010101)) >> jnp.uint32(24)).astype(jnp.int32)


def _make_kernel(d: int):
    def kernel(bm_ref, anded_ref, count_ref):
        acc = bm_ref[0]
        for i in range(1, d):  # static unroll over query terms
            acc = acc & bm_ref[i]
        anded_ref[...] = acc
        count_ref[...] = _popcount_u32(acc)

    return kernel


@functools.partial(jax.jit, static_argnames=("interpret",))
def bitmap_and_popcount_planar(
    bitmaps: jax.Array,  # u32[d, rows, 128]
    interpret: bool,
) -> tuple[jax.Array, jax.Array]:
    d, rows, lanes = bitmaps.shape
    assert lanes == LANES and rows % BLOCK_ROWS == 0
    grid = (rows // BLOCK_ROWS,)
    out_plane = pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0))
    return pl.pallas_call(
        _make_kernel(d),
        grid=grid,
        in_specs=[pl.BlockSpec((d, BLOCK_ROWS, LANES), lambda i: (0, i, 0))],
        out_specs=(out_plane, out_plane),
        out_shape=(
            jax.ShapeDtypeStruct((rows, LANES), jnp.uint32),
            jax.ShapeDtypeStruct((rows, LANES), jnp.int32),
        ),
        interpret=interpret,
    )(bitmaps)
