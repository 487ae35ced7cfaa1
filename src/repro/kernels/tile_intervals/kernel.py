"""Pallas TPU kernel: the spatial index's tile → toe-print interval table.

For every tile of the ``G×G`` grid the spatial index stores ≤ ``m``
intervals of toe-print IDs covering the toe prints that intersect it: the
tile's sorted ID set cut at its ``m − 1`` largest gaps.  On the host that
costs one step per (tile, toe print) pair — about 1.6·10^11 of them for
one chip's share of geoweb at grid 1024 — so at that size the table is
built here instead.

The grid is cut into patches of one vector tile (8 rows × 128 columns of
tiles).  Each grid step owns one patch and walks the IDs of the toe prints
overlapping it in ascending order (a host-built CSR, copied to SMEM in
chunks), keeping per tile, in vector registers: the first and the latest
covering ID and the ``m − 1`` largest gaps between consecutive covering
IDs with their left ends.  A gap enters the list only if it is strictly
larger than an entry, so equal gaps keep their first occurrence.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROWS = 8  # tile rows per patch
LANES = 128  # tile columns per patch
CHUNK = 1024  # candidates copied to SMEM at a time


def unpack_bounds(pk):
    """Patch-local (x0, x1, y0, y1) of a packed candidate (see ops)."""
    return pk & 127, (pk >> 7) & 127, (pk >> 14) & 7, (pk >> 17) & 7


def _kernel(
    chunk_ref, count_ref, ids_hbm, pk_hbm, out_ref, ids_s, pk_s, sem, *, n_px, k
):
    p = pl.program_id(0) * n_px + pl.program_id(1)
    n = count_ref[p]
    row = jax.lax.broadcasted_iota(jnp.int32, (ROWS, LANES), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (ROWS, LANES), 1)
    none = jnp.full((ROWS, LANES), -1, jnp.int32)

    def step(j, st):
        lo, prev, gaps, lefts = st
        tid = ids_s[j]
        x0, x1, y0, y1 = unpack_bounds(pk_s[j])
        cov = (col >= x0) & (col <= x1) & (row >= y0) & (row <= y1)
        g = tid - prev
        cand = cov & (prev >= 0)
        # insert (g, prev) after every entry with gap >= g
        new_g, new_l = [], []
        for s in range(k):
            beat = cand & (g > gaps[s])
            if s == 0:
                new_g.append(jnp.where(beat, g, gaps[0]))
                new_l.append(jnp.where(beat, prev, lefts[0]))
            else:
                shift = cand & (g > gaps[s - 1])
                new_g.append(
                    jnp.where(shift, gaps[s - 1], jnp.where(beat, g, gaps[s]))
                )
                new_l.append(
                    jnp.where(shift, lefts[s - 1], jnp.where(beat, prev, lefts[s]))
                )
        lo = jnp.where(cov & (lo < 0), tid, lo)
        prev = jnp.where(cov, tid, prev)
        return lo, prev, tuple(new_g), tuple(new_l)

    def chunk(c, st):
        # 1-D HBM arrays are tiled by CHUNK elements: copies start on one
        start = pl.multiple_of((chunk_ref[p] + c) * CHUNK, CHUNK)
        for src, dst in ((ids_hbm, ids_s), (pk_hbm, pk_s)):
            cp = pltpu.make_async_copy(src.at[pl.ds(start, CHUNK)], dst, sem)
            cp.start()
            cp.wait()
        return jax.lax.fori_loop(0, jnp.minimum(n - c * CHUNK, CHUNK), step, st)

    st = (none, none, (none,) * k, (none,) * k)
    lo, prev, gaps, lefts = jax.lax.fori_loop(0, (n + CHUNK - 1) // CHUNK, chunk, st)
    out_ref[0] = lo
    out_ref[1] = prev
    for s in range(k):
        out_ref[2 + s] = gaps[s]
        out_ref[2 + k + s] = lefts[s]


@functools.partial(jax.jit, static_argnames=("n_py", "n_px", "k", "interpret"))
def tile_gaps_planar(
    chunks: jax.Array,  # i32[n_py * n_px] first CHUNK of each patch's list
    counts: jax.Array,  # i32[n_py * n_px] candidates per patch
    ids: jax.Array,  # i32[C] candidate toe-print IDs, per patch ascending
    packed: jax.Array,  # i32[C] patch-local bounds (see ops)
    n_py: int,
    n_px: int,
    k: int,  # gaps kept per tile (m - 1, at least 1)
    interpret: bool,
) -> jax.Array:
    """Per tile: (first ID, last ID, k largest gaps, their left IDs) —
    i32[2 + 2k, n_py * ROWS, n_px * LANES]; -1 where none."""
    fields = 2 + 2 * k
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_py, n_px),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 2,
        out_specs=pl.BlockSpec(
            (fields, ROWS, LANES), lambda i, j, ch, cn: (0, i, j)
        ),
        scratch_shapes=[
            pltpu.SMEM((CHUNK,), jnp.int32),
            pltpu.SMEM((CHUNK,), jnp.int32),
            pltpu.SemaphoreType.DMA,
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, n_px=n_px, k=k),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            (fields, n_py * ROWS, n_px * LANES), jnp.int32
        ),
        interpret=interpret,
    )(chunks, counts, ids, packed)
