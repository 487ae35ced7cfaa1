"""Host side of the tile-interval kernel: the per-patch candidate CSR in,
the interval table out.  Same contract as
:func:`repro.core.spatial_index.tile_intervals_np`, its reference."""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import jax
import numpy as np

from repro.core.geometry import HOST_THREADS
from repro.kernels.tile_intervals.kernel import CHUNK, LANES, ROWS, tile_gaps_planar

INVALID = 2**31 - 1


def patch_candidates_np(x0, y0, x1, y1, grid: int):
    """Toe prints overlapping each (ROWS × LANES)-tile patch, IDs ascending.

    ``x0..y1`` are the toe prints' inclusive tile bounds (ID = index).
    Returns ``(chunks i32[n_patches], counts i32[n_patches], ids i32[C],
    packed i32[C], n_py, n_px)``: patch ``p = py * n_px + px`` owns
    ``ids[chunks[p] * CHUNK :][: counts[p]]`` — each list starts on a
    CHUNK boundary and is zero-padded to whole chunks, since the kernel
    copies whole, aligned chunks — and ``packed`` holds each candidate's
    bounds clipped to the patch, in patch-local tiles
    (``x0 | x1 << 7 | y0 << 14 | y1 << 17``).
    """
    n_py = -(-grid // ROWS)
    n_px = -(-grid // LANES)
    live = (x1 >= x0) & (y1 >= y0)
    py0, py1 = y0 // ROWS, y1 // ROWS
    px0, px1 = x0 // LANES, x1 // LANES

    def patch_row(py):
        ids = np.flatnonzero(live & (py0 <= py) & (py1 >= py))
        nx = px1[ids] - px0[ids] + 1
        off = np.cumsum(nx) - nx
        px = np.repeat(px0[ids] - off, nx) + np.arange(int(nx.sum()))
        order = np.argsort(px.astype(np.uint16), kind="stable")
        px, tid = px[order], np.repeat(ids, nx)[order]
        lx0 = np.clip(x0[tid] - px * LANES, 0, LANES - 1)
        lx1 = np.clip(x1[tid] - px * LANES, 0, LANES - 1)
        ly0 = np.clip(y0[tid] - py * ROWS, 0, ROWS - 1)
        ly1 = np.clip(y1[tid] - py * ROWS, 0, ROWS - 1)
        packed = lx0 | (lx1 << 7) | (ly0 << 14) | (ly1 << 17)
        counts = np.bincount(px, minlength=n_px)
        # scatter each patch's list to its chunk-aligned slot
        nch = -(-counts // CHUNK)
        base = (np.cumsum(nch) - nch) * CHUNK
        dst = np.repeat(base - (np.cumsum(counts) - counts), counts) + np.arange(
            len(tid)
        )
        out_t = np.zeros((int(nch.sum()) * CHUNK,), np.int32)
        out_p = np.zeros_like(out_t)
        out_t[dst] = tid
        out_p[dst] = packed
        return out_t, out_p, counts, nch

    with ThreadPoolExecutor(HOST_THREADS) as pool:
        rows = list(pool.map(patch_row, range(n_py)))
    counts = np.concatenate([r[2] for r in rows]).astype(np.int32)
    nch = np.concatenate([r[3] for r in rows])
    chunks = (np.cumsum(nch) - nch).astype(np.int32)
    ids = np.concatenate([r[0] for r in rows] + [np.zeros((CHUNK,), np.int32)])
    packed = np.concatenate([r[1] for r in rows] + [np.zeros((CHUNK,), np.int32)])
    return chunks, counts, ids, packed, n_py, n_px


def tile_intervals(
    x0, y0, x1, y1, grid: int, m: int, interpret: bool | None = None
) -> tuple[np.ndarray, np.ndarray]:
    """``(tile_starts, tile_ends)`` i32[grid², m] from inclusive tile
    bounds of the Morton-ordered toe prints, via the Pallas kernel
    (interpreted off the TPU)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    k = max(m - 1, 1)
    chunks, counts, ids, packed, n_py, n_px = patch_candidates_np(
        x0, y0, x1, y1, grid
    )
    # on the default backend's first device even when the caller stages
    # arrays elsewhere (a host build under jax.default_device(cpu))
    args = jax.device_put((chunks, counts, ids, packed), jax.devices()[0])
    out = np.asarray(
        tile_gaps_planar(*args, n_py, n_px, k, interpret=interpret)
    ).astype(np.int64)
    out = out[:, :grid, :grid].reshape(2 + 2 * k, grid * grid)
    lo, hi = out[0], out[1]
    gaps, lefts = out[2 : 2 + k], out[2 + k :]
    cut = (gaps > 1) & (np.arange(k)[:, None] < m - 1)
    left = np.where(cut, lefts, np.iinfo(np.int64).max)
    order = np.argsort(left, axis=0)  # cuts in ID order
    left = np.take_along_axis(left, order, axis=0)
    right = np.take_along_axis(np.where(cut, lefts + gaps, 0), order, axis=0)
    has = np.take_along_axis(cut, order, axis=0)
    starts = np.full((grid * grid, m), INVALID, np.int64)
    ends = np.full((grid * grid, m), INVALID, np.int64)
    starts[:, 0] = lo
    if m > 1:
        starts[:, 1:] = np.where(has, right, INVALID).T[:, : m - 1]
        ends[:, : m - 1] = np.where(has, left + 1, INVALID).T[:, : m - 1]
    ends[np.arange(grid * grid), has.sum(axis=0)] = hi + 1
    empty = lo < 0
    starts[empty] = INVALID
    ends[empty] = INVALID
    return starts.astype(np.int32), ends.astype(np.int32)
