"""Rectangle and space-filling-curve geometry for geo query processing.

World coordinates live in the unit square ``[0, 1) x [0, 1)``.  A rectangle
is a length-4 vector ``(x0, y0, x1, y1)`` with ``x0 <= x1``, ``y0 <= y1``.
Degenerate/empty rectangles are encoded with ``x1 < x0`` (e.g. padding).

Everything here has two flavors:

* ``jnp`` functions — jit-safe, used inside query pipelines.
* ``*_np`` functions — numpy, used at index-build time (host side).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

EMPTY_RECT = np.array([1.0, 1.0, 0.0, 0.0], dtype=np.float32)  # x1 < x0 => empty

# worker threads of the vectorised host builders (numpy releases the GIL in
# their kernels): fixed, so their temporaries stay bounded on a host that
# reports hundreds of cores
HOST_THREADS = 8


# ---------------------------------------------------------------------------
# Rectangle math (jit-safe)
# ---------------------------------------------------------------------------

def rect_area(r: jax.Array) -> jax.Array:
    """Area of rectangles ``r[..., 4]``; empty rects give 0."""
    w = jnp.maximum(r[..., 2] - r[..., 0], 0.0)
    h = jnp.maximum(r[..., 3] - r[..., 1], 0.0)
    return w * h


def rect_intersection_area(a: jax.Array, b: jax.Array) -> jax.Array:
    """Intersection area of broadcast rect arrays ``a[..., 4]``, ``b[..., 4]``."""
    x0 = jnp.maximum(a[..., 0], b[..., 0])
    y0 = jnp.maximum(a[..., 1], b[..., 1])
    x1 = jnp.minimum(a[..., 2], b[..., 2])
    y1 = jnp.minimum(a[..., 3], b[..., 3])
    return jnp.maximum(x1 - x0, 0.0) * jnp.maximum(y1 - y0, 0.0)


def rects_intersect(a: jax.Array, b: jax.Array) -> jax.Array:
    """Boolean: do rects overlap (with positive or zero-width touching area)?"""
    return (
        (jnp.maximum(a[..., 0], b[..., 0]) <= jnp.minimum(a[..., 2], b[..., 2]))
        & (jnp.maximum(a[..., 1], b[..., 1]) <= jnp.minimum(a[..., 3], b[..., 3]))
    )


def rect_union_bound(a: jax.Array, b: jax.Array) -> jax.Array:
    """MBR of two rects (broadcasting)."""
    return jnp.stack(
        [
            jnp.minimum(a[..., 0], b[..., 0]),
            jnp.minimum(a[..., 1], b[..., 1]),
            jnp.maximum(a[..., 2], b[..., 2]),
            jnp.maximum(a[..., 3], b[..., 3]),
        ],
        axis=-1,
    )


# ---------------------------------------------------------------------------
# Morton (Z-order) codes
# ---------------------------------------------------------------------------

def _part1by1_u32(v):
    """Spread the low 16 bits of v over even bit positions (u32 math)."""
    v = v & 0x0000FFFF
    v = (v | (v << 8)) & 0x00FF00FF
    v = (v | (v << 4)) & 0x0F0F0F0F
    v = (v | (v << 2)) & 0x33333333
    v = (v | (v << 1)) & 0x55555555
    return v


def morton_encode(ix, iy):
    """Interleave integer coordinates into a Z-order code (jit-safe).

    ``ix``/``iy`` are integer tile/cell coordinates, < 2**16.
    Returns int32 codes (safe for grids up to 2**15 per side; we use <= 2**10).
    """
    ix = jnp.asarray(ix, jnp.uint32)
    iy = jnp.asarray(iy, jnp.uint32)
    code = _part1by1_u32(ix) | (_part1by1_u32(iy) << jnp.uint32(1))
    return code.astype(jnp.int32)


def morton_encode_np(ix: np.ndarray, iy: np.ndarray) -> np.ndarray:
    ix = ix.astype(np.uint32)
    iy = iy.astype(np.uint32)

    def part(v):
        v = v & np.uint32(0x0000FFFF)
        v = (v | (v << 8)) & np.uint32(0x00FF00FF)
        v = (v | (v << 4)) & np.uint32(0x0F0F0F0F)
        v = (v | (v << 2)) & np.uint32(0x33333333)
        v = (v | (v << 1)) & np.uint32(0x55555555)
        return v

    return (part(ix) | (part(iy) << np.uint32(1))).astype(np.int64)


def point_to_cell(x, y, grid: int):
    """Map unit-square points to integer cell coordinates in a grid**2 grid."""
    ix = jnp.clip((x * grid).astype(jnp.int32), 0, grid - 1)
    iy = jnp.clip((y * grid).astype(jnp.int32), 0, grid - 1)
    return ix, iy


def rect_to_cell_range(r: jax.Array, grid: int):
    """Integer cell bounds ``(ix0, iy0, ix1, iy1)`` covered by rect(s) r.

    Inclusive bounds. Empty rects produce an inverted range (ix1 < ix0).
    """
    g = jnp.float32(grid)
    ix0 = jnp.clip(jnp.floor(r[..., 0] * g).astype(jnp.int32), 0, grid - 1)
    iy0 = jnp.clip(jnp.floor(r[..., 1] * g).astype(jnp.int32), 0, grid - 1)
    # Subtract a hair so that an exact upper boundary does not spill into the
    # next tile row/col.
    eps = 0.5 / grid * 1e-3
    ix1 = jnp.clip(jnp.floor((r[..., 2] - eps) * g).astype(jnp.int32), 0, grid - 1)
    iy1 = jnp.clip(jnp.floor((r[..., 3] - eps) * g).astype(jnp.int32), 0, grid - 1)
    empty = (r[..., 2] <= r[..., 0]) | (r[..., 3] <= r[..., 1])
    ix1 = jnp.where(empty, ix0 - 1, ix1)
    return ix0, iy0, ix1, iy1


def rect_cell_bounds_np(rects: np.ndarray, grid: int):
    """Integer cell bounds ``(ix0, iy0, ix1, iy1)`` covered by rects, numpy.

    The host-side twin of :func:`rect_to_cell_range` (same upper-edge eps),
    shared by the index build and the query planner so their rect→tile
    bucketing can never drift apart.  Empty rects yield inverted bounds.
    """
    g = float(grid)
    eps = 0.5 / grid * 1e-3
    ix0 = np.clip(np.floor(rects[..., 0] * g).astype(np.int64), 0, grid - 1)
    iy0 = np.clip(np.floor(rects[..., 1] * g).astype(np.int64), 0, grid - 1)
    ix1 = np.clip(np.floor((rects[..., 2] - eps) * g).astype(np.int64), 0, grid - 1)
    iy1 = np.clip(np.floor((rects[..., 3] - eps) * g).astype(np.int64), 0, grid - 1)
    return ix0, iy0, ix1, iy1


def enumerate_rect_tiles(r: jax.Array, grid: int, max_tiles: int):
    """Tile ids (row-major ``iy*grid+ix``) intersecting rect ``r[4]``.

    Returns ``(tile_ids i32[max_tiles], valid bool[max_tiles])``.  Tiles beyond
    the rect's coverage (or beyond ``max_tiles``) are masked out.  Tiles are
    enumerated row-major inside the covered cell range; if the rect covers
    more than ``max_tiles`` tiles the overflow is dropped (documented budget
    approximation — callers size ``max_tiles`` for the largest supported
    query footprint).
    """
    ix0, iy0, ix1, iy1 = rect_to_cell_range(r, grid)
    nx = jnp.maximum(ix1 - ix0 + 1, 0)
    ny = jnp.maximum(iy1 - iy0 + 1, 0)
    idx = jnp.arange(max_tiles, dtype=jnp.int32)
    # row-major within the covered sub-grid
    rel_y = idx // jnp.maximum(nx, 1)
    rel_x = idx % jnp.maximum(nx, 1)
    valid = (idx < nx * ny) & (nx > 0) & (ny > 0)
    tix = jnp.clip(ix0 + rel_x, 0, grid - 1)
    tiy = jnp.clip(iy0 + rel_y, 0, grid - 1)
    tile_ids = tiy * grid + tix
    return jnp.where(valid, tile_ids, 0), valid


def rect_center(r: jax.Array) -> tuple[jax.Array, jax.Array]:
    return (r[..., 0] + r[..., 2]) * 0.5, (r[..., 1] + r[..., 3]) * 0.5
