"""Spatial index: Morton-ordered toe-print store + tile→interval grid.

This is the paper's K-SWEEP substrate (§IV.C), adapted to HBM:

* Every footprint rectangle of every document is a *toe print*.  Toe prints
  are sorted by the Morton (Z-order) code of their center — the
  space-filling-curve layout that makes spatially-close toe prints adjacent
  in memory ("on disk").
* A ``G×G`` tile grid stores, per tile, up to ``m`` toe-print-ID *intervals*
  covering all toe prints intersecting that tile.  The whole structure is a
  few MB (paper: "the entire auxiliary structure can be stored in a few MB").
* A query unions the intervals of the tiles its footprint touches and
  coalesces them into ≤ ``k`` *sweeps* — contiguous ranges fetched with
  ``dynamic_slice`` streams instead of random gathers.

Also holds the doc-major footprint mirror (``doc_rects``/``doc_amps``) used
by the TEXT-FIRST / GEO-FIRST baselines (the "footprints sorted by docID on
disk" file), and per-doc MBRs for the GEO-FIRST in-memory filter (the
R*-tree stand-in: a memory-resident MBR table probed via the same tile grid).

Block-max metadata (the SEAL-style pruning substrate)
-----------------------------------------------------

The Morton-ordered store is additionally cut into fixed ``block_size``-
toe-print *blocks* (block ``b`` covers toe-print IDs ``[b*block_size,
(b+1)*block_size)``), and three per-block columns are precomputed at build:

* ``blk_mbr     f32[NB, 4]`` — MBR of the block's toe-print rects,
* ``blk_max_amp f32[NB]``    — max amplitude in the block,
* ``blk_max_mass f32[NB]``   — max per-toe-print ``amp * area``.

Together they give a cheap, *safe* upper bound on any toe print's partial
geo score against a query footprint::

    score_t <= min(blk_max_amp * sum_q area(blk_mbr ∩ q) * amp_q,
                   blk_max_mass * sum_q amp_q)

which is what the pruned K-SWEEP path (``budgets.prune``; see
``kernels/sweep_score``) tests against its running threshold θ to skip
scoring whole sweep blocks.  Like the tile grid, the block columns are a
small memory-resident auxiliary structure (``~T/block_size`` rows).  They
are always stored in f32 — computed from the (possibly f16-compressed)
store values actually scored at query time, so the bound stays safe under
lossy compression.  ``block_size`` must divide the Pallas streaming tile
(1024 toe prints) so a VMEM tile always covers whole blocks.
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import geometry

INVALID = np.int32(2**31 - 1)
SCALE_BLOCK = 128  # toe prints per int8 amplitude-scale block (= kernel lanes)
COMPRESS_MODES = ("none", "f16", "int8")


@jax.tree_util.register_dataclass
@dataclass(frozen=True)
class SpatialIndex:
    # --- Morton-sorted toe-print store (the k-sweep "disk file") ---
    # compressed builds store rects/amps in f16 (or amps in int8 with a
    # per-SCALE_BLOCK f32 scale) and doc ids in i16 when they fit — the
    # sweep kernels stream the stored dtypes and decode in-register
    tp_rects: jax.Array  # f32[T, 4] (f16 when compressed)
    tp_amps: jax.Array  # f32[T] (f16 / int8 when compressed)
    tp_doc_ids: jax.Array  # i32[T] (i16 when compressed and n_docs fits)
    tp_amp_scale: jax.Array  # f32[ceil(T/SCALE_BLOCK)] ([0] unless int8)
    # the pruned sweep kernel's 32-bit copy of the store (it copies single
    # rows, which a 16- or 8-bit array cannot give it on the TPU):
    # i32[5, rows, 128] f32 coords + amp, or i32[3, rows, 128] f16 coord
    # pairs + f32 amp (kernels/sweep_score/ops.py::pruned_store_planes)
    tp_planes: jax.Array
    # --- tile grid: per tile, m toe-print-ID intervals [start, end) ---
    tile_starts: jax.Array  # i32[G*G, m]
    tile_ends: jax.Array  # i32[G*G, m]
    # --- doc-major mirror (docID-sorted footprint file) ---
    doc_rects: jax.Array  # f32[N, R, 4]
    doc_amps: jax.Array  # f32[N, R]
    doc_mbr: jax.Array  # f32[N, 4]
    doc_mass: jax.Array  # f32[N]  (Σ area·amp, for score upper bounds)
    # --- block-max metadata over the toe-print store (pruned K-SWEEP) ---
    blk_mbr: jax.Array  # f32[NB, 4]
    blk_max_amp: jax.Array  # f32[NB]
    blk_max_mass: jax.Array  # f32[NB]  (max amp·area per block)
    grid: int = field(metadata=dict(static=True))
    n_docs: int = field(metadata=dict(static=True))
    block_size: int = field(default=128, metadata=dict(static=True))

    @property
    def n_toeprints(self) -> int:
        return self.tp_rects.shape[0]

    @property
    def n_blocks(self) -> int:
        return self.blk_mbr.shape[0]

    @property
    def m_intervals(self) -> int:
        return self.tile_starts.shape[1]

    @property
    def plane_bytes(self) -> float:
        """Bytes per toe print the sweep kernels stream (coordinate planes +
        amplitude + amortized scale column, NOT the doc-id column)."""
        scale = 4.0 / SCALE_BLOCK if self.tp_amp_scale.shape[0] else 0.0
        return (
            4 * self.tp_rects.dtype.itemsize
            + self.tp_amps.dtype.itemsize
            + scale
        )

    @property
    def tp_bytes(self) -> float:
        """Modeled bytes per full toe-print record (planes + doc id) — the
        unit behind ``bytes_spatial``/``bytes_scored``.  24 uncompressed."""
        return self.plane_bytes + self.tp_doc_ids.dtype.itemsize

    @property
    def pruned_tp_bytes(self) -> float:
        """Bytes per toe print the pruned sweep streams: its 32-bit
        ``tp_planes`` (20 B with f32 coordinates, 12 B with f16) + doc id."""
        return 4.0 * self.tp_planes.shape[0] + self.tp_doc_ids.dtype.itemsize

    @property
    def doc_bytes(self) -> float:
        """Bytes per doc-major footprint slot (rect + amp); 20 uncompressed."""
        return 4 * self.doc_rects.dtype.itemsize + self.doc_amps.dtype.itemsize


def normalize_compress(compress) -> str:
    """Accept the legacy bool flag or a mode string; return the mode."""
    if compress is True:
        return "f16"
    if compress is False or compress is None:
        return "none"
    if compress not in COMPRESS_MODES:
        raise ValueError(f"compress must be one of {COMPRESS_MODES}, got {compress!r}")
    return compress


def quantize_amps_np(amps: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Symmetric per-SCALE_BLOCK int8 quantization of the amp column.

    Returns (q int8[T], scale f32[ceil(T/SB)]); decode is
    ``q.astype(f32) * scale[t // SCALE_BLOCK]`` — the exact expression the
    kernels and references evaluate, so quantized values round-trip
    bit-identically everywhere.  Handles negative amps (symmetric range)
    and all-zero blocks (scale 1.0, q 0).
    """
    T = amps.shape[0]
    nb = max((T + SCALE_BLOCK - 1) // SCALE_BLOCK, 1)
    pad = nb * SCALE_BLOCK - T
    a = np.concatenate([amps.astype(np.float32), np.zeros((pad,), np.float32)])
    a = a.reshape(nb, SCALE_BLOCK)
    max_abs = np.abs(a).max(axis=1)
    scale = np.where(max_abs > 0, max_abs / 127.0, 1.0).astype(np.float32)
    q = np.clip(np.round(a / scale[:, None]), -127, 127).astype(np.int8)
    return q.reshape(-1)[:T], scale


def _id_dtype(n_docs: int, mode: str):
    return np.int16 if (mode != "none" and n_docs <= np.iinfo(np.int16).max) else np.int32


def build_spatial_index_np(
    doc_rects: np.ndarray,  # f32[N, R, 4] (padded with EMPTY_RECT)
    doc_amps: np.ndarray,  # f32[N, R]
    grid: int = 64,
    m_intervals: int = 2,
    compress: bool | str = False,  # "none"|"f16"|"int8" (paper: lossy compression)
    block_size: int = 128,  # toe prints per block-max metadata block
) -> SpatialIndex:
    """Host-side index build (the paper's offline preprocessing).

    ``compress="f16"`` stores footprint rects/amps in f16; ``"int8"``
    additionally quantizes the toe-print amp column to int8 with a
    per-:data:`SCALE_BLOCK` f32 scale.  Both narrow the streamed doc-id
    column to i16 when ``n_docs`` fits.  Block-max metadata is always
    computed from the decoded (post-quantization) values so the pruning
    bounds stay safe.
    """
    N, R, _ = doc_rects.shape
    valid = doc_rects[:, :, 2] > doc_rects[:, :, 0]
    doc_idx, rect_idx = np.nonzero(valid)
    rects = doc_rects[doc_idx, rect_idx]  # [T, 4]
    amps = doc_amps[doc_idx, rect_idx]

    # Morton order by rect-center cell in a fine 2^15 grid.
    cx = (rects[:, 0] + rects[:, 2]) * 0.5
    cy = (rects[:, 1] + rects[:, 3]) * 0.5
    fine = 1 << 15
    ix = np.clip((cx * fine).astype(np.int64), 0, fine - 1)
    iy = np.clip((cy * fine).astype(np.int64), 0, fine - 1)
    codes = geometry.morton_encode_np(ix.astype(np.uint32), iy.astype(np.uint32))
    order = np.argsort(codes, kind="stable")
    rects, amps, doc_idx = rects[order], amps[order], doc_idx[order]
    T = len(rects)

    # Tile grid: toe-print IDs intersecting each tile, compressed to m
    # intervals.  The host's cost is one step per (tile, toe print) pair —
    # ~1.6e11 for one chip's share of geoweb at grid 1024 — so on a TPU the
    # table comes from the patch-walking kernel, which returns the same
    # arrays (tests/test_builders.py).
    if jax.default_backend() == "tpu":
        from repro.kernels.tile_intervals.ops import tile_intervals

        cells = geometry.rect_cell_bounds_np(rects, grid)
        tile_starts, tile_ends = tile_intervals(*cells, grid, m_intervals)
    else:
        tile_starts, tile_ends = tile_intervals_np(rects, grid, m_intervals)

    # doc-major mirrors
    mbr = doc_mbr_np(doc_rects)
    area = np.maximum(doc_rects[:, :, 2] - doc_rects[:, :, 0], 0) * np.maximum(
        doc_rects[:, :, 3] - doc_rects[:, :, 1], 0
    )
    mass = (area * doc_amps).sum(axis=1).astype(np.float32)

    mode = normalize_compress(compress)
    ft = np.float16 if mode != "none" else np.float32
    if mode == "int8":
        tp_amps_store, tp_amp_scale = quantize_amps_np(amps)
        dec_amps = tp_amps_store.astype(np.float32) * np.repeat(
            tp_amp_scale, SCALE_BLOCK
        )[: len(tp_amps_store)]
    else:
        tp_amps_store = amps.astype(ft)
        tp_amp_scale = np.zeros((0,), np.float32)
        dec_amps = tp_amps_store.astype(np.float32)
    # block-max metadata is computed from the values the query path will
    # actually score (post-cast / dequantized), so the bounds stay safe
    # under lossy compression
    blk_mbr, blk_max_amp, blk_max_mass = block_metadata_np(
        rects.astype(ft).astype(np.float32),
        dec_amps,
        block_size,
    )
    from repro.kernels.sweep_score.ops import pruned_store_planes

    tp_rects = jnp.asarray(rects.astype(ft))
    tp_amps = jnp.asarray(tp_amps_store)
    tp_amp_scale = jnp.asarray(tp_amp_scale)
    return SpatialIndex(
        tp_rects=tp_rects,
        tp_amps=tp_amps,
        tp_doc_ids=jnp.asarray(doc_idx.astype(_id_dtype(N, mode))),
        tp_amp_scale=tp_amp_scale,
        tp_planes=pruned_store_planes(tp_rects, tp_amps, tp_amp_scale, block_size),
        tile_starts=jnp.asarray(tile_starts),
        tile_ends=jnp.asarray(tile_ends),
        doc_rects=jnp.asarray(doc_rects.astype(ft)),
        doc_amps=jnp.asarray(doc_amps.astype(ft)),
        doc_mbr=jnp.asarray(mbr.astype(ft)),
        doc_mass=jnp.asarray(mass.astype(ft)),
        blk_mbr=jnp.asarray(blk_mbr),
        blk_max_amp=jnp.asarray(blk_max_amp),
        blk_max_mass=jnp.asarray(blk_max_mass),
        grid=grid,
        n_docs=N,
        block_size=block_size,
    )


def block_metadata_np(
    rects: np.ndarray,  # f32[T, 4] Morton-ordered toe-print rects
    amps: np.ndarray,  # f32[T]
    block_size: int,
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Per-block (MBR, max amp, max amp·area) over the Morton-ordered store.

    Block ``b`` covers toe prints ``[b*block_size, (b+1)*block_size)``; the
    tail block may be short.  Returns arrays of length ``ceil(T/bs)`` (at
    least 1; a degenerate all-empty block when the store is empty).
    """
    if block_size not in (128, 256, 512, 1024):
        # must divide the kernel's 1024-toe-print VMEM tile into whole
        # 128-lane rows, so a tile's per-block skip masks are row-aligned
        raise ValueError(f"block_size {block_size} must be 128/256/512/1024")
    T = rects.shape[0]
    nb = max((T + block_size - 1) // block_size, 1)
    pad = nb * block_size - T
    # pad with empty rects / zero amps: they cannot raise any block max
    big = np.float32(np.inf)
    r = np.concatenate(
        [rects, np.tile([big, big, -big, -big], (pad, 1)).astype(np.float32)]
    ).reshape(nb, block_size, 4)
    a = np.concatenate([amps, np.zeros((pad,), np.float32)]).reshape(nb, block_size)
    mbr = np.stack(
        [
            r[:, :, 0].min(axis=1),
            r[:, :, 1].min(axis=1),
            r[:, :, 2].max(axis=1),
            r[:, :, 3].max(axis=1),
        ],
        axis=1,
    ).astype(np.float32)
    # fully-padded blocks: make the MBR a plain empty rect (finite)
    empty = ~np.isfinite(mbr).all(axis=1)
    mbr[empty] = geometry.EMPTY_RECT
    area = np.maximum(r[:, :, 2] - r[:, :, 0], 0) * np.maximum(
        r[:, :, 3] - r[:, :, 1], 0
    )
    area = np.where(np.isfinite(area), area, 0.0)
    return (
        mbr,
        a.max(axis=1).astype(np.float32),
        (a * area).max(axis=1).astype(np.float32),
    )


def doc_mbr_np(doc_rects: np.ndarray) -> np.ndarray:
    """Per-doc MBR over its non-empty rects — f32[N, 4]; docs without a
    footprint get ``EMPTY_RECT`` (as :func:`footprint_mbr_np` per doc)."""
    valid = doc_rects[:, :, 2] > doc_rects[:, :, 0]
    inf = np.float32(np.inf)
    lo = np.where(valid[..., None], doc_rects[..., :2], inf).min(axis=1)
    hi = np.where(valid[..., None], doc_rects[..., 2:], -inf).max(axis=1)
    mbr = np.concatenate([lo, hi], axis=1).astype(np.float32)
    mbr[~valid.any(axis=1)] = geometry.EMPTY_RECT
    return mbr


def tile_intervals_np(
    rects: np.ndarray, grid: int, m: int
) -> tuple[np.ndarray, np.ndarray]:
    """Per tile, ≤ ``m`` toe-print-ID intervals covering every toe print
    that intersects it — ``(tile_starts, tile_ends)`` i32[grid², m].

    ``rects`` are the Morton-ordered toe prints (ID = row).  A tile's
    sorted ID set is cut at its ``m − 1`` largest gaps (the first of equal
    gaps wins; gaps of 1 are never cut), which minimizes covered slack;
    tiles no toe print touches stay ``INVALID``.

    Vectorised per unit of one grid row × ``_COLS`` columns: every
    (column, ID) pair of the unit is materialized, ordered by column with
    a stable radix sort (IDs stay ascending within a column), and each
    column's gaps are reduced with segmented maxima.  The work is the
    number of (tile, toe print) pairs, spread over a thread pool (numpy
    releases the GIL in these kernels); units bound the memory per thread.
    """
    G = grid
    starts = np.full((G * G, m), INVALID, dtype=np.int32)
    ends = np.full((G * G, m), INVALID, dtype=np.int32)
    x0, y0, x1, y1 = geometry.rect_cell_bounds_np(rects, G)
    live = (x1 >= x0) & (y1 >= y0)
    x0, y0, x1, y1 = (v.astype(np.int32) for v in (x0, y0, x1, y1))
    band = 16  # grid rows whose candidate IDs are selected together

    def do_band(r0):
        r1 = min(r0 + band, G)
        cand = np.flatnonzero((y0 < r1) & (y1 >= r0) & live).astype(np.int32)
        cy0, cy1, cx0, cx1 = y0[cand], y1[cand], x0[cand], x1[cand]
        for ty in range(r0, r1):
            on_row = (cy0 <= ty) & (cy1 >= ty)
            ids, rx0, rx1 = cand[on_row], cx0[on_row], cx1[on_row]
            for c0 in range(0, G, _COLS):
                c1 = min(c0 + _COLS, G)
                sel = (rx0 < c1) & (rx1 >= c0)
                if sel.any():
                    a = np.maximum(rx0[sel], c0)
                    w = np.minimum(rx1[sel], c1 - 1) - a + 1
                    tiles, s, e = _unit_intervals(ids[sel], a - c0, w, m)
                    starts[ty * G + c0 + tiles] = s
                    ends[ty * G + c0 + tiles] = e

    with ThreadPoolExecutor(geometry.HOST_THREADS) as pool:
        list(pool.map(do_band, range(0, G, band)))
    return starts, ends


_COLS = 256  # grid columns per unit: a column fits in uint8 (radix sort)


def _unit_intervals(ids, a, w, m):
    """Intervals of one unit's tiles (see :func:`tile_intervals_np`).

    ``ids`` ascending, covering local columns ``[a, a + w)``.  Returns the
    unit's non-empty local columns and their ``[m]`` starts / ends.
    """
    p = int(w.sum())
    off = np.cumsum(w) - w
    col = np.repeat(a - off, w) + np.arange(p, dtype=np.int32)
    order = np.argsort(col.astype(np.uint8), kind="stable")
    tid = np.repeat(ids, w)[order]
    cnt = np.bincount(col, minlength=_COLS)
    cols = np.flatnonzero(cnt)
    seg_end = np.cumsum(cnt)[cols]
    seg = seg_end - cnt[cols]
    # key per gap after pair i: (gap << 32) | ~i — the max picks the
    # largest gap, and among equal gaps the first; -1 where a column ends
    gk = np.full((p,), -1 << 32, np.int64)
    gk[:-1] = (tid[1:] - tid[:-1]).astype(np.int64) << 32
    gk |= 0xFFFFFFFF - np.arange(p, dtype=np.int64)
    gk[seg_end - 1] = -1 << 32
    ncut = max(m - 1, 1)
    cuts = np.full((len(cols), ncut), p, np.int64)
    for c in range(m - 1):
        mx = np.maximum.reduceat(gk, seg)
        ok = (mx >> 32) > 1
        first = 0xFFFFFFFF - (mx & 0xFFFFFFFF)
        cuts[ok, c] = first[ok]
        gk[first[ok]] = -1 << 32
    cuts.sort(axis=1)
    has = cuts < p
    safe = np.where(has, cuts, 0)
    tid = tid.astype(np.int64)
    s = np.full((len(cols), m), INVALID, np.int64)
    e = np.full((len(cols), m), INVALID, np.int64)
    s[:, 0] = tid[seg]
    if m > 1:
        s[:, 1:] = np.where(has, tid[np.minimum(safe + 1, p - 1)], INVALID)
    e[:, :ncut] = np.where(has, tid[safe] + 1, INVALID)
    e[np.arange(len(cols)), has.sum(axis=1)] = tid[seg_end - 1] + 1
    return cols, s.astype(np.int32), e.astype(np.int32)


# ---------------------------------------------------------------------------
# Query-time primitives (jit-safe)
# ---------------------------------------------------------------------------

def gather_query_intervals(
    index: SpatialIndex,
    query_rects: jax.Array,  # f32[Qr, 4]
    max_tiles: int,
) -> tuple[jax.Array, jax.Array]:
    """Intervals of every tile touched by the query footprint.

    Returns (starts i32[Qr*max_tiles*m], ends …) with INVALID padding.
    """
    Qr = query_rects.shape[0]

    def per_rect(r):
        tiles, valid = geometry.enumerate_rect_tiles(r, index.grid, max_tiles)
        s = index.tile_starts[tiles]  # [max_tiles, m]
        e = index.tile_ends[tiles]
        s = jnp.where(valid[:, None], s, INVALID)
        e = jnp.where(valid[:, None], e, INVALID)
        return s.reshape(-1), e.reshape(-1)

    starts, ends = jax.vmap(per_rect)(query_rects)
    return starts.reshape(-1), ends.reshape(-1)


def coalesce_k_sweeps(
    starts: jax.Array,  # i32[I] with INVALID padding
    ends: jax.Array,
    k: int,
) -> tuple[jax.Array, jax.Array]:
    """Coalesce intervals into ≤ k sweeps minimizing fetched volume.

    Sort intervals by start; a sweep boundary is placed at the k−1 largest
    *positive* gaps between consecutive intervals (gap = next.start −
    running_max_end).  Closed-form, no data-dependent shapes.

    Returns (sweep_starts i32[k], sweep_ends i32[k]); empty sweeps have
    start == end == INVALID.
    """
    I = starts.shape[0]
    order = jnp.argsort(starts)
    s = starts[order]
    e = ends[order]
    valid = s != INVALID
    # running max of interval ends (prefix), to handle containment/overlap
    e_filled = jnp.where(valid, e, jnp.int32(-1))
    run_end = jax.lax.cummax(e_filled)
    prev_end = jnp.concatenate([jnp.zeros((1,), jnp.int32), run_end[:-1]])
    gap = jnp.where(valid, s - prev_end, jnp.int32(-1))
    gap = gap.at[0].set(jnp.where(valid[0], 0, -1))
    # first valid interval must always open a sweep; force its gap huge
    first_valid = jnp.argmax(valid)  # 0 if none valid
    gap = gap.at[first_valid].set(
        jnp.where(valid.any(), jnp.int32(2**30), gap[first_valid])
    )
    gap = jnp.where(jnp.arange(I) == first_valid, gap, jnp.where(gap > 0, gap, -1))

    # choose k cut positions = k largest positive gaps (first_valid included)
    top_gap, top_idx = jax.lax.top_k(gap, min(k, I))
    is_cut = jnp.zeros((I,), dtype=bool).at[top_idx].set(top_gap > 0)

    # sweep id per interval = cumsum of cuts − 1
    sweep_id = jnp.cumsum(is_cut.astype(jnp.int32)) - 1
    sweep_id = jnp.where(valid, sweep_id, k)  # invalid → bucket k (dropped)

    big = jnp.int32(2**30)
    sweep_starts = jnp.full((k + 1,), big, jnp.int32).at[sweep_id].min(
        jnp.where(valid, s, big)
    )[:k]
    sweep_ends = jnp.full((k + 1,), jnp.int32(-1), jnp.int32).at[sweep_id].max(
        jnp.where(valid, e, jnp.int32(-1))
    )[:k]
    empty = sweep_ends < sweep_starts
    sweep_starts = jnp.where(empty, INVALID, sweep_starts)
    sweep_ends = jnp.where(empty, INVALID, sweep_ends)
    return sweep_starts, sweep_ends


def split_sweeps_to_budget(
    sweep_starts: jax.Array,  # i32[k]
    sweep_ends: jax.Array,
    k: int,
    budget: int,
) -> tuple[jax.Array, jax.Array]:
    """Re-chunk coalesced runs into ≤ k sweeps of length ≤ budget.

    A run longer than ``budget`` would otherwise be tail-truncated by
    ``fetch_sweeps``; here each run r is split into ceil(len_r/budget)
    consecutive chunks and the first k chunks across runs are kept (total
    fetch stays ≤ k·budget — the fixed I/O budget).
    """
    lens = jnp.where(sweep_starts != INVALID, sweep_ends - sweep_starts, 0)
    chunks = (lens + budget - 1) // budget  # per-run chunk count
    cum = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(chunks).astype(jnp.int32)]
    )
    j = jnp.arange(k, dtype=jnp.int32)
    run = jnp.clip(jnp.searchsorted(cum, j, side="right") - 1, 0, k - 1)
    within = j - cum[run]
    valid = j < cum[-1]
    s = jnp.where(sweep_starts[run] == INVALID, 0, sweep_starts[run]) + within * budget
    e = jnp.minimum(s + budget, sweep_ends[run])
    s = jnp.where(valid, s, INVALID)
    e = jnp.where(valid, e, INVALID)
    return s, e


def fetch_sweeps(
    index: SpatialIndex,
    sweep_starts: jax.Array,  # i32[k]
    sweep_ends: jax.Array,
    sweep_budget: int,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Fetch toe prints of ≤ k sweeps as contiguous dynamic slices.

    Each sweep fetches exactly ``sweep_budget`` consecutive toe prints
    starting at its start (entries past the sweep end are masked).  This is
    the HBM analogue of the paper's "k highly efficient [disk] scans".

    Returns (rects f32[k*B,4], amps f32[k*B], doc_ids i32[k*B], valid bool[k*B]).
    """
    k = sweep_starts.shape[0]
    T = index.n_toeprints

    def fetch_one(s, e):
        start = jnp.clip(jnp.where(s == INVALID, 0, s), 0, max(T - sweep_budget, 0))
        r = jax.lax.dynamic_slice(index.tp_rects, (start, 0), (sweep_budget, 4))
        a = jax.lax.dynamic_slice(index.tp_amps, (start,), (sweep_budget,))
        d = jax.lax.dynamic_slice(index.tp_doc_ids, (start,), (sweep_budget,))
        pos = start + jnp.arange(sweep_budget, dtype=jnp.int32)
        # decode: same astype-then-multiply order the kernels use, so the
        # dequantized values bit-match the in-kernel decode
        a = a.astype(jnp.float32)
        if index.tp_amp_scale.shape[0]:
            a = a * index.tp_amp_scale[pos // SCALE_BLOCK]
        ok = (s != INVALID) & (pos >= s) & (pos < e)
        return r.astype(jnp.float32), a, d.astype(jnp.int32), ok

    rects, amps, docs, ok = jax.vmap(fetch_one)(sweep_starts, sweep_ends)
    return (
        rects.reshape(k * sweep_budget, 4),
        amps.reshape(-1),
        docs.reshape(-1),
        ok.reshape(-1),
    )


def fetch_sweep_ids(
    index: SpatialIndex,
    sweep_starts: jax.Array,  # i32[k]
    sweep_ends: jax.Array,
    sweep_budget: int,
) -> tuple[jax.Array, jax.Array]:
    """Doc-id-only sweep fetch (pairs with the fused sweep_score kernel,
    which produces the scores without materializing the geometry)."""
    k = sweep_starts.shape[0]
    T = index.n_toeprints

    def fetch_one(s, e):
        start = jnp.clip(jnp.where(s == INVALID, 0, s), 0, max(T - sweep_budget, 0))
        d = jax.lax.dynamic_slice(index.tp_doc_ids, (start,), (sweep_budget,))
        pos = start + jnp.arange(sweep_budget, dtype=jnp.int32)
        # re-window to [s, s+budget) convention used by the fused kernel
        shift = jnp.where(s == INVALID, 0, s) - start
        idx = jnp.clip(
            shift + jnp.arange(sweep_budget, dtype=jnp.int32), 0, sweep_budget - 1
        )
        return d[idx].astype(jnp.int32)

    docs = jax.vmap(fetch_one)(sweep_starts, sweep_ends)
    return docs.reshape(k * sweep_budget)


def tile_candidate_toeprints(
    index: SpatialIndex,
    query_rects: jax.Array,  # f32[Qr, 4]
    max_tiles: int,
    max_candidates: int,
    max_runs: int = 64,
) -> tuple[jax.Array, jax.Array]:
    """GEO-FIRST candidate generation: individual toe-print IDs from tiles.

    Merges the query's tile intervals into ≤ ``max_runs`` disjoint runs, then
    enumerates individual toe-print IDs (cumsum expansion) up to the
    ``max_candidates`` budget.  Models the R*-tree candidate lookup — each
    candidate toe print is then fetched *individually* (random access).

    Returns (tp_ids i32[max_candidates], valid bool[max_candidates]).
    """
    starts, ends = gather_query_intervals(index, query_rects, max_tiles)
    s, e = coalesce_k_sweeps(starts, ends, max_runs)  # disjoint runs
    lens = jnp.where(s != INVALID, e - s, 0)
    offs = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(lens).astype(jnp.int32)]
    )
    j = jnp.arange(max_candidates, dtype=jnp.int32)
    run = jnp.clip(jnp.searchsorted(offs, j, side="right") - 1, 0, max_runs - 1)
    ok = j < offs[-1]
    ids = jnp.where(s[run] == INVALID, 0, s[run]) + (j - offs[run])
    return jnp.where(ok, ids, 0), ok
