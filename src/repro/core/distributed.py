"""Distributed geo-search serving: doc-sharded index × query-sharded batch.

Topology (DESIGN.md §5): documents are partitioned into ``S`` index shards
laid out over the mesh's doc axes (``('pod','data')`` in production); the
query batch is sharded over the ``'model'`` axis (replica/throughput axis).
One ``shard_map`` serve step:

1. every device runs the full K-SWEEP pipeline against its local index shard
   for its local query slice;
2. local top-k per (query, shard);
3. hierarchical merge: ``all_gather`` along ``'data'`` (intra-pod ICI) +
   re-top-k, then along ``'pod'`` (inter-pod DCI) + final top-k.

Collective volume per query is O(k · n_doc_shards) — independent of corpus
size, the property that makes the architecture scale to thousands of chips.

The ``Partitioner`` protocol (paper §Conclusions future work)
-------------------------------------------------------------
Document partitioning is a first-class strategy object, not a string flag.
A partitioner implements:

* ``name`` — stable identifier (CLI / report label);
* ``assign(doc_rects, n_shards) -> i32[N]`` — shard id per document, given
  the doc footprint rects ``f32[N, R, 4]`` (padded slots: inverted rects);
* ``coverage(rects, amps) -> bool[G, G]`` — the bbox-grid summary of one
  shard's toe prints (shared base implementation; see below).

Shipped strategies:

* :class:`HashPartitioner`   — round-robin ``doc_id % n_shards`` (the
  standard engine layout; every shard sees every region);
* :class:`MortonPartitioner` — docs sorted by the Morton code of their
  footprint center, split into equal contiguous ranges: each shard owns a
  compact curve segment, its tile grid is denser and sweeps are tighter;
* :class:`RegionRangePartitioner` — recursive median (KD) splits of the
  footprint centers: each shard owns an axis-aligned region, the tightest
  per-shard MBRs of the three (the footprint-routing partitioner).

Strings are resolved exactly once, at the CLI boundary, via
:func:`resolve_partitioner`; every core/serving call site takes an
instance (passing a raw string raises ``TypeError``).

Coverage grids and footprint routing
------------------------------------
Each shard's spatial extent is summarized as a ``G×G`` boolean bbox grid
(``G = COVERAGE_GRID``) over its toe-print rects — the same clamped-floor
cell mapping (:func:`repro.core.planner.coarse_cells`, no upper-edge
epsilon) the planner's ``tp_span`` grid uses, so the summary *over-covers*:
any toe print ∩ query-rect intersection shares at least one cell with the
query's cell range.  The grid is stored as its summed-area table
(``coverage_sat f32[G+1, G+1]``, integral image of the 0/1 grid), making
"does this rect touch any covered cell" an O(1) four-corner lookup both
host-side (:func:`footprint_touch_np`) and inside the jit'd serve step.

Because ranking requires footprint overlap (``combine_scores`` scores a
doc −inf when its geo score is 0 — see :mod:`repro.core.ranking`), a shard
whose coverage grid misses every query footprint in a batch can only
produce empty local top-k lists.  Executors exploit this: the host
scatter-gather loop skips such shards outright, and the mesh serve step
(``make_serve_fn(with_routing=True)``) masks them so their counters and
score contributions are zero by construction — bit-identical results at
O(shards-touched) instead of O(S) per-query cost.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from repro.core import algorithms as alg
from repro.core import ranking
from repro.core.engine import GeoIndex
from repro.core.spatial_index import SpatialIndex, build_spatial_index_np
from repro.core.text_index import (
    DOC_PLANE_PAD,
    TextIndex,
    build_text_index_np,
    global_idf_np as tidx_global_idf,
)
from repro.core import geometry
from repro.core.planner import coarse_cells
from repro.kernels.sweep_score.ops import pruned_store_planes

#: Side length of the per-shard coverage bbox grid.  Matches the planner's
#: ``tp_span`` grid resolution (``planner._SPAN_GRID``): fine enough that
#: city-sized footprints resolve to a few cells, coarse enough that the
#: [S, G+1, G+1] SAT stack stays negligible next to the index arrays.
COVERAGE_GRID = 16


def _valid_rects_np(rects: np.ndarray, amps: np.ndarray | None = None) -> np.ndarray:
    """bool[...] mask of real (non-padding) rect slots: positive area and,
    when amplitudes are given, positive amplitude."""
    rects = np.asarray(rects)
    v = (rects[..., 2] > rects[..., 0]) & (rects[..., 3] > rects[..., 1])
    if amps is not None:
        v = v & (np.asarray(amps) > 0)
    return v


def coverage_grid_np(
    rects: np.ndarray, amps: np.ndarray | None = None, grid: int = COVERAGE_GRID
) -> np.ndarray:
    """Occupancy grid ``bool[G, G]`` (row = y cell) of the valid rects.

    Cells are claimed through :func:`repro.core.planner.coarse_cells` — the
    shared clamped-floor mapping with no upper-edge epsilon — so the grid
    over-covers: every point of every valid rect lands in a claimed cell.
    """
    occ = np.zeros((grid, grid), dtype=bool)
    r = np.asarray(rects).reshape(-1, 4)
    valid = _valid_rects_np(rects, amps).reshape(-1)
    r = r[valid]
    if r.shape[0] == 0:
        return occ
    ix0, iy0, ix1, iy1 = coarse_cells(r, grid)
    for x0, y0, x1, y1 in zip(ix0, iy0, ix1, iy1):
        occ[y0 : y1 + 1, x0 : x1 + 1] = True
    return occ


def coverage_sat_np(occ: np.ndarray) -> np.ndarray:
    """Summed-area table ``f32[G+1, G+1]`` of a 0/1 occupancy grid."""
    g = occ.shape[0]
    sat = np.zeros((g + 1, g + 1), dtype=np.float32)
    sat[1:, 1:] = np.cumsum(np.cumsum(occ.astype(np.float32), axis=0), axis=1)
    return sat


def footprint_touch_np(
    sats: np.ndarray,
    rects: np.ndarray,
    amps: np.ndarray | None = None,
    grid: int = COVERAGE_GRID,
) -> np.ndarray:
    """Which shards each query's footprints can reach: ``bool[S, B]``.

    ``sats`` is the stacked coverage SAT ``f32[S, G+1, G+1]``; ``rects`` the
    query footprints ``f32[B, R, 4]`` (``amps f32[B, R]`` marks padding).
    A query touches a shard iff any valid rect's coarse-cell range contains
    a covered cell — an O(1) four-corner SAT lookup per (shard, rect).
    Queries with no valid rect touch nothing (scored −inf everywhere by
    ``require_geo`` ranking regardless of routing).
    """
    sats = np.asarray(sats)
    rects = np.asarray(rects)
    valid = _valid_rects_np(rects, amps)  # [B, R]
    ix0, iy0, ix1, iy1 = coarse_cells(rects, grid)  # each [B, R]
    cover = (
        sats[:, iy1 + 1, ix1 + 1]
        - sats[:, iy0, ix1 + 1]
        - sats[:, iy1 + 1, ix0]
        + sats[:, iy0, ix0]
    )  # [S, B, R]
    return np.any((cover > 0) & valid[None], axis=-1)


def _footprint_centers(doc_rects: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Mean footprint center per doc, over valid rect slots (f64[N], f64[N])."""
    r = np.asarray(doc_rects, dtype=np.float64)
    valid = _valid_rects_np(r)  # [N, R]
    w = np.maximum(valid.sum(axis=1), 1)
    cx = np.where(valid, (r[:, :, 0] + r[:, :, 2]) * 0.5, 0.0).sum(axis=1) / w
    cy = np.where(valid, (r[:, :, 1] + r[:, :, 3]) * 0.5, 0.0).sum(axis=1) / w
    return cx, cy


class Partitioner:
    """Document-partitioning strategy (see module docstring).

    Stateless: ``assign`` maps doc footprints to shard ids; ``coverage``
    summarizes one shard's toe prints as the routing occupancy grid (the
    base implementation is shared — strategies only differ in ``assign``).
    """

    name: str = "base"

    def assign(self, doc_rects: np.ndarray, n_shards: int) -> np.ndarray:
        raise NotImplementedError

    def coverage(
        self,
        rects: np.ndarray,
        amps: np.ndarray | None = None,
        grid: int = COVERAGE_GRID,
    ) -> np.ndarray:
        return coverage_grid_np(rects, amps, grid)

    def __repr__(self) -> str:  # pragma: no cover - cosmetic
        return f"{type(self).__name__}()"


class HashPartitioner(Partitioner):
    """Round-robin ``doc_id % n_shards`` — the geography-blind baseline."""

    name = "hash"

    def assign(self, doc_rects: np.ndarray, n_shards: int) -> np.ndarray:
        n_docs = np.asarray(doc_rects).shape[0]
        return (np.arange(n_docs) % n_shards).astype(np.int32)


class MortonPartitioner(Partitioner):
    """Equal contiguous ranges of the Morton order of footprint centers."""

    name = "morton"

    def assign(self, doc_rects: np.ndarray, n_shards: int) -> np.ndarray:
        n_docs = np.asarray(doc_rects).shape[0]
        cx, cy = _footprint_centers(doc_rects)
        fine = 1 << 15
        code = geometry.morton_encode_np(
            np.clip(cx * fine, 0, fine - 1).astype(np.uint32),
            np.clip(cy * fine, 0, fine - 1).astype(np.uint32),
        )
        order = np.argsort(code, kind="stable")
        per = (n_docs + n_shards - 1) // n_shards
        ids = np.empty(n_docs, dtype=np.int32)
        ids[order] = np.arange(n_docs) // per
        return ids


class RegionRangePartitioner(Partitioner):
    """Recursive median (KD) splits of footprint centers: each shard owns a
    compact axis-aligned region, so coverage grids are the tightest of the
    shipped strategies.  Handles any ``n_shards`` via proportional child
    targets (shard sizes differ by at most one doc)."""

    name = "region"

    def assign(self, doc_rects: np.ndarray, n_shards: int) -> np.ndarray:
        n_docs = np.asarray(doc_rects).shape[0]
        cx, cy = _footprint_centers(doc_rects)
        ids = np.zeros(n_docs, dtype=np.int32)
        next_id = [0]

        def split(sel: np.ndarray, parts: int, depth: int) -> None:
            if parts <= 1:
                ids[sel] = next_id[0]
                next_id[0] += 1
                return
            left = parts // 2
            axis = cx if depth % 2 == 0 else cy
            order = sel[np.argsort(axis[sel], kind="stable")]
            cut = (len(sel) * left + parts - 1) // parts
            split(order[:cut], left, depth + 1)
            split(order[cut:], parts - left, depth + 1)

        split(np.arange(n_docs), n_shards, 0)
        return ids


_PARTITIONERS = {
    "hash": HashPartitioner,
    "morton": MortonPartitioner,
    "region": RegionRangePartitioner,
    # legacy CLI spelling from the string-flag era: Morton order
    "geo": MortonPartitioner,
}


def resolve_partitioner(spec: "str | Partitioner | None") -> Partitioner:
    """CLI-boundary resolution: str → instance (once); instances pass through.

    ``None`` resolves to :class:`MortonPartitioner` (the serving default).
    Everywhere else in core/serving, raw strings are a ``TypeError``.
    """
    if spec is None:
        return MortonPartitioner()
    if isinstance(spec, Partitioner):
        return spec
    if isinstance(spec, str):
        try:
            return _PARTITIONERS[spec]()
        except KeyError:
            raise ValueError(
                f"unknown partitioner {spec!r}; choose from {sorted(_PARTITIONERS)}"
            ) from None
    raise TypeError(f"expected Partitioner instance or name, got {type(spec).__name__}")


def _require_partitioner(
    partitioner: "Partitioner | None", default: type[Partitioner]
) -> Partitioner:
    """Core-API guard: instances only (strings stop at the CLI boundary)."""
    if partitioner is None:
        return default()
    if isinstance(partitioner, Partitioner):
        return partitioner
    raise TypeError(
        "partitioner must be a Partitioner instance (e.g. MortonPartitioner()); "
        "raw strings are only accepted at the CLI boundary via "
        f"resolve_partitioner() — got {partitioner!r}"
    )


@jax.tree_util.register_dataclass
@dataclass(frozen=True)
class ShardedGeoIndex:
    """Stacked per-shard index arrays; leading dim = doc shard."""

    # text index
    postings: jax.Array  # i32[S, P]
    impacts: jax.Array  # f32[S, P]
    offsets: jax.Array  # i32[S, M+1]
    # text index: delta + bit-packed doc-id store ([S, 0] when uncompressed)
    post_packed: jax.Array  # u32[S, W]
    blk_first: jax.Array  # i32[S, NBp]
    blk_bits: jax.Array  # i32[S, NBp]
    blk_word_off: jax.Array  # i32[S, NBp]
    blk_n_exc: jax.Array  # i32[S, NBp] PForDelta exception words per block
    # logical 128-posting block framing (both layouts; see text_index.py)
    blk_len: jax.Array  # i32[S, NBt]
    blk_pos: jax.Array  # i32[S, NBt]
    blk_max_impact: jax.Array  # f32[S, NBt] post-quantization block maxima
    imp_plane: jax.Array  # [S, NBt, 128] block-major impacts
    doc_plane: jax.Array  # i32[S, NBp, 128] block-major doc ids ([S, 0, 128] raw)
    blk_term_off: jax.Array  # i32[S, M+1]
    # impact-ordered segment CSR (degenerate under layout="docid")
    seg_term_off: jax.Array  # i32[S, M+1]
    seg_pos: jax.Array  # i32[S, NSp]
    seg_len: jax.Array  # i32[S, NSp]
    # spatial index (stored dtypes: f16/int8/i16 under compressed modes)
    tp_rects: jax.Array  # f32[S, T, 4]
    tp_amps: jax.Array  # f32[S, T]
    tp_doc_ids: jax.Array  # i32[S, T]
    tp_amp_scale: jax.Array  # f32[S, ceil(T/SCALE_BLOCK)] ([S, 0] unless int8)
    tp_planes: jax.Array  # i32[S, P, rows, 128] (SpatialIndex.tp_planes)
    tile_starts: jax.Array  # i32[S, G*G, m]
    tile_ends: jax.Array  # i32[S, G*G, m]
    doc_rects: jax.Array  # f32[S, N, R, 4]
    doc_amps: jax.Array  # f32[S, N, R]
    doc_mbr: jax.Array  # f32[S, N, 4]
    doc_mass: jax.Array  # f32[S, N]
    # block-max metadata columns (pruned K-SWEEP; see core/spatial_index.py)
    blk_mbr: jax.Array  # f32[S, NB, 4]
    blk_max_amp: jax.Array  # f32[S, NB]
    blk_max_mass: jax.Array  # f32[S, NB]
    pagerank: jax.Array  # f32[S, N]
    doc_offset: jax.Array  # i32[S]  local→global docID base
    # routing: per-shard coverage-grid summed-area table (module docstring)
    coverage_sat: jax.Array  # f32[S, CG+1, CG+1]
    grid: int = field(metadata=dict(static=True))
    n_terms: int = field(metadata=dict(static=True))
    block_size: int = field(default=128, metadata=dict(static=True))
    coverage_grid: int = field(default=COVERAGE_GRID, metadata=dict(static=True))
    # max posting blocks of any term on any shard (pruned-text window bound)
    max_term_blocks: int = field(default=1, metadata=dict(static=True))
    # posting order of every shard's text index ("docid" | "impact")
    layout: str = field(default="docid", metadata=dict(static=True))
    # max impact segments of any term on any shard (segmented probe bound)
    max_term_segments: int = field(default=1, metadata=dict(static=True))

    @property
    def n_shards(self) -> int:
        return self.postings.shape[0]


def shard_corpus_np(
    doc_terms: list[np.ndarray],
    doc_rects: np.ndarray,
    doc_amps: np.ndarray,
    pagerank: np.ndarray,
    n_terms: int,
    n_shards: int,
    partitioner: "Partitioner | None" = None,
    grid: int = 64,
    m_intervals: int = 2,
    block_size: int = 128,
    compress: "bool | str" = False,
    layout: str = "docid",
) -> ShardedGeoIndex:
    """Partition a corpus with ``partitioner`` (default hash round-robin)
    and build one index per shard (host side), including each shard's
    coverage SAT for footprint routing.  ``compress`` takes the same
    ``{none, f16, int8}`` modes as the single-index builders: every shard
    stores bit-packed postings and quantized toe prints.  ``layout``
    selects every shard's posting order (``"docid"`` | ``"impact"``; see
    :mod:`repro.core.text_index`)."""
    from repro.core.spatial_index import SCALE_BLOCK, normalize_compress

    mode = normalize_compress(compress)
    n_docs = len(doc_terms)
    partitioner = _require_partitioner(partitioner, default=HashPartitioner)
    shard_ids = np.asarray(partitioner.assign(doc_rects, n_shards))
    if shard_ids.shape != (n_docs,):
        raise ValueError(
            f"{partitioner.name}.assign returned shape {shard_ids.shape}, "
            f"expected ({n_docs},)"
        )

    idf_global = tidx_global_idf(doc_terms, n_terms)
    shards = []
    coverage = []
    for s in range(n_shards):
        # ascending global ids within the shard: local tie-breaks (lower
        # local docID wins) then agree with the single-index engine's
        sel = np.flatnonzero(shard_ids == s)
        terms = [doc_terms[i] for i in sel]
        # broadcast global term statistics (IDF) so shards rank like the
        # single-index engine would — built in directly (not rescaled after
        # the fact) so impacts are bit-identical across partitionings
        # impacts stored as the single-index engine stores them (f16 under
        # any compressed mode), so a shard scores like GeoSearchEngine
        text = build_text_index_np(
            terms, n_terms, idf=idf_global, compress=(mode != "none"),
            impact_dtype=(np.float16 if mode != "none" else None),
            layout=layout,
        )
        spatial = build_spatial_index_np(
            doc_rects[sel], doc_amps[sel], grid, m_intervals,
            block_size=block_size, compress=mode,
        )
        shards.append((text, spatial, pagerank[sel], sel))
        # routing coverage wants decoded f32 amps (int8 stores are scaled)
        cov_amps = np.asarray(spatial.tp_amps).astype(np.float32)
        if spatial.tp_amp_scale.shape[0]:
            sc = np.asarray(spatial.tp_amp_scale)
            cov_amps = cov_amps * np.repeat(sc, SCALE_BLOCK)[: cov_amps.shape[0]]
        occ = partitioner.coverage(
            np.asarray(spatial.tp_rects).astype(np.float32), cov_amps, COVERAGE_GRID
        )
        coverage.append(coverage_sat_np(occ))

    # pad to uniform shapes and stack
    P_max = max(s[0].impacts.shape[0] for s in shards)
    Pp_max = max(s[0].postings.shape[0] for s in shards)  # 0 when compressed
    W_max = max(s[0].post_packed.shape[0] for s in shards)
    NBp_max = max(s[0].blk_first.shape[0] for s in shards)  # 0 uncompressed
    NBt_max = max(s[0].blk_len.shape[0] for s in shards)  # logical framing
    NS_max = max(s[0].seg_pos.shape[0] for s in shards)  # impact segments
    T_max = max(s[1].tp_rects.shape[0] for s in shards)
    SB_max = max(s[1].tp_amp_scale.shape[0] for s in shards)
    N_max = max(len(s[3]) for s in shards)
    R = doc_rects.shape[1]

    def padded(a, n, fill):
        a = np.asarray(a)
        out = np.full((n,) + a.shape[1:], fill, dtype=a.dtype)
        out[: a.shape[0]] = a
        return out

    stacked = {}
    stacked["postings"] = np.stack(
        [padded(s[0].postings, Pp_max, 2**31 - 1) for s in shards]
    )
    stacked["impacts"] = np.stack([padded(s[0].impacts, P_max, 0.0) for s in shards])
    stacked["offsets"] = np.stack([np.asarray(s[0].offsets) for s in shards])
    # packed posting columns (all width-0 when uncompressed); padded blocks
    # are unreachable (every probe is bounded by its term's blk_term_off
    # slice) — bits pad 1 so even an accidental decode stays well-defined
    stacked["post_packed"] = np.stack(
        [padded(s[0].post_packed, W_max, 0) for s in shards]
    )
    stacked["blk_first"] = np.stack([padded(s[0].blk_first, NBp_max, 0) for s in shards])
    stacked["blk_bits"] = np.stack([padded(s[0].blk_bits, NBp_max, 1) for s in shards])
    stacked["blk_word_off"] = np.stack(
        [padded(s[0].blk_word_off, NBp_max, 0) for s in shards]
    )
    stacked["blk_n_exc"] = np.stack(
        [padded(s[0].blk_n_exc, NBp_max, 0) for s in shards]
    )
    # logical framing columns exist in both layouts; padded blocks are
    # empty (len 0) with a zero impact bound, so they can never be probed
    # or beat a pruning threshold
    stacked["blk_len"] = np.stack([padded(s[0].blk_len, NBt_max, 0) for s in shards])
    stacked["blk_pos"] = np.stack([padded(s[0].blk_pos, NBt_max, 0) for s in shards])
    stacked["blk_max_impact"] = np.stack(
        [padded(s[0].blk_max_impact, NBt_max, 0.0) for s in shards]
    )
    stacked["imp_plane"] = np.stack(
        [padded(s[0].imp_plane, NBt_max, 0.0) for s in shards]
    )
    stacked["doc_plane"] = np.stack(
        [padded(s[0].doc_plane, NBp_max, DOC_PLANE_PAD) for s in shards]
    )
    stacked["blk_term_off"] = np.stack(
        [np.asarray(s[0].blk_term_off) for s in shards]
    )
    # impact-segment CSR: padded segments are empty (len 0) and every probe
    # is bounded by its term's seg_term_off slice, so padding is unreachable
    stacked["seg_term_off"] = np.stack(
        [np.asarray(s[0].seg_term_off) for s in shards]
    )
    stacked["seg_pos"] = np.stack([padded(s[0].seg_pos, NS_max, 0) for s in shards])
    stacked["seg_len"] = np.stack([padded(s[0].seg_len, NS_max, 0) for s in shards])
    stacked["tp_rects"] = np.stack(
        [
            padded(s[1].tp_rects, T_max, 0.0) for s in shards
        ]
    )
    # make padded toe prints empty rects
    for i, s in enumerate(shards):
        t = s[1].tp_rects.shape[0]
        stacked["tp_rects"][i, t:] = geometry.EMPTY_RECT
    stacked["tp_amps"] = np.stack([padded(s[1].tp_amps, T_max, 0.0) for s in shards])
    stacked["tp_doc_ids"] = np.stack(
        [padded(s[1].tp_doc_ids, T_max, 0) for s in shards]
    )
    # int8 amp scales: pad with 1.0 (decode of zero-padded amps stays 0)
    stacked["tp_amp_scale"] = np.stack(
        [padded(s[1].tp_amp_scale, SB_max, 1.0) for s in shards]
    )
    # the pruned sweep's 32-bit planes, of the padded store each shard
    # view serves
    stacked["tp_planes"] = np.stack([
        np.asarray(pruned_store_planes(
            jnp.asarray(stacked["tp_rects"][i]), jnp.asarray(stacked["tp_amps"][i]),
            jnp.asarray(stacked["tp_amp_scale"][i]), block_size,
        ))
        for i in range(len(shards))
    ])
    stacked["tile_starts"] = np.stack([np.asarray(s[1].tile_starts) for s in shards])
    stacked["tile_ends"] = np.stack([np.asarray(s[1].tile_ends) for s in shards])
    stacked["doc_rects"] = np.stack(
        [padded(s[1].doc_rects, N_max, 0.0) for s in shards]
    )
    for i, s in enumerate(shards):
        n = s[1].doc_rects.shape[0]
        stacked["doc_rects"][i, n:] = geometry.EMPTY_RECT
    stacked["doc_amps"] = np.stack([padded(s[1].doc_amps, N_max, 0.0) for s in shards])
    stacked["doc_mbr"] = np.stack([padded(s[1].doc_mbr, N_max, 0.0) for s in shards])
    stacked["doc_mass"] = np.stack([padded(s[1].doc_mass, N_max, 0.0) for s in shards])
    # block-max columns: zero-padded blocks have ub == 0 → always skipped
    NB_max = max(s[1].blk_mbr.shape[0] for s in shards)
    stacked["blk_mbr"] = np.stack([padded(s[1].blk_mbr, NB_max, 0.0) for s in shards])
    stacked["blk_max_amp"] = np.stack(
        [padded(s[1].blk_max_amp, NB_max, 0.0) for s in shards]
    )
    stacked["blk_max_mass"] = np.stack(
        [padded(s[1].blk_max_mass, NB_max, 0.0) for s in shards]
    )
    stacked["pagerank"] = np.stack([padded(s[2], N_max, 0.0) for s in shards])
    # local→global docID translation table
    gid = np.stack([padded(s[3].astype(np.int32), N_max, -1) for s in shards])
    stacked["doc_offset"] = gid  # [S, N] full map (name kept for pytree stability)

    return ShardedGeoIndex(
        postings=jnp.asarray(stacked["postings"]),
        impacts=jnp.asarray(stacked["impacts"]),
        offsets=jnp.asarray(stacked["offsets"]),
        post_packed=jnp.asarray(stacked["post_packed"]),
        blk_first=jnp.asarray(stacked["blk_first"]),
        blk_bits=jnp.asarray(stacked["blk_bits"]),
        blk_word_off=jnp.asarray(stacked["blk_word_off"]),
        blk_n_exc=jnp.asarray(stacked["blk_n_exc"]),
        blk_len=jnp.asarray(stacked["blk_len"]),
        blk_pos=jnp.asarray(stacked["blk_pos"]),
        blk_max_impact=jnp.asarray(stacked["blk_max_impact"]),
        imp_plane=jnp.asarray(stacked["imp_plane"]),
        doc_plane=jnp.asarray(stacked["doc_plane"]),
        blk_term_off=jnp.asarray(stacked["blk_term_off"]),
        seg_term_off=jnp.asarray(stacked["seg_term_off"]),
        seg_pos=jnp.asarray(stacked["seg_pos"]),
        seg_len=jnp.asarray(stacked["seg_len"]),
        tp_rects=jnp.asarray(stacked["tp_rects"]),
        tp_amps=jnp.asarray(stacked["tp_amps"]),
        tp_doc_ids=jnp.asarray(stacked["tp_doc_ids"]),
        tp_amp_scale=jnp.asarray(stacked["tp_amp_scale"]),
        tp_planes=jnp.asarray(stacked["tp_planes"]),
        tile_starts=jnp.asarray(stacked["tile_starts"]),
        tile_ends=jnp.asarray(stacked["tile_ends"]),
        doc_rects=jnp.asarray(stacked["doc_rects"]),
        doc_amps=jnp.asarray(stacked["doc_amps"]),
        doc_mbr=jnp.asarray(stacked["doc_mbr"]),
        doc_mass=jnp.asarray(stacked["doc_mass"]),
        blk_mbr=jnp.asarray(stacked["blk_mbr"]),
        blk_max_amp=jnp.asarray(stacked["blk_max_amp"]),
        blk_max_mass=jnp.asarray(stacked["blk_max_mass"]),
        pagerank=jnp.asarray(stacked["pagerank"]),
        doc_offset=jnp.asarray(gid),
        coverage_sat=jnp.asarray(np.stack(coverage)),
        grid=grid,
        n_terms=n_terms,
        block_size=shards[0][1].block_size,
        coverage_grid=COVERAGE_GRID,
        max_term_blocks=max(s[0].max_term_blocks for s in shards),
        layout=layout,
        max_term_segments=max(s[0].max_term_segments for s in shards),
    )


def sharded_index_specs(
    doc_axes: tuple[str, ...],
    grid: int,
    n_terms: int,
    block_size: int = 128,
    coverage_grid: int = COVERAGE_GRID,
    max_term_blocks: int = 1,
    layout: str = "docid",
    max_term_segments: int = 1,
) -> ShardedGeoIndex:
    """PartitionSpecs for every field (leading dim over the doc axes)."""
    lead = P(doc_axes)
    return ShardedGeoIndex(
        postings=lead, impacts=lead, offsets=lead,
        post_packed=lead, blk_first=lead, blk_bits=lead, blk_len=lead,
        blk_word_off=lead, blk_n_exc=lead, blk_pos=lead, blk_max_impact=lead,
        imp_plane=lead, doc_plane=lead, blk_term_off=lead, seg_term_off=lead,
        seg_pos=lead, seg_len=lead,
        tp_rects=lead, tp_amps=lead, tp_doc_ids=lead, tp_amp_scale=lead,
        tp_planes=lead, tile_starts=lead, tile_ends=lead,
        doc_rects=lead, doc_amps=lead, doc_mbr=lead, doc_mass=lead,
        blk_mbr=lead, blk_max_amp=lead, blk_max_mass=lead,
        pagerank=lead, doc_offset=lead, coverage_sat=lead,
        grid=grid, n_terms=n_terms, block_size=block_size,
        coverage_grid=coverage_grid, max_term_blocks=max_term_blocks,
        layout=layout, max_term_segments=max_term_segments,
    )


def make_serve_fn(
    mesh: Mesh,
    budgets: alg.QueryBudgets,
    weights: ranking.RankWeights = ranking.RankWeights(),
    doc_axes: tuple[str, ...] = ("data",),
    query_axis: str = "model",
    algorithm: str = "k_sweep",
    grid: int = 64,
    n_terms: int = 0,
    fused: bool = False,
    block_size: int = 128,
    with_stats: bool = False,
    with_routing: bool = False,
    max_term_blocks: int = 1,
    layout: str = "docid",
    max_term_segments: int = 1,
):
    """Build the jit'd distributed serve step for a mesh.

    Returns ``serve(index: ShardedGeoIndex, query: QueryBatch)
    -> (ids i32[B, k], scores f32[B, k])`` with global docIDs.
    ``fused=True`` routes k_sweep through the Pallas fused (and, with
    ``budgets.prune``, block-max pruned) sweep kernel on every shard.

    ``with_stats=True`` additionally returns the per-query byte-counter
    dict *measured inside the step*: each shard's per-stage counters are
    summed over the doc axes with ``psum`` (k·S-independent — one scalar
    vector per query rides the existing collective phase), so serving
    reports see exact mesh traffic instead of a host-side capacity model.

    ``with_routing=True`` (requires ``with_stats``) turns on footprint
    routing inside the step: each shard tests the batch's footprints
    against its coverage SAT; a shard no query touches is *masked* — its
    local results are forced to (−1, −inf) and its counters zeroed before
    the psum, so merged outputs and counters are exactly what a host loop
    that skipped the shard would produce.  Counter masking is batch-level
    (a shard any query touches counts its whole batch, matching the host
    executor's visit accounting); result masking is per-query.  Two stat
    keys are added: ``shards_touched`` (per query — shards its footprints
    reach) and ``shards_visited`` (per batch — shards any query reaches).
    """
    if with_routing and not with_stats:
        raise ValueError("with_routing requires with_stats=True")
    fn = alg.get_algorithm(algorithm)
    if algorithm in ("k_sweep", "text_first") and fused:
        from functools import partial as _partial

        fn = _partial(fn, fused=True)
    idx_specs = sharded_index_specs(
        doc_axes, grid, n_terms, block_size, max_term_blocks=max_term_blocks,
        layout=layout, max_term_segments=max_term_segments,
    )
    q_spec = alg.QueryBatch(
        terms=P(query_axis), rects=P(query_axis), amps=P(query_axis)
    )
    # tree-prefix specs: the trailing P broadcasts over the stats dict
    out_spec = (
        (P(query_axis), P(query_axis), P(query_axis))
        if with_stats
        else (P(query_axis), P(query_axis))
    )

    def local_index(idx: ShardedGeoIndex) -> tuple[GeoIndex, jax.Array]:
        text = TextIndex(
            postings=idx.postings[0], impacts=idx.impacts[0], offsets=idx.offsets[0],
            bitmaps=jnp.zeros((0, 4), jnp.uint32),
            bitmap_term_ids=jnp.zeros((0,), jnp.int32),
            post_packed=idx.post_packed[0], blk_first=idx.blk_first[0],
            blk_bits=idx.blk_bits[0], blk_len=idx.blk_len[0],
            blk_word_off=idx.blk_word_off[0], blk_n_exc=idx.blk_n_exc[0],
            blk_pos=idx.blk_pos[0],
            blk_max_impact=idx.blk_max_impact[0],
            imp_plane=idx.imp_plane[0],
            doc_plane=idx.doc_plane[0],
            blk_term_off=idx.blk_term_off[0],
            seg_term_off=idx.seg_term_off[0], seg_pos=idx.seg_pos[0],
            seg_len=idx.seg_len[0],
            n_docs=idx.doc_rects.shape[1], n_terms=idx.n_terms,
            max_term_blocks=idx.max_term_blocks,
            layout=idx.layout,
            max_term_segments=idx.max_term_segments,
        )
        spatial = SpatialIndex(
            tp_rects=idx.tp_rects[0], tp_amps=idx.tp_amps[0],
            tp_doc_ids=idx.tp_doc_ids[0], tp_amp_scale=idx.tp_amp_scale[0],
            tp_planes=idx.tp_planes[0],
            tile_starts=idx.tile_starts[0], tile_ends=idx.tile_ends[0],
            doc_rects=idx.doc_rects[0], doc_amps=idx.doc_amps[0],
            doc_mbr=idx.doc_mbr[0], doc_mass=idx.doc_mass[0],
            blk_mbr=idx.blk_mbr[0], blk_max_amp=idx.blk_max_amp[0],
            blk_max_mass=idx.blk_max_mass[0],
            grid=idx.grid, n_docs=idx.doc_rects.shape[1],
            block_size=idx.block_size,
        )
        local = GeoIndex(text=text, spatial=spatial, pagerank=idx.pagerank[0])
        return local, idx.doc_offset[0]

    def shard_touch(idx: ShardedGeoIndex, query: alg.QueryBatch) -> jax.Array:
        """Footprint routing test against this shard's coverage SAT: bool[B].

        Mirrors :func:`footprint_touch_np` (same clamped-floor cell mapping
        as :func:`repro.core.planner.coarse_cells`) for one shard in-jit.
        """
        sat = idx.coverage_sat[0]
        cg = idx.coverage_grid
        g = float(cg)
        rects = query.rects
        ix0 = jnp.clip(jnp.floor(rects[..., 0] * g).astype(jnp.int32), 0, cg - 1)
        iy0 = jnp.clip(jnp.floor(rects[..., 1] * g).astype(jnp.int32), 0, cg - 1)
        ix1 = jnp.clip(jnp.floor(rects[..., 2] * g).astype(jnp.int32), 0, cg - 1)
        iy1 = jnp.clip(jnp.floor(rects[..., 3] * g).astype(jnp.int32), 0, cg - 1)
        valid = (
            (rects[..., 2] > rects[..., 0])
            & (rects[..., 3] > rects[..., 1])
            & (query.amps > 0)
        )  # [B, R]
        cover = (
            sat[iy1 + 1, ix1 + 1] - sat[iy0, ix1 + 1] - sat[iy1 + 1, ix0] + sat[iy0, ix0]
        )  # [B, R]
        return jnp.any((cover > 0) & valid, axis=-1)

    def shard_body(idx: ShardedGeoIndex, query: alg.QueryBatch):
        local, gid_map = local_index(idx)
        res = fn(local.text, local.spatial, local.pagerank, query, budgets, weights)
        # local → global docIDs
        k = res.ids.shape[-1]
        safe = jnp.clip(res.ids, 0, gid_map.shape[0] - 1)
        gids = jnp.where(res.ids >= 0, gid_map[safe], -1)
        scores = jnp.where(res.ids >= 0, res.scores, -jnp.inf)
        if with_routing:
            # mask untouched (query, shard) pairs before the merge: their
            # contribution becomes structurally empty (provably it already
            # was — require_geo scores a non-overlapping shard −inf)
            touch = shard_touch(idx, query)  # [B]
            gids = jnp.where(touch[:, None], gids, -1)
            scores = jnp.where(touch[:, None], scores, -jnp.inf)
        # hierarchical top-k merge over doc axes (innermost first = intra-pod)
        for ax in reversed(doc_axes):
            g_ids = jax.lax.all_gather(gids, ax)  # [n_ax, B, k]
            g_scores = jax.lax.all_gather(scores, ax)
            n_ax = g_ids.shape[0]
            g_ids = jnp.moveaxis(g_ids, 0, -2).reshape(*gids.shape[:-1], n_ax * k)
            g_scores = jnp.moveaxis(g_scores, 0, -2).reshape(
                *scores.shape[:-1], n_ax * k
            )
            scores, sel = jax.lax.top_k(g_scores, k)
            gids = jnp.take_along_axis(g_ids, sel, axis=-1)
        if with_stats:
            # exact per-query counters: sum each shard's measured stats
            # over the doc axes (every query executed on every shard)
            raw = res.stats
            if with_routing:
                # batch-level visit accounting: a shard counts its whole
                # batch iff any query touches it — exactly the host loop's
                # skip semantics, so host and mesh counters stay equal
                visited = jnp.any(touch)
                raw = {
                    key: jnp.where(visited, v, jnp.zeros_like(v))
                    for key, v in raw.items()
                }
            stats = {key: jax.lax.psum(v, doc_axes) for key, v in raw.items()}
            if with_routing:
                stats["shards_touched"] = jax.lax.psum(
                    touch.astype(jnp.float32), doc_axes
                )
                # [1] not scalar: stats ride the P(query_axis) out_spec,
                # so each query-shard contributes its own visit count
                stats["shards_visited"] = jax.lax.psum(
                    jnp.any(touch).astype(jnp.float32)[None], doc_axes
                )
            return gids, scores, stats
        return gids, scores

    mapped = shard_map(
        shard_body,
        mesh=mesh,
        in_specs=(idx_specs, q_spec),
        out_specs=out_spec,
        check_vma=False,
    )
    return jax.jit(mapped)
