"""The vectorised host builders against the per-document loop builders
they replaced.

The loop builders below are the reference (kept only here): for the same
input, ``build_text_index_np`` (both layouts, every compression mode),
``pack_postings_np``, ``global_idf_np`` and ``build_spatial_index_np``
must return arrays equal to theirs, array for array.
"""
import numpy as np
import pytest
import jax.numpy as jnp

from repro.core import geometry
from repro.core import spatial_index as sidx
from repro.core import text_index as tidx
from repro.core.footprint import footprint_mbr_np
from repro.core.spatial_index import (
    INVALID,
    SCALE_BLOCK,
    SpatialIndex,
    _id_dtype,
    block_metadata_np,
    normalize_compress,
    quantize_amps_np,
)
from repro.core.text_index import (
    BLOCK,
    PFOR_HIGH_BITS,
    PFOR_SLOT_BITS,
    POSTING_BLOCK,
    WORDS_PER_BLOCK,
    TextIndex,
    _empty_pack,
    _trivial_segments_np,
    impact_levels_np,
)
from repro.corpus import make_corpus
from repro.kernels.sweep_score.ops import pruned_store_planes


# ---------------------------------------------------------------------------
# reference: the per-document / per-term / per-tile loop builders
# ---------------------------------------------------------------------------

def _block_max_ref(
    impacts: np.ndarray, blk_pos: np.ndarray, blk_len: np.ndarray
) -> np.ndarray:
    """Per-block max of the *stored* impacts, decoded to f32 — f32[NB].

    Computed from the stored (possibly f16-quantized) values so the bound
    stays an upper bound after lossy compression: round-to-nearest can
    round a value *up*, so a max taken pre-quantization would be unsafe.
    Empty blocks get 0.0 (vacuous — no posting ever reads their bound).
    """
    NB = blk_pos.shape[0]
    out = np.zeros((NB,), np.float32)
    P = int(np.sum(blk_len))
    if P > 0:
        # blocks tile the CSR contiguously and in order in both layouts,
        # so posting p belongs to the block repeated at position p
        bid = np.repeat(np.arange(NB), blk_len)
        np.maximum.at(out, bid, np.asarray(impacts[:P]).astype(np.float32))
    return out



def _impact_plane_ref(impacts, blk_pos, blk_len):
    """Block b's impacts decoded to f32 in row b, zero-padded past its
    length."""
    plane = np.zeros((blk_pos.shape[0], POSTING_BLOCK), np.float32)
    for b, (p, n) in enumerate(zip(blk_pos, blk_len)):
        plane[b, :n] = impacts[p : p + n]
    return plane


def _doc_plane_ref(postings, blk_pos, blk_len):
    """Block b's doc ids in row b, DOC_PLANE_PAD past its length."""
    plane = np.full((blk_pos.shape[0], POSTING_BLOCK), tidx.DOC_PLANE_PAD, np.int32)
    for b, (p, n) in enumerate(zip(blk_pos, blk_len)):
        plane[b, :n] = postings[p : p + n]
    return plane


def _pfor_width_ref(real_deltas: np.ndarray) -> tuple[int, int]:
    """Pick a block's PForDelta base width — ``(bits, n_exc)``.

    Minimizes total stored words: ``ceil(len·bits/32)`` tail-trimmed base
    words plus one exception word per delta exceeding the base width.
    The floor ``bits ≥ bit_length(max) − PFOR_HIGH_BITS`` keeps every
    exception's high bits inside one 24-bit patch field; ties break
    toward the wider base (fewer exceptions → cheaper decode).
    """
    n = len(real_deltas)
    maxbits = max(int(real_deltas.max(initial=0)).bit_length(), 1)
    best_bits, best_exc, best_words = maxbits, 0, max(-(-n * maxbits // 32), 1)
    for width in range(max(1, maxbits - PFOR_HIGH_BITS), maxbits):
        n_exc = int(np.count_nonzero(real_deltas >> width))
        words = max(-(-n * width // 32), 1) + n_exc
        if words < best_words:
            best_bits, best_exc, best_words = width, n_exc, words
    return best_bits, best_exc



def _pack_postings_ref(
    postings: np.ndarray,
    offsets: np.ndarray,
    impacts: np.ndarray | None = None,
) -> dict[str, np.ndarray]:
    """Delta + bit-pack each term's posting slice into 128-posting blocks.

    Blocks never straddle terms; within a block the first element stores
    delta 0 (its doc id lives in ``blk_first``) and subsequent deltas are
    strictly ≥ 1 (postings are sorted unique doc ids within a term).
    Framing is PForDelta: each block picks the total-word-minimizing base
    width (``_pfor_width_ref``) and stores ``ceil(len·bits/32)``
    tail-trimmed base words holding every delta's low ``bits`` bits,
    followed by one patch word per delta that overflows the base width —
    ``slot | high_bits << PFOR_SLOT_BITS``.  The tail padding a ragged
    last block would need is not materialized (``blk_word_off`` is
    explicit, so blocks are variable-width), which is what makes short
    posting lists actually compress.  Decoded slots past ``blk_len`` are
    therefore garbage (they read into the exception words or the next
    block) and every consumer masks them before trusting membership.

    When ``impacts`` is given (the *stored*, possibly quantized, values)
    the dict additionally carries ``blk_max_impact`` — the per-block score
    upper bound driving the pruned traversal (see
    :func:`block_max_impacts_np` for why it must be computed
    post-quantization).
    """
    M = len(offsets) - 1
    blk_term_off = np.zeros((M + 1,), np.int32)
    firsts: list[int] = []
    bits_l: list[int] = []
    lens: list[int] = []
    poss: list[int] = []
    word_off: list[int] = []
    n_exc_l: list[int] = []
    chunks: list[np.ndarray] = []
    w = 0
    j64 = np.arange(POSTING_BLOCK, dtype=np.int64)
    for t in range(M):
        lo, hi = int(offsets[t]), int(offsets[t + 1])
        nb = (hi - lo + POSTING_BLOCK - 1) // POSTING_BLOCK
        blk_term_off[t + 1] = blk_term_off[t] + nb
        for b in range(nb):
            s = lo + b * POSTING_BLOCK
            e = min(s + POSTING_BLOCK, hi)
            ids = postings[s:e].astype(np.int64)
            deltas = np.ones((POSTING_BLOCK,), np.int64)
            deltas[0] = 0
            deltas[1:e - s] = np.diff(ids)
            real = deltas[: e - s]
            bits, n_exc = _pfor_width_ref(real)
            low = deltas & ((np.int64(1) << bits) - 1)
            nw = (POSTING_BLOCK * bits) // 32  # 128·bits/32 = 4·bits exactly
            buf = np.zeros((nw,), np.uint64)
            bitpos = j64 * bits
            wi = bitpos >> 5
            off = (bitpos & 31).astype(np.uint64)
            lo64 = low.astype(np.uint64) << off
            np.bitwise_or.at(buf, wi, lo64 & np.uint64(0xFFFFFFFF))
            spill = lo64 >> np.uint64(32)
            # a nonzero spill always lands inside the block (the last delta
            # ends exactly at the block's word boundary), so the clamp only
            # ever redirects zero-valued ORs
            np.bitwise_or.at(buf, np.minimum(wi + 1, nw - 1), spill)
            # store only the words real postings reach: a ragged last block
            # keeps ceil(len·bits/32) words instead of the full 4·bits
            nw_t = max(-(-(e - s) * bits // 32), 1)
            words = buf[:nw_t].astype(np.uint32)
            if n_exc:
                slots = np.flatnonzero(real >> bits).astype(np.uint32)
                high = (real[slots] >> bits).astype(np.uint32)
                words = np.concatenate(
                    [words, slots | (high << np.uint32(PFOR_SLOT_BITS))]
                )
            chunks.append(words)
            firsts.append(int(ids[0]))
            bits_l.append(bits)
            lens.append(e - s)
            poss.append(s)
            word_off.append(w)
            n_exc_l.append(n_exc)
            w += nw_t + n_exc
    if not firsts:  # empty posting store: one degenerate empty block
        chunks.append(np.zeros((4,), np.uint32))
        firsts, bits_l, lens, poss, word_off = [0], [1], [0], [0], [0]
        n_exc_l = [0]
    out = dict(
        post_packed=np.concatenate(chunks),
        blk_first=np.asarray(firsts, np.int32),
        blk_bits=np.asarray(bits_l, np.int32),
        blk_len=np.asarray(lens, np.int32),
        blk_word_off=np.asarray(word_off, np.int32),
        blk_pos=np.asarray(poss, np.int32),
        blk_term_off=blk_term_off,
        blk_n_exc=np.asarray(n_exc_l, np.int32),
    )
    if impacts is not None:
        out["blk_max_impact"] = _block_max_ref(
            impacts, out["blk_pos"], out["blk_len"]
        )
    return out



def _impact_order_ref(
    postings: np.ndarray, impacts: np.ndarray, offsets: np.ndarray
) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Reorder each term's slice into descending-impact-level segments.

    Returns ``(postings, impacts, seg_term_off, seg_pos, seg_len)`` — the
    reordered columns plus the segment CSR.  Within each segment doc ids
    ascend (sort key ``(level, docid)``), so delta coding still applies;
    segments tile each term's CSR slice contiguously.
    """
    lvl = impact_levels_np(impacts)
    M = len(offsets) - 1
    post2 = postings.copy()
    imp2 = impacts.copy()
    seg_term_off = np.zeros((M + 1,), np.int32)
    seg_pos_l: list[int] = []
    seg_len_l: list[int] = []
    for t in range(M):
        lo, hi = int(offsets[t]), int(offsets[t + 1])
        ns = 0
        if hi > lo:
            order = np.lexsort((postings[lo:hi], lvl[lo:hi]))
            post2[lo:hi] = postings[lo:hi][order]
            imp2[lo:hi] = impacts[lo:hi][order]
            lv = lvl[lo:hi][order]
            starts = np.flatnonzero(np.r_[True, lv[1:] != lv[:-1]])
            ends = np.r_[starts[1:], hi - lo]
            for a, b in zip(starts, ends):
                seg_pos_l.append(lo + int(a))
                seg_len_l.append(int(b - a))
            ns = len(starts)
        seg_term_off[t + 1] = seg_term_off[t] + ns
    if not seg_pos_l:  # empty store: one degenerate empty segment
        seg_pos_l, seg_len_l = [0], [0]
    return (
        post2, imp2, seg_term_off,
        np.asarray(seg_pos_l, np.int32), np.asarray(seg_len_l, np.int32),
    )



def _suffix_max_ref(
    blk_max: np.ndarray, blk_term_off: np.ndarray
) -> np.ndarray:
    """Per-term suffix-max envelope of block maxima — f32[NB].

    ``out[b] = max(blk_max[b : term_end])`` within each term's block run:
    a safe upper bound for block ``b`` that is monotone non-increasing
    along the run, which is what lets the pruned kernel early-exit.
    """
    out = np.asarray(blk_max, np.float32).copy()
    for t in range(len(blk_term_off) - 1):
        b0, b1 = int(blk_term_off[t]), int(blk_term_off[t + 1])
        if b1 > b0:
            out[b0:b1] = np.maximum.accumulate(out[b0:b1][::-1])[::-1]
    return out



def _build_text_index_ref(
    doc_terms: list[np.ndarray],
    n_terms: int,
    n_bitmap_terms: int = 0,
    idf: np.ndarray | None = None,
    compress: bool = False,
    impact_dtype: np.dtype | str | None = None,
    layout: str = "docid",
) -> TextIndex:
    """Build from per-doc term-id arrays (with repetitions = frequencies).

    Pure-numpy index construction (host side, analogous to the paper's
    offline index build).  ``idf`` overrides the collection IDF — shard
    builders pass the *corpus-global* IDF (:func:`global_idf_np`) so each
    posting's impact is rounded to f32 exactly once from statistics that
    do not depend on the partitioning, making per-doc scores bit-identical
    across shard layouts (the routing equivalence gate relies on this).

    ``impact_dtype`` lossy-compresses the impact column at build time (the
    one compression entry point — ``normalize_compress`` modes pass f16
    here), so ``blk_max_impact`` is computed from the values that are
    actually stored and the pruning bound survives quantization.

    ``layout`` selects the posting order: ``"docid"`` (ascending doc ids,
    the bit-identical reference) or ``"impact"`` (descending
    quantized-impact segments per term — see the module docstring).
    Impact ordering happens *after* quantization so segments group the
    stored values, and block framing restarts at segment boundaries.
    """
    if layout not in ("docid", "impact"):
        raise ValueError(f"unknown posting layout: {layout!r}")
    n_docs = len(doc_terms)
    # term frequencies per doc, collection document frequencies
    doc_ids_per_term: list[list[int]] = [[] for _ in range(n_terms)]
    freq_per_term: list[list[int]] = [[] for _ in range(n_terms)]
    doc_len = np.zeros((n_docs,), dtype=np.float64)
    for d, terms in enumerate(doc_terms):
        doc_len[d] = max(len(terms), 1)
        uniq, counts = np.unique(terms, return_counts=True)
        for w, c in zip(uniq, counts):
            doc_ids_per_term[int(w)].append(d)
            freq_per_term[int(w)].append(int(c))

    df = np.array([len(x) for x in doc_ids_per_term], dtype=np.float64)
    if idf is None:
        idf = np.log(1.0 + n_docs / np.maximum(df, 1.0))

    offsets = np.zeros((n_terms + 1,), dtype=np.int32)
    offsets[1:] = np.cumsum([len(x) for x in doc_ids_per_term])
    P = int(offsets[-1])
    postings = np.zeros((P,), dtype=np.int32)
    impacts = np.zeros((P,), dtype=np.float32)
    for w in range(n_terms):
        lo, hi = offsets[w], offsets[w + 1]
        if hi == lo:
            continue
        ids = np.asarray(doc_ids_per_term[w], dtype=np.int32)
        fr = np.asarray(freq_per_term[w], dtype=np.float64)
        order = np.argsort(ids)
        postings[lo:hi] = ids[order]
        imp = idf[w] * (1.0 + np.log(fr[order])) / np.sqrt(doc_len[ids[order]])
        impacts[lo:hi] = imp.astype(np.float32)

    # block bitmaps for the most frequent terms
    n_blocks = (n_docs + BLOCK - 1) // BLOCK
    n_words = n_blocks * WORDS_PER_BLOCK
    if n_bitmap_terms > 0:
        top_terms = np.argsort(-df)[:n_bitmap_terms].astype(np.int32)
        bitmaps = np.zeros((n_bitmap_terms, n_words), dtype=np.uint32)
        for row, w in enumerate(top_terms):
            lo, hi = offsets[w], offsets[w + 1]
            ids = postings[lo:hi]
            words = ids // 32
            bits = (ids % 32).astype(np.uint32)
            np.bitwise_or.at(bitmaps[row], words, np.uint32(1) << bits)
    else:
        top_terms = np.zeros((0,), dtype=np.int32)
        bitmaps = np.zeros((0, n_words), dtype=np.uint32)

    if impact_dtype is not None:
        impacts = impacts.astype(impact_dtype)
    if layout == "impact":
        postings, impacts, seg_term_off, seg_pos, seg_len = _impact_order_ref(
            postings, impacts, offsets
        )
        seg = dict(seg_term_off=seg_term_off, seg_pos=seg_pos, seg_len=seg_len)
        # frame blocks over *segments* (blocks never straddle a segment):
        # segments tile each term's CSR slice contiguously and in order,
        # so segment ends are a valid CSR over the whole posting store
        NS = int(seg_term_off[-1])
        frame_off = np.zeros((NS + 1,), np.int64)
        frame_off[1:] = (seg_pos[:NS] + seg_len[:NS]).astype(np.int64)
    else:
        seg = _trivial_segments_np(n_terms)
        frame_off = offsets
    if compress:
        pack = _pack_postings_ref(postings, frame_off, impacts=impacts)
        pack["doc_plane"] = _doc_plane_ref(postings, pack["blk_pos"], pack["blk_len"])
        postings = np.zeros((0,), np.int32)  # packed words are the store
    else:
        pack = _empty_pack(frame_off)
        pack["doc_plane"] = np.zeros((0, POSTING_BLOCK), np.int32)
        pack["blk_max_impact"] = _block_max_ref(
            impacts, pack["blk_pos"], pack["blk_len"]
        )
    if layout == "impact":
        # collapse the per-segment block CSR back to per-term, and widen
        # the exact block maxima into the per-term suffix-max envelope —
        # the monotone bound the early-exiting pruned traversal needs
        pack["blk_term_off"] = pack["blk_term_off"][seg["seg_term_off"]]
        pack["blk_max_impact"] = _suffix_max_ref(
            pack["blk_max_impact"], pack["blk_term_off"]
        )
    pack["imp_plane"] = _impact_plane_ref(impacts, pack["blk_pos"], pack["blk_len"])
    term_blocks = np.diff(pack["blk_term_off"])
    term_segments = np.diff(seg["seg_term_off"])
    return TextIndex(
        postings=jnp.asarray(postings),
        impacts=jnp.asarray(impacts),
        offsets=jnp.asarray(offsets),
        bitmaps=jnp.asarray(bitmaps),
        bitmap_term_ids=jnp.asarray(top_terms),
        **{k: jnp.asarray(v) for k, v in pack.items()},
        **{k: jnp.asarray(v) for k, v in seg.items()},
        n_docs=n_docs,
        n_terms=n_terms,
        max_term_blocks=int(max(term_blocks.max(initial=0), 1)),
        layout=layout,
        max_term_segments=int(max(term_segments.max(initial=0), 1)),
    )



def _build_spatial_index_ref(
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

    # Tile grid: toe-print IDs intersecting each tile, compressed to m intervals.
    tile_starts = np.full((grid * grid, m_intervals), INVALID, dtype=np.int32)
    tile_ends = np.full((grid * grid, m_intervals), INVALID, dtype=np.int32)

    # enumerate (tile, toeprint) pairs
    x0, y0, x1, y1 = geometry.rect_cell_bounds_np(rects, grid)
    tile_lists: dict[int, list[int]] = {}
    for t in range(T):
        for ty in range(y0[t], y1[t] + 1):
            base = ty * grid
            for tx in range(x0[t], x1[t] + 1):
                tile_lists.setdefault(base + tx, []).append(t)

    for tile, ids in tile_lists.items():
        ivs = _coalesce_to_m_ref(np.asarray(ids, dtype=np.int64), m_intervals)
        for j, (s, e) in enumerate(ivs):
            tile_starts[tile, j] = s
            tile_ends[tile, j] = e

    # doc-major mirrors
    mbr = np.stack([footprint_mbr_np(doc_rects[i]) for i in range(N)], axis=0)
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



def _coalesce_to_m_ref(ids: np.ndarray, m: int) -> list[tuple[int, int]]:
    """Cover sorted toe-print IDs with ≤ m [start, end) intervals.

    Greedy-optimal: cut at the m−1 largest gaps (minimizes covered slack).
    """
    if len(ids) == 0:
        return []
    ids = np.unique(ids)
    if len(ids) == 1:
        return [(int(ids[0]), int(ids[0]) + 1)]
    gaps = np.diff(ids)
    n_cuts = min(m - 1, len(gaps))
    if n_cuts > 0:
        cut_pos = np.argsort(-gaps, kind="stable")[:n_cuts]
        # only cut where the gap is > 1 (else no benefit)
        cut_pos = cut_pos[gaps[cut_pos] > 1]
        cut_pos = np.sort(cut_pos)
    else:
        cut_pos = np.array([], dtype=np.int64)
    bounds = np.concatenate([[-1], cut_pos, [len(ids) - 1]])
    out = []
    for i in range(len(bounds) - 1):
        s = int(ids[bounds[i] + 1])
        e = int(ids[bounds[i + 1]]) + 1
        out.append((s, e))
    return out




def _global_idf_ref(doc_terms, n_terms):
    df = np.zeros((n_terms,), dtype=np.float64)
    for terms in doc_terms:
        np.add.at(df, np.unique(terms), 1.0)
    return np.log(1.0 + len(doc_terms) / np.maximum(df, 1.0))


# ---------------------------------------------------------------------------
# equivalence
# ---------------------------------------------------------------------------


def _assert_same_index(got, want):
    for f in got.__dataclass_fields__:
        a, b = getattr(got, f), getattr(want, f)
        if hasattr(a, "shape"):
            a, b = np.asarray(a), np.asarray(b)
            assert a.dtype == b.dtype, f
            np.testing.assert_array_equal(a, b, err_msg=f)
        else:
            assert a == b, f


def _ragged_docs(seed, n_docs, n_terms):
    """Variable-length docs with repeats, empty docs and unused terms."""
    rng = np.random.default_rng(seed)
    docs = []
    for _ in range(n_docs):
        n = int(rng.integers(0, 40))
        docs.append(np.minimum(rng.zipf(1.2, n) - 1, n_terms - 3).astype(np.int32))
    return docs


CORPORA = {
    "synth": lambda: make_corpus(n_docs=600, n_terms=150, seed=3).doc_terms,
    "ragged": lambda: _ragged_docs(5, 700, 90),
    # a hot term in every doc: multi-block lists, PForDelta exceptions
    "hot": lambda: [
        np.concatenate([[0, 0, 1], t]) for t in _ragged_docs(7, 1500, 40)
    ],
    "empty": lambda: [np.zeros((0,), np.int32) for _ in range(5)],
}


@pytest.mark.parametrize("layout", ["docid", "impact"])
@pytest.mark.parametrize("compress", ["none", "f16", "int8"])
@pytest.mark.parametrize("corpus", sorted(CORPORA))
def test_text_index_matches_loop_builder(corpus, compress, layout):
    docs = CORPORA[corpus]()
    n_terms = 150 if corpus == "synth" else 90 if corpus == "ragged" else 40
    kw = dict(
        compress=compress != "none",
        impact_dtype=np.float16 if compress != "none" else None,
        layout=layout,
        n_bitmap_terms=3,
    )
    _assert_same_index(
        tidx.build_text_index_np(docs, n_terms, **kw),
        _build_text_index_ref(docs, n_terms, **kw),
    )


@pytest.mark.parametrize("corpus", sorted(CORPORA))
def test_global_idf_matches_loop(corpus):
    docs = CORPORA[corpus]()
    np.testing.assert_array_equal(
        tidx.global_idf_np(docs, 150), _global_idf_ref(docs, 150)
    )


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_pack_postings_matches_loop(seed):
    """Random gaps up to 2^30 exercise every base width and the 24-bit
    patch bound; lengths straddle the 128-posting block."""
    rng = np.random.default_rng(seed)
    counts = rng.integers(0, 400, 12)
    offsets = np.zeros(13, np.int64)
    offsets[1:] = np.cumsum(counts)
    post = []
    for c in counts:
        gaps = np.where(
            rng.random(c) < 0.1, rng.integers(1, 2**30 // 400, c), rng.integers(1, 9, c)
        )
        post.append(np.cumsum(gaps))
    postings = np.concatenate(post).astype(np.int32)
    imp = rng.random(len(postings)).astype(np.float32)
    got = tidx.pack_postings_np(postings, offsets, impacts=imp)
    want = _pack_postings_ref(postings, offsets, impacts=imp)
    assert set(got) == set(want)
    for k in want:
        assert got[k].dtype == want[k].dtype, k
        np.testing.assert_array_equal(got[k], want[k], err_msg=k)


@pytest.mark.parametrize("chunk", [4, 7, 1 << 13])
def test_doc_plane_matches_loop(monkeypatch, chunk):
    """The plane assembled in fixed-size chunks (the last one overlapping
    its neighbour) equals the per-block loop, ragged and empty blocks
    included."""
    monkeypatch.setattr(tidx, "_PLANE_CHUNK", chunk)
    rng = np.random.default_rng(9)
    counts = rng.integers(0, 300, 11)
    counts[3] = 0
    offsets = np.zeros(12, np.int64)
    offsets[1:] = np.cumsum(counts)
    postings = rng.integers(0, 1 << 22, int(offsets[-1])).astype(np.int32)
    _, blk_pos, blk_len = tidx.logical_posting_blocks_np(offsets)
    got = np.asarray(tidx.doc_plane(postings, blk_pos, blk_len))
    assert got.dtype == np.int32
    np.testing.assert_array_equal(got, _doc_plane_ref(postings, blk_pos, blk_len))


@pytest.mark.parametrize("m_intervals", [1, 2, 3, 5])
@pytest.mark.parametrize("compress", ["none", "f16", "int8"])
@pytest.mark.parametrize("grid,n_docs,seed", [(16, 300, 0), (64, 500, 1), (128, 200, 2)])
def test_spatial_index_matches_loop_builder(grid, n_docs, seed, compress, m_intervals):
    c = make_corpus(n_docs=n_docs, n_terms=50, seed=seed)
    rects = c.doc_rects.copy()
    rects[::7, 0] = [0.3, 0.3, 0.3, 0.3]  # degenerate zero-area rects
    rects[::11] = geometry.EMPTY_RECT  # docs with no footprint
    got = sidx.build_spatial_index_np(
        rects, c.doc_amps, grid=grid, m_intervals=m_intervals, compress=compress
    )
    want = _build_spatial_index_ref(
        rects, c.doc_amps, grid=grid, m_intervals=m_intervals, compress=compress
    )
    _assert_same_index(got, want)


@pytest.mark.parametrize(
    "grid,n_docs,m", [(16, 300, 2), (64, 500, 3), (128, 200, 1), (200, 400, 2)]
)
def test_tile_interval_kernel_matches_host(grid, n_docs, m):
    """The TPU path of the tile table (Pallas kernel, interpret mode here)
    returns the host path's arrays."""
    from repro.kernels.tile_intervals.ops import tile_intervals

    c = make_corpus(n_docs=n_docs, n_terms=50, seed=grid)
    r = c.doc_rects.reshape(-1, 4)
    r = r[r[:, 2] > r[:, 0]]
    want = sidx.tile_intervals_np(r, grid, m)
    got = tile_intervals(*geometry.rect_cell_bounds_np(r, grid), grid, m, interpret=True)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)
