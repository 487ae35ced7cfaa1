"""Inverted text index: CSR posting arrays + impact scores + block bitmaps.

Layout (paper §II.B, adapted to HBM-resident fixed-shape arrays):

* ``postings i32[P]``  — docIDs, ascending within each term's slice.
* ``impacts  f32[P]``  — precomputed per-posting *impact* score: the term's
  full contribution to the lnc.ltc cosine of eq. (3),
  ``ln(1 + n/f_t) * (1 + ln f_{D,t}) / sqrt(|D|)``, so query-time text
  scoring is a pure gather+sum (quantizable to f16/int8; see ``quantize``).
* ``offsets  i32[M+1]`` — CSR slices: term w owns postings[offsets[w]:offsets[w+1]].
* block bitmaps: for the ``n_bitmap_terms`` most frequent terms, a packed
  u32 bitmap over ceil(N/128)*4 words marking which 128-doc *blocks*'
  documents contain the term — the TPU-idiomatic conjunction prefilter
  (AND + popcount; see kernels/bitmap_filter).

Membership probing at query time is a vectorized binary search
(``searchsorted``) into the term slice — the TPU analogue of DAAT list
merging.

Compressed posting storage (paper §II.B: "compressed index formats")
--------------------------------------------------------------------

``build_text_index_np(compress=True)`` replaces the raw ``postings i32[P]``
column with a delta + bit-packed store cut into 128-posting blocks that
never straddle a term slice:

* ``post_packed u32[W]`` — little-endian bit-packed doc-id deltas; each
  block is word-aligned and stores its deltas at a per-block *base* width
  ``blk_bits[b]`` (PForDelta framing, below), followed by
  ``blk_n_exc[b]`` exception words.
* ``blk_first/blk_bits/blk_len/blk_word_off/blk_pos i32[NB]`` — per-block
  first doc id, base bit width, valid count, start word, and absolute CSR
  position of the block's first posting (impacts stay CSR-addressed).
* ``blk_n_exc i32[NB]`` — PForDelta exception words per block.
* ``blk_term_off i32[M+1]`` — CSR of blocks per term.

PForDelta exception framing
---------------------------

Instead of one bit width per block sized by the *largest* delta (one
outlier gap inflates all 128 slots), each block picks the base width
minimizing total words: ``ceil(len·bits/32)`` base words (tail-trimmed)
plus one patch word per delta that does not fit.  A patch word packs
``slot | high_bits << 8`` — the slot index (< 128, 8 bits) and the bits
above the base width (≤ 24, enforced by ``bits ≥ bit_length(max) − 24``).
Decoding extracts the base bits, then replays the patch list; the
query path reads the decoded ids from ``doc_plane`` instead.  In practice
the chosen base width covers ~90% of deltas and the outliers ride in the
exception list.

Posting layouts (``build_text_index_np(layout=)``)
--------------------------------------------------

* ``"docid"`` (default) — postings ascend by doc id within each term
  slice; ``blk_max_impact`` is the exact per-block max.  This is the
  bit-identical correctness reference.
* ``"impact"`` — each term's postings are grouped into descending
  quantized-impact *segments* (:data:`IMPACT_LEVELS` global geometric
  levels), docID-ascending *within* a segment so delta + bit-packing
  still applies; blocks never straddle segments.  The segment CSR
  (``seg_term_off i32[M+1]``, ``seg_pos/seg_len i32[NS]``) drives the
  segment-aware membership probes.  ``blk_max_impact`` is the per-term
  *suffix-max envelope* of the exact block maxima — monotone
  non-increasing along each term's block run, so the pruned traversal
  (kernels/text_probe, ``monotone=True``) can early-exit a term the
  first time a block's bound drops below θ.  Scores are unchanged (same
  stored impacts, different order): top-k ids and scores match the
  docID layout exactly.

The *logical* 128-posting framing (``blk_term_off``/``blk_pos``/``blk_len``)
plus the block-max metadata ``blk_max_impact f32[NB]`` are built in BOTH
storage modes: they are the skip unit of the WAND-style pruned traversal
(kernels/text_probe), which is independent of how doc ids are stored.

Query-time probes binary-search the block heads (``blk_first``) and read
exactly one block per key as one row of the resident block-major doc-id
plane ``doc_plane i32[NB, POSTING_BLOCK]`` (:func:`decode_posting_blocks`),
built from the CSR doc ids with the index — the doc-id twin of
``imp_plane``.  A block is one 512-byte row gather there, where decoding
it from the packed words takes two element gathers per slot.  The packed
words stay the store of record and ``posting_bytes`` models them.
"""
from __future__ import annotations

import dataclasses
import functools
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.geometry import HOST_THREADS

BLOCK = 128  # docs per bitmap block
WORDS_PER_BLOCK = BLOCK // 32
POSTING_BLOCK = 128  # postings per delta/bit-pack compression block
# bytes per posting in the pruned probe's f32 impact plane (imp_plane)
PLANE_BYTES = 4
# doc_plane's value past a block's length: no doc id equals it
DOC_PLANE_PAD = -1
# PForDelta patch word: slot (8 bits, block slots < 128) | high_bits << 8
PFOR_SLOT_BITS = 8
PFOR_HIGH_BITS = 32 - PFOR_SLOT_BITS
# impact-ordered layout: global geometric quantization into this many
# descending levels; each level spans a RATIO-wide band of stored impacts.
# The ratio sets the pruning granularity — a θ cut can only drop whole
# trailing levels of a term, so levels must be fine enough that one term's
# impact spread (typically ~4×: the tf and length-norm factors) covers
# several of them.  1.2 gives ~8 levels across a 4× spread; 32 levels
# (~340× total dynamic range) covers the cross-term idf spread.
IMPACT_LEVELS = 32
IMPACT_LEVEL_RATIO = 1.2


@jax.tree_util.register_dataclass
@dataclass(frozen=True)
class TextIndex:
    """HBM-resident inverted index (a pytree of arrays)."""

    postings: jax.Array  # i32[P] docIDs ([0] when compressed — see post_packed)
    impacts: jax.Array  # f32[P] precomputed impact scores
    offsets: jax.Array  # i32[M+1]
    bitmaps: jax.Array  # u32[n_bitmap_terms, n_words]  (may be [0, n_words])
    bitmap_term_ids: jax.Array  # i32[n_bitmap_terms] term id per bitmap row
    # --- delta + bit-packed doc-id store ([0] when uncompressed) ---
    post_packed: jax.Array  # u32[W] packed deltas, word-aligned blocks
    blk_first: jax.Array  # i32[NB] first doc id per block
    blk_bits: jax.Array  # i32[NB] delta bit width per block
    # --- logical 128-posting block addressing (BOTH layouts: blocks never
    # straddle terms, so compressed and uncompressed share one framing) ---
    blk_len: jax.Array  # i32[NB] valid postings per block (≤ POSTING_BLOCK)
    blk_word_off: jax.Array  # i32[NB] start word in post_packed ([0] raw)
    blk_pos: jax.Array  # i32[NB] absolute CSR position of block's 1st posting
    blk_term_off: jax.Array  # i32[M+1] CSR of blocks per term
    # block-max impact metadata (both layouts; see block_max_impacts_np):
    # per-block max of the *stored* impacts, decoded to f32 — computed
    # post-quantization so WAND-style upper bounds stay safe under f16;
    # under layout="impact" this is the per-term suffix-max envelope
    # (monotone non-increasing along each term's block run)
    blk_max_impact: jax.Array  # f32[NB]
    # PForDelta exception words per block ([0] when uncompressed)
    blk_n_exc: jax.Array  # i32[NB]
    # block-major copy of the stored impacts decoded to f32, zero past
    # blk_len: the rows the pruned TEXT-FIRST probe copies block by block
    # (impact_plane_np)
    imp_plane: jax.Array  # f32[NB, POSTING_BLOCK]
    # block-major copy of the doc ids, DOC_PLANE_PAD past blk_len: the rows
    # every compressed-store reader gathers (doc_plane; [0, POSTING_BLOCK]
    # when uncompressed — the raw postings are read directly)
    doc_plane: jax.Array  # i32[NB, POSTING_BLOCK]
    # impact-ordered segment CSR (degenerate under layout="docid": the
    # probes never read it, so it stays one zero entry)
    seg_term_off: jax.Array  # i32[M+1] CSR of impact segments per term
    seg_pos: jax.Array  # i32[NS] absolute CSR position of segment start
    seg_len: jax.Array  # i32[NS] postings per segment
    n_docs: int = field(metadata=dict(static=True))
    n_terms: int = field(metadata=dict(static=True))
    # max blocks owned by any single term (static: sizes the pruned-probe
    # kernel's per-query block lattice)
    max_term_blocks: int = field(default=1, metadata=dict(static=True))
    # posting order: "docid" (ascending doc ids per term) or "impact"
    # (descending quantized-impact segments per term)
    layout: str = field(default="docid", metadata=dict(static=True))
    # max segments owned by any single term (static: bounds the
    # segment-aware probe loop; 1 under layout="docid")
    max_term_segments: int = field(default=1, metadata=dict(static=True))

    @property
    def n_postings(self) -> int:
        # impacts stay CSR-addressed in both layouts, so P comes from them
        return self.impacts.shape[0]

    @property
    def is_compressed(self) -> bool:
        return self.blk_first.shape[0] > 0

    @property
    def posting_bytes(self) -> float:
        """Modeled bytes per posting: doc id (+ block metadata) + impact.

        Uncompressed this is the classic ``4 + impact_itemsize`` (= 8 at
        f32); compressed it is the bit-packed words (base + PForDelta
        exception words) plus the 20 B/block of metadata (incl.
        ``blk_n_exc``) plus the (possibly quantized) impact, amortized per
        posting.  The impact layout additionally pays 8 B per segment for
        the ``seg_pos``/``seg_len`` prefixes.  The planner and the
        per-query ``bytes_postings`` counters both read this property, so
        compressed bytes are what the cost model optimizes end to end.
        """
        P = max(self.n_postings, 1)
        imp = self.impacts.dtype.itemsize
        seg = 8 * self.seg_pos.shape[0] if self.layout == "impact" else 0
        if self.is_compressed:
            packed = 4 * self.post_packed.shape[0] + 20 * self.blk_first.shape[0]
            return (packed + seg) / P + imp
        return (4.0 * P + seg) / P + imp


def logical_posting_blocks_np(
    offsets: np.ndarray,
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """128-posting block framing of a CSR posting store.

    Returns ``(blk_term_off i32[M+1], blk_pos i32[NB], blk_len i32[NB])``
    with blocks that never straddle a term slice — the exact framing
    :func:`pack_postings_np` uses, so compressed and uncompressed indexes
    address the same logical blocks (the pruned traversal's skip unit).
    An all-empty store yields one degenerate empty block (matching the
    packed layout's sentinel) so block columns are never zero-width.
    """
    M = len(offsets) - 1
    counts = np.diff(offsets.astype(np.int64))
    nb = (counts + POSTING_BLOCK - 1) // POSTING_BLOCK
    blk_term_off = np.zeros((M + 1,), np.int32)
    blk_term_off[1:] = np.cumsum(nb).astype(np.int32)
    NB = int(blk_term_off[-1])
    if NB == 0:
        return blk_term_off, np.zeros((1,), np.int32), np.zeros((1,), np.int32)
    term_of_blk = np.repeat(np.arange(M), nb)
    k = np.arange(NB, dtype=np.int64) - np.repeat(blk_term_off[:-1], nb)
    poss = offsets[term_of_blk].astype(np.int64) + k * POSTING_BLOCK
    lens = np.minimum(counts[term_of_blk] - k * POSTING_BLOCK, POSTING_BLOCK)
    return blk_term_off, poss.astype(np.int32), lens.astype(np.int32)


def block_max_impacts_np(
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
    live = blk_len > 0
    if live.any():
        # blocks tile the CSR contiguously and in order in both layouts,
        # so each live block is the run starting at its blk_pos
        v = np.asarray(impacts).astype(np.float32)
        mx = np.maximum.reduceat(v, blk_pos[live].astype(np.int64))
        out[live] = np.maximum(mx, 0.0)
    return out


def impact_plane_np(
    impacts: np.ndarray, blk_pos: np.ndarray, blk_len: np.ndarray
) -> np.ndarray:
    """Block-major impact plane — f32 ``[NB, POSTING_BLOCK]``.

    Row b holds block b's impacts (``impacts[blk_pos[b] : +blk_len[b]]``)
    zero-padded past ``blk_len``, decoded to f32 (exact for every stored
    dtype).  The pruned probe kernel copies one row per block, and a row of
    a 16-bit array is not tile-aligned on the TPU, so the plane is built
    once in f32 rather than widened in every query call.
    """
    v = np.asarray(impacts)
    NB = blk_pos.shape[0]
    plane = np.zeros((NB, POSTING_BLOCK), np.float32)
    j = np.arange(POSTING_BLOCK, dtype=np.int64)

    def rows(b0):
        b1 = min(b0 + _PACK_CHUNK, NB)
        idx = blk_pos[b0:b1, None].astype(np.int64) + j[None, :]
        live = j[None, :] < blk_len[b0:b1, None]
        plane[b0:b1] = np.where(live, v[np.where(live, idx, 0)], 0)

    if v.shape[0]:
        with ThreadPoolExecutor(HOST_THREADS) as pool:
            list(pool.map(rows, range(0, NB, _PACK_CHUNK)))
    return plane


_PLANE_CHUNK = 1 << 13  # doc_plane rows per upload (4 MiB of i32)


@functools.partial(jax.jit, donate_argnums=0)
def _put_rows(plane, rows, b0):
    return jax.lax.dynamic_update_slice(plane, rows, (b0, jnp.int32(0)))


def doc_plane(
    postings: np.ndarray, blk_pos: np.ndarray, blk_len: np.ndarray
) -> jax.Array:
    """Block-major doc-id plane — i32 ``[NB, POSTING_BLOCK]`` on the default
    device.

    Row b holds block b's doc ids (``postings[blk_pos[b] : +blk_len[b]]``)
    and :data:`DOC_PLANE_PAD` past ``blk_len``.  Rows are filled on the
    host in chunks and written into the device array in place, so a large
    plane never exists twice on the host (the plane of one chip's geoweb
    shard is 2.6 GB).
    """
    NB = blk_pos.shape[0]
    j = np.arange(POSTING_BLOCK, dtype=np.int64)

    def rows(b0):
        b1 = min(b0 + _PLANE_CHUNK, NB)
        idx = blk_pos[b0:b1, None].astype(np.int64) + j[None, :]
        live = j[None, :] < blk_len[b0:b1, None]
        got = postings[np.where(live, idx, 0)] if postings.shape[0] else 0
        return np.where(live, got, DOC_PLANE_PAD).astype(np.int32)

    if NB <= _PLANE_CHUNK:
        return jnp.asarray(rows(0))
    # fixed-size chunks (one compile); the last one ends at NB, rewriting
    # rows of its neighbour with the same values
    starts = [min(b0, NB - _PLANE_CHUNK) for b0 in range(0, NB, _PLANE_CHUNK)]
    plane = jnp.full((NB, POSTING_BLOCK), DOC_PLANE_PAD, jnp.int32)
    with ThreadPoolExecutor(HOST_THREADS) as pool:
        for w in range(0, len(starts), HOST_THREADS):  # bounded look-ahead
            wave = starts[w : w + HOST_THREADS]
            for b0, r in zip(wave, pool.map(rows, wave)):
                plane = _put_rows(plane, r, jnp.int32(b0))
    return plane


def _empty_pack(offsets: np.ndarray) -> dict[str, np.ndarray]:
    """Uncompressed layout: zero-width packed columns + logical blocks."""
    z = np.zeros((0,), np.int32)
    blk_term_off, blk_pos, blk_len = logical_posting_blocks_np(offsets)
    return dict(
        post_packed=np.zeros((0,), np.uint32), blk_first=z, blk_bits=z,
        blk_len=blk_len, blk_word_off=z, blk_pos=blk_pos,
        blk_term_off=blk_term_off, blk_n_exc=z,
    )


_PACK_CHUNK = 1 << 11  # blocks per vectorised packing step (bounds temporaries)


def _chunk_deltas_np(postings, blk_pos, blk_len):
    """Doc-id deltas of a contiguous run of blocks, flat over its postings.

    Blocks tile the CSR contiguously and in order, so the run's postings
    are one slice.  Returns ``(block i64[n], slot i64[n], delta i64[n])``:
    each block's slot 0 stores 0 (its first id lives in ``blk_first``),
    the others the gap to the previous id.
    """
    p0 = int(blk_pos[0])
    lens = blk_len.astype(np.int64)
    ids = postings[p0 : p0 + int(lens.sum())].astype(np.int64)
    block = np.repeat(np.arange(len(lens), dtype=np.int64), lens)
    slot = np.arange(len(ids), dtype=np.int64) - np.repeat(np.cumsum(lens) - lens, lens)
    delta = np.zeros_like(ids)
    delta[1:] = ids[1:] - ids[:-1]
    delta[slot == 0] = 0
    return block, slot, delta


def _pfor_widths_np(block, delta, blk_len):
    """PForDelta base width per block — ``(bits i64[nb], n_exc i64[nb])``.

    Minimizes total stored words: ``ceil(len·bits/32)`` tail-trimmed base
    words plus one exception word per delta exceeding the base width.
    The floor ``bits ≥ bit_length(max) − PFOR_HIGH_BITS`` keeps every
    exception's high bits inside one 24-bit patch field.  The full width
    wins ties against every narrower one; among narrower widths the
    narrowest of the cheapest wins.
    """
    nb = len(blk_len)
    bl = np.frexp(delta.astype(np.float64))[1]  # bit_length (exact < 2^53)
    hist = np.bincount(block * 34 + bl, minlength=nb * 34).reshape(nb, 34)
    # n_over[:, w] = deltas whose bit length exceeds w
    n_over = hist[:, ::-1].cumsum(axis=1)[:, ::-1][:, 1:].astype(np.int64)
    maxbits = np.maximum(33 - np.argmax(hist[:, ::-1][:, :33] > 0, axis=1), 1)
    maxbits = np.where(hist[:, 1:].sum(axis=1) > 0, maxbits, 1)
    w = np.arange(33, dtype=np.int64)
    n = blk_len.astype(np.int64)[:, None]
    words = np.maximum(-(-n * w[None, :] // 32), 1) + n_over
    cand = (w[None, :] >= np.maximum(1, maxbits - PFOR_HIGH_BITS)[:, None]) & (
        w[None, :] < maxbits[:, None]
    )
    big = np.iinfo(np.int64).max
    cw = np.where(cand, words, big)
    best = cw.argmin(axis=1)  # first (narrowest) cheapest candidate
    full_words = np.maximum(-(-blk_len.astype(np.int64) * maxbits // 32), 1)
    take = cw[np.arange(nb), best] < full_words
    bits = np.where(take, best, maxbits)
    n_exc = np.where(take, n_over[np.arange(nb), best], 0)
    return bits, n_exc


def pack_postings_np(
    postings: np.ndarray,
    offsets: np.ndarray,
    impacts: np.ndarray | None = None,
) -> dict[str, np.ndarray]:
    """Delta + bit-pack each term's posting slice into 128-posting blocks.

    Blocks never straddle terms; within a block the first element stores
    delta 0 (its doc id lives in ``blk_first``) and subsequent deltas are
    strictly ≥ 1 (postings are sorted unique doc ids within a term).
    Framing is PForDelta: each block picks the total-word-minimizing base
    width (:func:`_pfor_widths_np`) and stores ``ceil(len·bits/32)``
    tail-trimmed base words holding every delta's low ``bits`` bits,
    followed by one patch word per delta that overflows the base width —
    ``slot | high_bits << PFOR_SLOT_BITS``.  The tail padding a ragged
    last block would need is not materialized (``blk_word_off`` is
    explicit, so blocks are variable-width), which is what makes short
    posting lists actually compress.  Decoded slots past ``blk_len`` are
    therefore garbage (they read into the exception words or the next
    block) and every consumer masks them before trusting membership.

    Vectorised over blocks in chunks; the slots past ``blk_len`` are
    packed with the filler delta 1 wherever they share a stored word with
    real slots, exactly as the per-block reference loop in the tests.

    When ``impacts`` is given (the *stored*, possibly quantized, values)
    the dict additionally carries ``blk_max_impact`` — the per-block score
    upper bound driving the pruned traversal (see
    :func:`block_max_impacts_np` for why it must be computed
    post-quantization).
    """
    blk_term_off, blk_pos, blk_len = logical_posting_blocks_np(offsets)
    if int(blk_term_off[-1]) == 0:  # empty posting store: one degenerate block
        z = np.zeros((1,), np.int32)
        out = dict(
            post_packed=np.zeros((4,), np.uint32), blk_first=z.copy(),
            blk_bits=np.ones((1,), np.int32), blk_len=z.copy(),
            blk_word_off=z.copy(), blk_pos=z.copy(),
            blk_term_off=blk_term_off, blk_n_exc=z.copy(),
        )
    else:
        out = _pack_blocks_np(postings, blk_term_off, blk_pos, blk_len)
    if impacts is not None:
        out["blk_max_impact"] = block_max_impacts_np(
            impacts, out["blk_pos"], out["blk_len"]
        )
    return out


def _pack_blocks_np(postings, blk_term_off, blk_pos, blk_len):
    NB = blk_pos.shape[0]
    chunks = [(c0, min(c0 + _PACK_CHUNK, NB)) for c0 in range(0, NB, _PACK_CHUNK)]
    bits = np.zeros((NB,), np.int64)
    n_exc = np.zeros((NB,), np.int64)

    def widths(c):
        c0, c1 = c
        block, _, delta = _chunk_deltas_np(postings, blk_pos[c0:c1], blk_len[c0:c1])
        bits[c0:c1], n_exc[c0:c1] = _pfor_widths_np(block, delta, blk_len[c0:c1])

    lens = blk_len.astype(np.int64)
    with ThreadPoolExecutor(HOST_THREADS) as pool:
        list(pool.map(widths, chunks))
        nw_t = np.maximum(-(-lens * bits // 32), 1)  # tail-trimmed base words
        word_off = np.zeros((NB,), np.int64)
        word_off[1:] = np.cumsum(nw_t + n_exc)[:-1]
        W = int(word_off[-1] + nw_t[-1] + n_exc[-1])
        packed = np.zeros((W,), np.uint32)

        def pack(c):
            c0, c1 = c
            block, slot, delta = _chunk_deltas_np(
                postings, blk_pos[c0:c1], blk_len[c0:c1]
            )
            b, nt, ln = bits[c0:c1], nw_t[c0:c1], lens[c0:c1]
            # filler slots past blk_len store delta 1 wherever they reach
            # into the stored words (slots starting past them add nothing)
            n_fill = np.maximum(np.minimum(-(-nt * 32 // b), POSTING_BLOCK) - ln, 0)
            fb = np.repeat(np.arange(c1 - c0, dtype=np.int64), n_fill)
            fs = np.arange(len(fb), dtype=np.int64) - np.repeat(
                np.cumsum(n_fill) - n_fill, n_fill
            ) + ln[fb]
            blk = np.concatenate([block, fb])
            sl = np.concatenate([slot, fs])
            bb = b[blk]
            low = np.concatenate([delta, np.ones_like(fb)]) & ((1 << bb) - 1)
            bitpos = sl * bb
            wi = bitpos >> 5
            lo64 = low << (bitpos & 31)
            spill = np.minimum(wi + 1, 4 * bb - 1)  # 128·bits/32 words per block
            wo = word_off[c0:c1][blk] - word_off[c0]
            ntb = nt[blk]
            # slots write disjoint bit fields, so OR-ing them is a sum; every
            # partial sum stays < 2^32, exact in bincount's float64 weights
            keep_lo, keep_sp = wi < ntb, spill < ntb
            idx = np.concatenate([(wo + wi)[keep_lo], (wo + spill)[keep_sp]])
            val = np.concatenate([(lo64 & 0xFFFFFFFF)[keep_lo], (lo64 >> 32)[keep_sp]])
            span = int(word_off[c1 - 1] + nw_t[c1 - 1] + n_exc[c1 - 1] - word_off[c0])
            acc = np.bincount(idx, weights=val.astype(np.float64), minlength=span)
            # exception (patch) words follow each block's base words, in
            # slot order
            high = delta >> b[block]
            exc = high != 0
            eb = block[exc]
            first_exc = np.searchsorted(eb, np.arange(c1 - c0))
            rank = np.arange(len(eb)) - first_exc[eb]
            ew = word_off[c0:c1][eb] - word_off[c0] + nt[eb] + rank
            acc[ew] = slot[exc] | (high[exc] << PFOR_SLOT_BITS)
            packed[word_off[c0] : word_off[c0] + span] = acc.astype(np.uint32)

        list(pool.map(pack, chunks))
    first = postings[blk_pos.astype(np.int64)]
    return dict(
        post_packed=packed,
        blk_first=first.astype(np.int32),
        blk_bits=bits.astype(np.int32),
        blk_len=blk_len.astype(np.int32),
        blk_word_off=word_off.astype(np.int32),
        blk_pos=blk_pos.astype(np.int32),
        blk_term_off=blk_term_off,
        blk_n_exc=n_exc.astype(np.int32),
    )


def impact_levels_np(impacts: np.ndarray) -> np.ndarray:
    """Global geometric impact level per posting — i32, 0 = highest.

    Level ``l`` covers stored impacts in ``(vmax/r^(l+1), vmax/r^l]`` with
    ``r = IMPACT_LEVEL_RATIO``; everything below the last boundary folds
    into level ``IMPACT_LEVELS - 1``.  Computed from the *stored* (possibly
    quantized) values so segment order matches what queries actually score.
    """
    v = np.asarray(impacts, np.float32).astype(np.float64)
    vmax = float(v.max(initial=0.0))
    if vmax <= 0.0:
        return np.zeros(v.shape, np.int32)
    lvl = np.floor(
        np.log(vmax / np.maximum(v, vmax * 1e-12))
        / np.log(IMPACT_LEVEL_RATIO)
    )
    return np.clip(lvl, 0, IMPACT_LEVELS - 1).astype(np.int32)


def _impact_order_np(
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
    counts = np.diff(offsets.astype(np.int64))
    term = np.repeat(np.arange(M, dtype=np.int64), counts)
    order = np.lexsort((postings, lvl, term))
    post2, imp2, lv, term = postings[order], impacts[order], lvl[order], term
    P = len(post2)
    seg_term_off = np.zeros((M + 1,), np.int32)
    if P == 0:  # empty store: one degenerate empty segment
        z = np.zeros((1,), np.int32)
        return post2, imp2, seg_term_off, z, z.copy()
    new = np.ones((P,), bool)
    new[1:] = (lv[1:] != lv[:-1]) | (term[1:] != term[:-1])
    starts = np.flatnonzero(new)
    seg_len = np.diff(np.append(starts, P))
    seg_term_off[1:] = np.cumsum(np.bincount(term[starts], minlength=M))
    return (
        post2, imp2, seg_term_off,
        starts.astype(np.int32), seg_len.astype(np.int32),
    )


def _suffix_max_per_term_np(
    blk_max: np.ndarray, blk_term_off: np.ndarray
) -> np.ndarray:
    """Per-term suffix-max envelope of block maxima — f32[NB].

    ``out[b] = max(blk_max[b : term_end])`` within each term's block run:
    a safe upper bound for block ``b`` that is monotone non-increasing
    along the run, which is what lets the pruned kernel early-exit.
    Vectorised as one running max over keys ``(term rank, value)`` walked
    backwards: a later term's rank resets the max, and the value bits are
    mapped so that integer order is float order.
    """
    out = np.asarray(blk_max, np.float32).copy()
    n = int(blk_term_off[-1])
    if n == 0:
        return out
    M = len(blk_term_off) - 1
    term = np.repeat(np.arange(M, dtype=np.int64), np.diff(blk_term_off))
    b = out[:n].view(np.int32).astype(np.int64)
    key = (b ^ ((b >> 31) & 0x7FFFFFFF)) + 2**31  # float order, ≥ 0
    key = ((M - 1 - term) << 32) | key
    run = np.maximum.accumulate(key[::-1])[::-1] & 0xFFFFFFFF
    b = run - 2**31
    out[:n] = (b ^ ((b >> 31) & 0x7FFFFFFF)).astype(np.int32).view(np.float32)
    return out


def _trivial_segments_np(M: int) -> dict[str, np.ndarray]:
    """Degenerate segment columns for layout="docid" (never probed)."""
    return dict(
        seg_term_off=np.zeros((M + 1,), np.int32),
        seg_pos=np.zeros((1,), np.int32),
        seg_len=np.zeros((1,), np.int32),
    )


_DOC_CHUNK = 1 << 13  # documents per vectorised step (bounds temporaries)


def doc_term_matrix_np(doc_terms) -> np.ndarray:
    """Documents as one ``[N, L]`` term-id matrix, ``-1`` padded.

    A 2-D array (what :func:`repro.corpus.make_corpus` returns) passes
    through; a list of per-doc arrays (repetitions = frequencies) is
    padded to its longest document.
    """
    if isinstance(doc_terms, np.ndarray) and doc_terms.ndim == 2:
        return doc_terms
    lens = np.fromiter((len(t) for t in doc_terms), np.int64, len(doc_terms))
    mat = np.full((len(doc_terms), max(int(lens.max(initial=0)), 1)), -1, np.int32)
    if lens.sum():
        mat[np.arange(mat.shape[1])[None, :] < lens[:, None]] = np.concatenate(
            [np.asarray(t).ravel() for t in doc_terms]
        )
    return mat


def _doc_term_runs_np(mat: np.ndarray, c0: int, c1: int):
    """Distinct terms of docs ``[c0, c1)`` with their frequencies.

    Returns ``(doc i64[p], term i64[p], tf i64[p])`` in doc-major,
    term-ascending order.
    """
    x = np.sort(mat[c0:c1], axis=1)
    L = x.shape[1]
    new = np.ones(x.shape, bool)
    new[:, 1:] = x[:, 1:] != x[:, :-1]
    idx = np.flatnonzero(new)
    tf = np.diff(np.append(idx, x.size))  # runs end at the next run or row
    term = x.ravel()[idx].astype(np.int64)
    keep = term >= 0
    return (c0 + idx[keep] // L).astype(np.int64), term[keep], tf[keep]


def document_frequencies_np(doc_terms, n_terms: int) -> np.ndarray:
    """Documents containing each term — f64[n_terms]."""
    mat = doc_term_matrix_np(doc_terms)
    df = np.zeros((n_terms,), np.float64)
    for c0 in range(0, mat.shape[0], _DOC_CHUNK):
        _, term, _ = _doc_term_runs_np(mat, c0, min(c0 + _DOC_CHUNK, mat.shape[0]))
        df += np.bincount(term, minlength=n_terms)
    return df


def build_text_index_np(
    doc_terms,
    n_terms: int,
    n_bitmap_terms: int = 0,
    idf: np.ndarray | None = None,
    compress: bool = False,
    impact_dtype: np.dtype | str | None = None,
    layout: str = "docid",
    bitmap_min_df: int | None = None,
) -> TextIndex:
    """Build from per-doc term ids (with repetitions = frequencies): a
    list of arrays or a ``-1``-padded ``[N, L]`` matrix.

    Pure-numpy index construction (host side, analogous to the paper's
    offline index build), vectorised over documents and postings.
    ``idf`` overrides the collection IDF — shard
    builders pass the *corpus-global* IDF (:func:`global_idf_np`) so each
    posting's impact is rounded to f32 exactly once from statistics that
    do not depend on the partitioning, making per-doc scores bit-identical
    across shard layouts (the routing equivalence gate relies on this).

    ``impact_dtype`` lossy-compresses the impact column at build time (the
    one compression entry point — ``normalize_compress`` modes pass f16
    here), so ``blk_max_impact`` is computed from the values that are
    actually stored and the pruning bound survives quantization.

    ``bitmap_min_df`` adds a per-document bitmap for every term with at
    least that many postings (the exact scan's membership masks).

    ``layout`` selects the posting order: ``"docid"`` (ascending doc ids,
    the bit-identical reference) or ``"impact"`` (descending
    quantized-impact segments per term — see the module docstring).
    Impact ordering happens *after* quantization so segments group the
    stored values, and block framing restarts at segment boundaries.
    """
    if layout not in ("docid", "impact"):
        raise ValueError(f"unknown posting layout: {layout!r}")
    mat = doc_term_matrix_np(doc_terms)
    n_docs = len(doc_terms)
    M = n_terms
    doc_len = np.maximum((mat >= 0).sum(axis=1), 1).astype(np.float64)
    # one sortable key per posting: term | doc | frequency, in bit fields
    tf_bits = max(int(mat.shape[1]).bit_length(), 1)
    doc_bits = max(int(n_docs - 1).bit_length(), 1)
    if max(M - 1, 1).bit_length() + doc_bits + tf_bits > 63:
        raise ValueError("corpus too large for one 64-bit posting key")
    tf_mask, doc_mask = (1 << tf_bits) - 1, (1 << doc_bits) - 1

    def keys(c0):
        doc, term, tf = _doc_term_runs_np(mat, c0, min(c0 + _DOC_CHUNK, n_docs))
        return (((term << doc_bits) | doc) << tf_bits) | tf

    with ThreadPoolExecutor(HOST_THREADS) as pool:
        chunks = list(pool.map(keys, range(0, n_docs, _DOC_CHUNK)))
        key = np.concatenate(chunks) if chunks else np.zeros((0,), np.int64)
        del chunks
        key.sort()  # term-major, doc ids ascending within each term
        P = key.shape[0]
        spans = [(a, min(a + _DOC_CHUNK * 128, P)) for a in range(0, P, _DOC_CHUNK * 128)]
        postings = np.empty((P,), np.int32)

        def decode(span):
            k = key[span[0] : span[1]]
            postings[span[0] : span[1]] = (k >> tf_bits) & doc_mask
            return np.bincount(k >> (tf_bits + doc_bits), minlength=M)

        df = np.zeros((M,), np.float64)
        for part in pool.map(decode, spans):
            df += part
        if idf is None:
            idf = np.log(1.0 + n_docs / np.maximum(df, 1.0))
        offsets = np.zeros((M + 1,), dtype=np.int32)
        offsets[1:] = np.cumsum(df).astype(np.int64)
        impacts = np.empty((P,), np.float32)

        def impact(span):
            k = key[span[0] : span[1]]
            fr = (k & tf_mask).astype(np.float64)
            doc = (k >> tf_bits) & doc_mask
            imp = idf[k >> (tf_bits + doc_bits)] * (1.0 + np.log(fr)) / np.sqrt(
                doc_len[doc]
            )
            impacts[span[0] : span[1]] = imp.astype(np.float32)

        list(pool.map(impact, spans))
    del key

    # block bitmaps for the most frequent terms
    n_blocks = (n_docs + BLOCK - 1) // BLOCK
    n_words = n_blocks * WORDS_PER_BLOCK
    if bitmap_min_df is not None:
        n_bitmap_terms = max(n_bitmap_terms, int((df >= bitmap_min_df).sum()))
    if n_bitmap_terms > 0:
        top_terms = np.argsort(-df)[:n_bitmap_terms].astype(np.int32)
        bitmaps = np.zeros((n_bitmap_terms, n_words), dtype=np.uint32)

        def fill(row):
            w = top_terms[row]
            hit = np.zeros((n_words * 32,), bool)
            hit[postings[offsets[w] : offsets[w + 1]]] = True
            # bit (doc % 32) of word doc // 32
            bitmaps[row] = np.packbits(hit, bitorder="little").view("<u4")

        with ThreadPoolExecutor(HOST_THREADS) as pool:
            list(pool.map(fill, range(n_bitmap_terms)))
    else:
        top_terms = np.zeros((0,), dtype=np.int32)
        bitmaps = np.zeros((0, n_words), dtype=np.uint32)

    if impact_dtype is not None:
        impacts = impacts.astype(impact_dtype)
    if layout == "impact":
        postings, impacts, seg_term_off, seg_pos, seg_len = _impact_order_np(
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
        pack = pack_postings_np(postings, frame_off, impacts=impacts)
        plane = doc_plane(postings, pack["blk_pos"], pack["blk_len"])
        postings = np.zeros((0,), np.int32)  # packed words are the store
    else:
        pack = _empty_pack(frame_off)
        plane = jnp.zeros((0, POSTING_BLOCK), jnp.int32)
        pack["blk_max_impact"] = block_max_impacts_np(
            impacts, pack["blk_pos"], pack["blk_len"]
        )
    if layout == "impact":
        # collapse the per-segment block CSR back to per-term, and widen
        # the exact block maxima into the per-term suffix-max envelope —
        # the monotone bound the early-exiting pruned traversal needs
        pack["blk_term_off"] = pack["blk_term_off"][seg["seg_term_off"]]
        pack["blk_max_impact"] = _suffix_max_per_term_np(
            pack["blk_max_impact"], pack["blk_term_off"]
        )
    pack["imp_plane"] = impact_plane_np(impacts, pack["blk_pos"], pack["blk_len"])
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
        doc_plane=plane,
        n_docs=n_docs,
        n_terms=n_terms,
        max_term_blocks=int(max(term_blocks.max(initial=0), 1)),
        layout=layout,
        max_term_segments=int(max(term_segments.max(initial=0), 1)),
    )


def _with_impacts(index: TextIndex, impacts: jax.Array) -> TextIndex:
    """Replace the impact column and refresh ``blk_max_impact`` to match.

    Under layout="impact" the refreshed maxima are re-enveloped per term —
    per-term rescaling preserves within-term order, so the suffix-max
    stays both a safe bound and monotone along each block run.
    """
    bm = block_max_impacts_np(
        np.asarray(impacts), np.asarray(index.blk_pos), np.asarray(index.blk_len)
    )
    if index.layout == "impact":
        bm = _suffix_max_per_term_np(bm, np.asarray(index.blk_term_off))
    plane = impact_plane_np(
        np.asarray(impacts), np.asarray(index.blk_pos), np.asarray(index.blk_len)
    )
    return dataclasses.replace(
        index, impacts=impacts, blk_max_impact=jnp.asarray(bm),
        imp_plane=jnp.asarray(plane),
    )


def quantize_impacts(index: TextIndex, dtype=jnp.float16) -> TextIndex:
    """Deprecated shim: quantize impacts post-build.

    Prefer ``build_text_index_np(..., impact_dtype=...)`` — the one
    compression entry point (engine builders route every ``compress`` mode
    through it).  Kept for callers holding an already-built index; it
    refreshes ``blk_max_impact`` so pruning bounds stay safe.
    """
    return _with_impacts(index, index.impacts.astype(dtype))


def global_idf_np(doc_terms, n_terms: int) -> np.ndarray:
    """Corpus-wide IDF, matching ``build_text_index_np``'s formula."""
    df = document_frequencies_np(doc_terms, n_terms)
    return np.log(1.0 + len(doc_terms) / np.maximum(df, 1.0))


def rescale_impacts_to_global(index: TextIndex, idf_global: np.ndarray) -> TextIndex:
    """Swap a shard-local index's IDF for the corpus-global one.

    Text impacts are ``idf · (1+log tf) / sqrt(doc_len)``; tf and doc_len
    are per-document, but idf is a *collection* statistic — a shard scoring
    with its local idf would rank differently from the whole corpus.  Real
    distributed engines broadcast global term stats to every shard; we do
    the same by rescaling each posting's impact by ``idf_global/idf_local``.
    """
    offsets = np.asarray(index.offsets)
    counts = np.diff(offsets)
    idf_local = np.log(1.0 + index.n_docs / np.maximum(counts.astype(np.float64), 1.0))
    ratio = np.where(counts > 0, idf_global / idf_local, 1.0)
    impacts = np.asarray(index.impacts) * np.repeat(ratio, counts).astype(np.float32)
    return _with_impacts(index, jnp.asarray(impacts))


# ---------------------------------------------------------------------------
# Query-time primitives (jit-safe)
# ---------------------------------------------------------------------------

def term_slice(index: TextIndex, term: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(start, length) of a term's posting slice."""
    lo = index.offsets[term]
    hi = index.offsets[term + 1]
    return lo, hi - lo


def decode_posting_blocks(index: TextIndex, blocks: jax.Array) -> jax.Array:
    """Doc ids of compressed blocks — i32[..., POSTING_BLOCK].

    One row of ``doc_plane`` per block.  Slots past ``blk_len`` hold
    :data:`DOC_PLANE_PAD`; readers mask them with ``blk_len``.
    """
    return index.doc_plane[blocks]


def _probe_term_packed(
    index: TextIndex, term: jax.Array, doc_ids: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """Compressed-layout probe: block-head binary search + one-block decode."""
    b0 = index.blk_term_off[term]
    nb = index.blk_term_off[term + 1] - b0
    NB = index.blk_first.shape[0]
    # containing block = last block whose first doc id is ≤ the key
    pos = _searchsorted_slice(index.blk_first, b0, nb, doc_ids)
    exact = (pos < b0 + nb) & (
        index.blk_first[jnp.clip(pos, 0, NB - 1)] == doc_ids
    )
    blk = jnp.where(exact, pos, pos - 1)
    in_range = (blk >= b0) & (blk < b0 + nb) & (nb > 0)
    blk_s = jnp.clip(blk, 0, NB - 1)
    decoded = decode_posting_blocks(index, blk_s)  # [..., 128]
    j = jnp.arange(POSTING_BLOCK, dtype=jnp.int32)
    hit = (decoded == doc_ids[..., None]) & (j < index.blk_len[blk_s][..., None])
    member = in_range & hit.any(axis=-1)
    jpos = jnp.argmax(hit, axis=-1).astype(jnp.int32)
    apos = jnp.clip(index.blk_pos[blk_s] + jpos, 0, index.n_postings - 1)
    impact = jnp.where(member, index.impacts[apos].astype(jnp.float32), 0.0)
    return member, impact


def _probe_term_segmented(
    index: TextIndex, term: jax.Array, doc_ids: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """Impact-layout probe: binary search within each of the term's segments.

    Impact ordering breaks the global docID-ascending invariant the plain
    probes rely on, but doc ids still ascend *within* each segment — so
    membership is an OR over ``max_term_segments`` per-segment searches
    (a doc occurs at most once per term, so segment hits are disjoint and
    the impact sum picks up exactly the one stored value).
    """
    s0 = index.seg_term_off[term]
    ns = index.seg_term_off[term + 1] - s0
    NS = index.seg_pos.shape[0]
    P = index.n_postings
    member0 = jnp.zeros(doc_ids.shape, bool)
    impact0 = jnp.zeros(doc_ids.shape, jnp.float32)
    if index.is_compressed:
        NB = index.blk_first.shape[0]
        j = jnp.arange(POSTING_BLOCK, dtype=jnp.int32)

        def seg_one(i, carry):
            member, impact, b_off = carry
            s = jnp.clip(s0 + i, 0, NS - 1)
            live = i < ns
            # segments tile the term's block run contiguously, so the
            # running block offset carried across iterations addresses
            # this segment's ceil(len/128) blocks directly
            nb_s = jnp.where(
                live, -(-index.seg_len[s] // POSTING_BLOCK), 0
            )
            pos = _searchsorted_slice(index.blk_first, b_off, nb_s, doc_ids)
            exact = (pos < b_off + nb_s) & (
                index.blk_first[jnp.clip(pos, 0, NB - 1)] == doc_ids
            )
            blk = jnp.where(exact, pos, pos - 1)
            in_range = (blk >= b_off) & (blk < b_off + nb_s)
            blk_s = jnp.clip(blk, 0, NB - 1)
            decoded = decode_posting_blocks(index, blk_s)
            hit = (decoded == doc_ids[..., None]) & (
                j < index.blk_len[blk_s][..., None]
            )
            m = in_range & hit.any(axis=-1)
            jpos = jnp.argmax(hit, axis=-1).astype(jnp.int32)
            apos = jnp.clip(index.blk_pos[blk_s] + jpos, 0, P - 1)
            imp = jnp.where(m, index.impacts[apos].astype(jnp.float32), 0.0)
            return member | m, impact + imp, b_off + nb_s

        member, impact, _ = jax.lax.fori_loop(
            0, index.max_term_segments, seg_one,
            (member0, impact0, index.blk_term_off[term]),
        )
        return member, impact

    def seg_one(i, carry):
        member, impact = carry
        s = jnp.clip(s0 + i, 0, NS - 1)
        live = i < ns
        lo = index.seg_pos[s]
        n = jnp.where(live, index.seg_len[s], 0)
        pos = _searchsorted_slice(index.postings, lo, n, doc_ids)
        found = index.postings[jnp.clip(pos, 0, P - 1)]
        m = (pos < lo + n) & (found == doc_ids) & (n > 0)
        imp = jnp.where(
            m, index.impacts[jnp.clip(pos, 0, P - 1)].astype(jnp.float32), 0.0
        )
        return member | m, impact + imp

    member, impact = jax.lax.fori_loop(
        0, index.max_term_segments, seg_one, (member0, impact0)
    )
    return member, impact


def probe_term(
    index: TextIndex, term: jax.Array, doc_ids: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """Membership + impact of ``doc_ids`` in one term's posting list.

    Vectorized binary search over the whole posting array restricted to the
    term slice.  Returns (member bool[...], impact f32[...]).  The impact
    layout dispatches to the segment-aware probe (doc ids only ascend
    within a segment there); the docid layout keeps the single-slice fast
    path, bit-identical to what it always did.
    """
    if index.layout == "impact":
        return _probe_term_segmented(index, term, doc_ids)
    if index.is_compressed:
        return _probe_term_packed(index, term, doc_ids)
    lo, n = term_slice(index, term)
    # searchsorted over the full array with translated bounds: postings within
    # a slice are sorted, and slices are disjoint, so search the slice via
    # index arithmetic on a gathered window — instead do searchsorted on the
    # full array bounded to [lo, lo+n) by clamping.
    pos = _searchsorted_slice(index.postings, lo, n, doc_ids)
    found_id = index.postings[jnp.clip(pos, 0, index.n_postings - 1)]
    member = (pos < lo + n) & (found_id == doc_ids) & (n > 0)
    safe_pos = jnp.clip(pos, 0, index.n_postings - 1)
    impact = jnp.where(member, index.impacts[safe_pos].astype(jnp.float32), 0.0)
    return member, impact


def _searchsorted_slice(
    arr: jax.Array, lo: jax.Array, n: jax.Array, keys: jax.Array
) -> jax.Array:
    """Branchless binary search of ``keys`` in ``arr[lo:lo+n)`` (left).

    Works for traced (dynamic) lo/n: a fixed ``ceil(log2(P))+1``-step bisection.
    Returns absolute positions in [lo, lo+n].
    """
    P = arr.shape[0]
    steps = max(int(np.ceil(np.log2(max(P, 2)))) + 1, 1)
    lo_ = jnp.broadcast_to(lo, keys.shape).astype(jnp.int32)
    hi_ = jnp.broadcast_to(lo + n, keys.shape).astype(jnp.int32)

    def body(_, lh):
        l, h = lh
        active = l < h
        # overflow-safe midpoint: l + h wraps int32 once the posting store
        # passes 2^30 entries (production-scale shards); l + (h-l)//2 is
        # value-identical for 0 <= l <= h and never overflows
        mid = l + (h - l) // 2
        v = arr[jnp.clip(mid, 0, P - 1)]
        go_right = v < keys
        l = jnp.where(active & go_right, mid + 1, l)
        h = jnp.where(active & ~go_right, mid, h)
        return l, h

    l, _ = jax.lax.fori_loop(0, steps, body, (lo_, hi_))
    return l


def conjunction_candidates(
    index: TextIndex,
    terms: jax.Array,  # i32[d] (padded with -1)
    max_candidates: int,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """TEXT-FIRST driver: intersect posting lists of ``terms``.

    Uses the *first valid* term's posting list as the driver (capped at
    ``max_candidates`` postings, an early-termination budget) and probes the
    remaining terms by binary search.  Returns

      cand_ids  i32[max_candidates]   (docIDs; ascending among valid under
                                       layout="docid", impact-segment
                                       order under layout="impact")
      valid     bool[max_candidates]
      text_score f32[max_candidates]  (sum of impacts over query terms)
    """
    d = terms.shape[0]
    # Classic optimization: drive the intersection with the *shortest* list.
    safe_terms = jnp.maximum(terms, 0)
    lens = index.offsets[safe_terms + 1] - index.offsets[safe_terms]
    lens = jnp.where(terms >= 0, lens, jnp.int32(2**31 - 1))
    driver = jnp.argmin(lens).astype(jnp.int32)
    t0 = safe_terms[driver]
    any_real = terms[0] >= 0  # terms are packed left; term 0 real iff query nonempty

    lo, n = term_slice(index, t0)
    n = jnp.minimum(n, max_candidates)
    idx = jnp.arange(max_candidates, dtype=jnp.int32)
    valid = (idx < n) & any_real
    if index.is_compressed:
        # stream the driver's blocks: decode ceil(mc/128) consecutive blocks
        # once and flatten, instead of per-element block decodes
        NB = index.blk_first.shape[0]
        nbd = (max_candidates + POSTING_BLOCK - 1) // POSTING_BLOCK
        if index.layout == "impact":
            # framing restarts at every segment, so the first max_candidates
            # postings can end one ragged block per segment further on
            nbd += index.max_term_segments
        blocks = jnp.clip(
            index.blk_term_off[t0] + jnp.arange(nbd, dtype=jnp.int32), 0, NB - 1
        )
        decoded = decode_posting_blocks(index, blocks)
        if index.layout == "impact":
            # segment-restarted framing leaves ragged blocks *mid-run*
            # (each segment's tail), so a plain flatten would interleave
            # garbage slots: map each CSR offset through the blocks' valid
            # lengths instead.  The docid layout keeps the plain flatten
            # (only its last block is ragged — past n is masked anyway).
            cl = jnp.cumsum(index.blk_len[blocks])
            bi = jnp.searchsorted(cl, idx, side="right")
            bi_s = jnp.clip(bi, 0, nbd - 1)
            lane = idx - jnp.where(bi > 0, cl[jnp.maximum(bi - 1, 0)], 0)
            cand = decoded[bi_s, jnp.clip(lane, 0, POSTING_BLOCK - 1)]
            # blocks tile the CSR contiguously, so the driver's i-th
            # posting lives at CSR position lo + i in both layouts
            apos = jnp.clip(lo + idx, 0, index.n_postings - 1)
        else:
            cand = decoded.reshape(-1)[:max_candidates]
            apos = jnp.clip(
                index.blk_pos[blocks][:, None]
                + jnp.arange(POSTING_BLOCK, dtype=jnp.int32)[None, :],
                0,
                index.n_postings - 1,
            ).reshape(-1)[:max_candidates]
        imp = index.impacts[apos].astype(jnp.float32)
    else:
        pos = lo + idx
        cand = index.postings[jnp.clip(pos, 0, index.n_postings - 1)]
        imp = index.impacts[jnp.clip(pos, 0, index.n_postings - 1)].astype(
            jnp.float32
        )
    cand = jnp.where(valid, cand, jnp.int32(2**31 - 1))
    score = jnp.where(valid, imp, 0.0)

    def probe_one(i, carry):
        valid, score = carry
        t = terms[i]
        is_real = (t >= 0) & (i != driver)
        member, imp = probe_term(index, jnp.maximum(t, 0), cand)
        valid = valid & (member | ~is_real)
        score = score + jnp.where(is_real, imp, 0.0)
        return valid, score

    valid, score = jax.lax.fori_loop(0, d, probe_one, (valid, score))
    cand = jnp.where(valid, cand, jnp.int32(2**31 - 1))
    score = jnp.where(valid, score, 0.0)
    return cand, valid, score


def term_postings(
    index: TextIndex, term: jax.Array
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One term's whole posting list, block by block — ``(docs i32,
    impacts f32, live bool)``, each ``[max_term_blocks, POSTING_BLOCK]``.

    Slots past a block's length or the term's block run are not live.
    Both layouts and both stores: a term's blocks are one contiguous run,
    and block b's postings sit at CSR positions ``blk_pos[b] + j``.
    """
    shape = (index.max_term_blocks, POSTING_BLOCK)
    P = index.n_postings
    if P == 0:
        return (
            jnp.zeros(shape, jnp.int32), jnp.zeros(shape, jnp.float32),
            jnp.zeros(shape, bool),
        )
    NB = index.blk_pos.shape[0]
    b0 = index.blk_term_off[term]
    nb = index.blk_term_off[term + 1] - b0
    i = jnp.arange(shape[0], dtype=jnp.int32)
    blocks = jnp.clip(b0 + i, 0, NB - 1)
    j = jnp.arange(POSTING_BLOCK, dtype=jnp.int32)
    live = (i < nb)[:, None] & (j[None, :] < index.blk_len[blocks][:, None])
    pos = jnp.clip(index.blk_pos[blocks][:, None] + j[None, :], 0, P - 1)
    if index.is_compressed:
        docs = decode_posting_blocks(index, blocks)
    else:
        docs = index.postings[pos]
    return docs, index.impacts[pos].astype(jnp.float32), live


def text_score_of_docs(
    index: TextIndex,
    terms: jax.Array,  # i32[d] padded with -1
    doc_ids: jax.Array,  # i32[C]
) -> tuple[jax.Array, jax.Array]:
    """AND-semantics text score for arbitrary candidate docs.

    Returns (match bool[C], score f32[C]); ``match`` requires every valid
    query term to occur in the doc.
    """
    d = terms.shape[0]

    def probe_one(i, carry):
        match, score = carry
        t = terms[i]
        is_real = t >= 0
        member, imp = probe_term(index, jnp.maximum(t, 0), doc_ids)
        match = match & (member | ~is_real)
        score = score + jnp.where(is_real, imp, 0.0)
        return match, score

    match0 = jnp.ones(doc_ids.shape, dtype=bool)
    score0 = jnp.zeros(doc_ids.shape, dtype=jnp.float32)
    match, score = jax.lax.fori_loop(0, d, probe_one, (match0, score0))
    return match, score


def text_score_of_docs_counted(
    index: TextIndex,
    terms: jax.Array,  # i32[d] padded with -1
    doc_ids: jax.Array,  # i32[C]
    valid: jax.Array,  # bool[C] — candidates that are live before term 0
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """``text_score_of_docs`` plus an honest probe counter.

    Same match/score math (bit-identical outputs), but additionally counts
    the probes a term-at-a-time short-circuiting evaluator would issue:
    before each term only the candidates still matching every earlier term
    are probed, so the count shrinks as terms eliminate candidates.
    Returns (match bool[C], score f32[C], probes i32 scalar).
    """
    d = terms.shape[0]

    def probe_one(i, carry):
        match, score, probes = carry
        t = terms[i]
        is_real = t >= 0
        live = match & valid
        probes = probes + jnp.where(
            is_real, jnp.sum(live.astype(jnp.int32)), 0
        )
        member, imp = probe_term(index, jnp.maximum(t, 0), doc_ids)
        match = match & (member | ~is_real)
        score = score + jnp.where(is_real, imp, 0.0)
        return match, score, probes

    match0 = jnp.ones(doc_ids.shape, dtype=bool)
    score0 = jnp.zeros(doc_ids.shape, dtype=jnp.float32)
    match, score, probes = jax.lax.fori_loop(
        0, d, probe_one, (match0, score0, jnp.int32(0))
    )
    return match, score, probes
