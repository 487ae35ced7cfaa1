"""Impact-ordered posting layout: bit-identity to the docID layout across
the full compress × prune × fused grid, monotone suffix-max envelopes +
segment CSR invariants, PForDelta exception-framing round-trip edge
cases, and the layout's end-to-end byte/skip win on a natural zipf trace."""
from types import SimpleNamespace

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import GeoSearchEngine, QueryBudgets
from repro.core.text_index import (
    PFOR_HIGH_BITS,
    POSTING_BLOCK,
    build_text_index_np,
    decode_posting_blocks,
    doc_plane,
    impact_levels_np,
    pack_postings_np,
)
from repro.corpus import make_corpus, make_zipf_trace, pad_trace_batch


def _engine(corpus, layout, compress="none", prune=False, mc=512):
    budgets = QueryBudgets(
        max_candidates=mc, max_tiles=128, k_sweeps=4, sweep_budget=512,
        top_k=10, prune=prune,
    )
    return GeoSearchEngine.build(
        corpus.doc_terms, corpus.doc_rects, corpus.doc_amps, corpus.n_terms,
        pagerank=corpus.pagerank, grid=32, budgets=budgets,
        compress=compress, layout=layout,
    )


@pytest.fixture(scope="module")
def zipf_corpus_and_batch():
    corpus = make_corpus(1536, 160, seed=11)
    trace = make_zipf_trace(corpus, n_queries=48, pool_size=24, seed=12)
    return corpus, pad_trace_batch(trace)


# ---------------------------------------------------------------------------
# the core property: the impact layout is a pure storage reordering — ids
# AND scores are bit-identical to the docID layout on every pipeline
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("compress", ["none", "f16", "int8"])
@pytest.mark.parametrize(
    "prune,fused", [(False, False), (True, False), (True, True)]
)
def test_impact_equals_docid_text_first(
    zipf_corpus_and_batch, compress, prune, fused
):
    """Pruned selection is order-invariant at any budget (the θ rule only
    ever discards candidates the top-C select stage would drop), so
    pruned runs must agree bit-for-bit.  The *unpruned* traversal
    truncates the driver's CSR walk at ``max_candidates`` — under the
    impact layout that keeps the highest-impact postings instead of the
    lowest docIDs, a different (better) candidate subset — so the
    unpruned case is compared at covering budgets, where both layouts
    stream every driver posting."""
    corpus, batch = zipf_corpus_and_batch
    mc = 512 if prune else len(corpus.doc_terms)
    out = {}
    for layout in ("docid", "impact"):
        eng = _engine(corpus, layout, compress=compress, prune=prune, mc=mc)
        kw = {"fused": True} if fused else {}
        out[layout] = eng.query(batch, "text_first", **kw)
    np.testing.assert_array_equal(
        np.asarray(out["docid"].ids), np.asarray(out["impact"].ids)
    )
    np.testing.assert_array_equal(
        np.asarray(out["docid"].scores), np.asarray(out["impact"].scores)
    )


def test_impact_layout_other_algorithms_identical(zipf_corpus_and_batch):
    """geo_first and k_sweep probe postings through the same segment-aware
    path — the layout must be invisible to them too."""
    corpus, batch = zipf_corpus_and_batch
    for algorithm in ("geo_first", "k_sweep"):
        out = {}
        for layout in ("docid", "impact"):
            eng = _engine(corpus, layout, compress="f16")
            out[layout] = eng.query(batch, algorithm)
        np.testing.assert_array_equal(
            np.asarray(out["docid"].ids), np.asarray(out["impact"].ids)
        )
        np.testing.assert_array_equal(
            np.asarray(out["docid"].scores), np.asarray(out["impact"].scores)
        )


def test_impact_prune_skips_more_blocks_on_natural_trace(zipf_corpus_and_batch):
    """The layout's purpose: on a plain zipf trace (no planted bimodality)
    the monotone bounds + early-exit cut turn θ-pruning into actual
    skipped blocks and fewer streamed posting bytes, at identical
    results (checked above).  2-term queries keep the min-df driver hot
    — the regime where docID-ordered pruning has nothing to skip."""
    corpus, _ = zipf_corpus_and_batch
    batch = pad_trace_batch(
        make_zipf_trace(
            corpus, n_queries=48, pool_size=24, seed=12, d_terms=2
        )
    )
    stats = {}
    for layout in ("docid", "impact"):
        eng = _engine(corpus, layout, compress="f16", prune=True, mc=256)
        r = eng.query(batch, "text_first", fused=True)
        stats[layout] = {
            k: float(np.asarray(v).sum()) for k, v in r.stats.items()
        }
    assert stats["impact"]["text_blocks_skipped"] > 0
    assert (
        stats["impact"]["text_blocks_skipped"]
        >= stats["docid"]["text_blocks_skipped"]
    )
    assert (
        stats["impact"]["bytes_postings"] < stats["docid"]["bytes_postings"]
    )


# ---------------------------------------------------------------------------
# layout invariants: monotone envelope + segment CSR structure
# ---------------------------------------------------------------------------

def test_blk_max_impact_monotone_per_term(zipf_corpus_and_batch):
    corpus, _ = zipf_corpus_and_batch
    idx = build_text_index_np(corpus.doc_terms, corpus.n_terms, layout="impact")
    bto = np.asarray(idx.blk_term_off)
    env = np.asarray(idx.blk_max_impact)
    for t in range(idx.n_terms):
        run = env[bto[t] : bto[t + 1]]
        assert np.all(np.diff(run) <= 0), f"term {t} envelope not monotone"


def test_segment_csr_structure(zipf_corpus_and_batch):
    """Segments tile each term's CSR slice exactly; docIDs ascend within a
    segment; quantized impact levels strictly descend across segments."""
    corpus, _ = zipf_corpus_and_batch
    idx = build_text_index_np(corpus.doc_terms, corpus.n_terms, layout="impact")
    raw = build_text_index_np(corpus.doc_terms, corpus.n_terms, layout="docid")
    offs = np.asarray(idx.offsets)
    sto = np.asarray(idx.seg_term_off)
    spos, slen = np.asarray(idx.seg_pos), np.asarray(idx.seg_len)
    post, imp = np.asarray(idx.postings), np.asarray(idx.impacts)
    lvl = impact_levels_np(imp)
    for t in range(idx.n_terms):
        segs = range(int(sto[t]), int(sto[t + 1]))
        assert sum(int(slen[s]) for s in segs) == int(offs[t + 1] - offs[t])
        cursor = int(offs[t])
        prev_lvl = -1
        for s in segs:
            a, n = int(spos[s]), int(slen[s])
            assert a == cursor  # segments tile the slice contiguously
            cursor += n
            ids = post[a : a + n]
            assert np.all(np.diff(ids) > 0)  # docID-ascending, duplicate-free
            levels = lvl[a : a + n]
            assert np.all(levels == levels[0])  # one level per segment
            assert levels[0] > prev_lvl  # strictly descending impact
            prev_lvl = levels[0]
    # a reordering, not a reweighting: the multiset of (doc, impact)
    # pairs per term is exactly the docID layout's
    roffs = np.asarray(raw.offsets)
    for t in range(idx.n_terms):
        a, b = int(offs[t]), int(offs[t + 1])
        got = sorted(zip(post[a:b].tolist(), imp[a:b].tolist()))
        want = sorted(
            zip(
                np.asarray(raw.postings)[roffs[t] : roffs[t + 1]].tolist(),
                np.asarray(raw.impacts)[roffs[t] : roffs[t + 1]].tolist(),
            )
        )
        assert got == want


def test_impact_layout_pays_segment_bytes(zipf_corpus_and_batch):
    """posting_bytes charges the packed words + 20 B/block + 8 B/segment
    honestly; the impact layout's extra framing (blocks restart at every
    segment boundary, plus the segment prefixes) makes it strictly
    costlier per posting than the docID layout."""
    corpus, _ = zipf_corpus_and_batch
    doc = build_text_index_np(corpus.doc_terms, corpus.n_terms, compress=True)
    imp = build_text_index_np(
        corpus.doc_terms, corpus.n_terms, compress=True, layout="impact"
    )
    for idx in (doc, imp):
        seg = 8 * idx.seg_pos.shape[0] if idx.layout == "impact" else 0
        want = (
            4 * idx.post_packed.shape[0] + 20 * idx.blk_first.shape[0] + seg
        ) / max(idx.n_postings, 1) + idx.impacts.dtype.itemsize
        assert idx.posting_bytes == pytest.approx(want, rel=1e-9)
    assert imp.posting_bytes > doc.posting_bytes
    assert imp.blk_first.shape[0] >= doc.blk_first.shape[0]


# ---------------------------------------------------------------------------
# PForDelta exception framing: round-trip edge cases (pack_postings_np
# driven directly, so delta gaps far beyond any test corpus are cheap)
# ---------------------------------------------------------------------------

def _pack(plists):
    """Pack a list of per-term sorted posting arrays; return a decode
    handle: the packed columns plus the doc-id plane
    (`decode_posting_blocks` reads only ``doc_plane``)."""
    offsets = np.zeros((len(plists) + 1,), np.int64)
    offsets[1:] = np.cumsum([len(p) for p in plists])
    postings = (
        np.concatenate(plists).astype(np.int64)
        if offsets[-1]
        else np.zeros((0,), np.int64)
    )
    cols = pack_postings_np(postings, offsets)
    cols["doc_plane"] = doc_plane(postings, cols["blk_pos"], cols["blk_len"])
    return SimpleNamespace(**{k: jnp.asarray(v) for k, v in cols.items()})


def _decode_term(idx, t, decode=decode_posting_blocks):
    bto = np.asarray(idx.blk_term_off)
    blk_len = np.asarray(idx.blk_len)
    ids = [
        np.asarray(decode(idx, jnp.int32(b)))[: int(blk_len[b])]
        for b in range(int(bto[t]), int(bto[t + 1]))
    ]
    return np.concatenate(ids) if ids else np.zeros((0,), np.int64)


def _assert_decodes(idx, t, want, bit_extract_decode):
    """Term ``t`` reads back as ``want`` from the doc-id plane and from a
    bit-extracting decode of the packed words."""
    np.testing.assert_array_equal(_decode_term(idx, t), want)
    np.testing.assert_array_equal(_decode_term(idx, t, bit_extract_decode), want)


def test_pfor_zero_exception_block(bit_extract_decode):
    """Uniform small deltas: the width argmin lands on the plain framing
    (no exception words) and decodes exactly."""
    plist = np.arange(0, 2 * POSTING_BLOCK * 3, 3, dtype=np.int64)
    idx = _pack([plist])
    assert int(np.asarray(idx.blk_n_exc).sum()) == 0
    _assert_decodes(idx, 0, plist, bit_extract_decode)


def test_pfor_exception_heavy_block(bit_extract_decode):
    """Half tiny deltas, half huge: patching the outliers (one exception
    word each) beats widening the whole block, so the argmin framing
    carries many exceptions — and still decodes exactly."""
    deltas = np.ones(POSTING_BLOCK, np.int64)
    deltas[1::2] = 1 << 20  # 64 outliers, interleaved
    plist = np.cumsum(deltas) - 1
    idx = _pack([plist])
    n_exc = int(np.asarray(idx.blk_n_exc)[0])
    assert n_exc == POSTING_BLOCK // 2
    # exception framing must beat the no-exception alternative:
    # 64 patch words + a narrow base < 128 postings at 21 bits
    words_noexc = -(-POSTING_BLOCK * 21 // 32)
    bits = int(np.asarray(idx.blk_bits)[0])
    assert -(-POSTING_BLOCK * bits // 32) + n_exc < words_noexc
    _assert_decodes(idx, 0, plist, bit_extract_decode)


def test_pfor_single_posting_and_max_gap(bit_extract_decode):
    """A single-posting term, and terms whose one delta is a maximal
    doc-id gap — wider than PFOR_HIGH_BITS, so the base width's floor
    (bits ≥ bit_length − PFOR_HIGH_BITS) must keep every exception's
    high bits inside one patch field."""
    big = (1 << (PFOR_HIGH_BITS + 4)) + 5
    plists = [
        np.asarray([7], np.int64),  # single posting
        np.asarray([0, big], np.int64),  # maximal delta gap
        # the gap hidden among tiny deltas: forces an exception whose
        # high bits exercise the width floor
        np.concatenate(
            [np.arange(64, dtype=np.int64), np.asarray([big], np.int64)]
        ),
    ]
    idx = _pack(plists)
    for t, want in enumerate(plists):
        _assert_decodes(idx, t, want, bit_extract_decode)


def test_pfor_ragged_tail_block(bit_extract_decode):
    """A list whose last block is part-full: tail-trimmed base words plus
    exceptions decode exactly, and padding lanes never leak."""
    rng = np.random.default_rng(41)
    n = 2 * POSTING_BLOCK + 37  # ragged tail
    deltas = rng.integers(1, 4, size=n).astype(np.int64)
    deltas[n - 5] = 1 << 18  # an outlier inside the ragged tail
    plist = np.cumsum(deltas) - 1
    idx = _pack([plist])
    assert int(np.asarray(idx.blk_n_exc).sum()) >= 1
    _assert_decodes(idx, 0, plist, bit_extract_decode)


def test_pfor_roundtrip_random_impact_layout(bit_extract_decode):
    """Random corpus under layout="impact": segment-local delta streams
    (docIDs restart ascending at each segment) round-trip exactly."""
    corpus = make_corpus(n_docs=700, n_terms=90, seed=42)
    comp = build_text_index_np(
        corpus.doc_terms, corpus.n_terms, compress=True, layout="impact"
    )
    raw = build_text_index_np(
        corpus.doc_terms, corpus.n_terms, compress=False, layout="impact"
    )
    offs = np.asarray(raw.offsets)
    for t in range(corpus.n_terms):
        want = np.asarray(raw.postings)[offs[t] : offs[t + 1]]
        _assert_decodes(comp, t, want, bit_extract_decode)

