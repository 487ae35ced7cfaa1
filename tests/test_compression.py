"""Compressed posting/toe-print stores: bit-exact round-trips, kernel ≡
ref on compressed inputs across the prune × fused grid, recall floors vs
the uncompressed oracle, and the ≥ 2× byte-accounting drop the compressed
layout is supposed to buy."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import GeoSearchEngine, QueryBudgets
from repro.core.spatial_index import (
    SCALE_BLOCK,
    block_metadata_np,
    build_spatial_index_np,
    quantize_amps_np,
)
from repro.core.text_index import (
    DOC_PLANE_PAD,
    POSTING_BLOCK,
    build_text_index_np,
    decode_posting_blocks,
    probe_term,
)
from repro.corpus import make_corpus, make_query_trace, make_zipf_trace, pad_trace_batch
from repro.kernels.sweep_score.ops import sweep_score, sweep_score_pruned
from repro.kernels.sweep_score.ref import sweep_score_pruned_ref, sweep_score_ref

INVALID = 2**31 - 1


# ---------------------------------------------------------------------------
# posting store: delta + bit-packed round-trip
# ---------------------------------------------------------------------------

def _decode_all_terms(idx):
    """Decode every term's packed blocks back to flat doc-id lists."""
    bto = np.asarray(idx.blk_term_off)
    blk_len = np.asarray(idx.blk_len)
    out = []
    for t in range(idx.n_terms):
        ids = []
        for b in range(int(bto[t]), int(bto[t + 1])):
            dec = np.asarray(decode_posting_blocks(idx, jnp.int32(b)))
            ids.append(dec[: int(blk_len[b])])
        out.append(np.concatenate(ids) if ids else np.zeros((0,), np.int64))
    return out


def test_posting_roundtrip_edge_cases():
    """Empty terms, single-posting lists, a maximal delta gap, and a list
    spanning multiple 128-posting blocks all decode back exactly."""
    N = 300  # docs; term 3 spans 3 blocks (300 > 2·128)
    doc_terms = []
    for d in range(N):
        t = [3]  # term 3: every doc (multi-block list)
        if d == 0:
            t += [1, 2]  # term 1: single posting; term 2 gets doc 0
        if d == N - 1:
            t += [2]  # term 2: {0, N-1} — the maximal delta gap
        doc_terms.append(np.asarray(t, np.int32))
    # term 0 stays empty
    comp = build_text_index_np(doc_terms, n_terms=4, compress=True)
    raw = build_text_index_np(doc_terms, n_terms=4, compress=False)
    assert comp.is_compressed and not raw.is_compressed
    assert comp.postings.shape[0] == 0  # packed words are the store
    offs = np.asarray(raw.offsets)
    decoded = _decode_all_terms(comp)
    for t in range(4):
        want = np.asarray(raw.postings)[offs[t] : offs[t + 1]]
        np.testing.assert_array_equal(decoded[t], want)
    # impacts stay CSR-addressed at full length in both layouts
    np.testing.assert_array_equal(np.asarray(comp.impacts), np.asarray(raw.impacts))
    # compressed store is strictly smaller per posting
    assert comp.posting_bytes < raw.posting_bytes


def test_posting_roundtrip_random_corpus():
    corpus = make_corpus(n_docs=500, n_terms=120, seed=21)
    comp = build_text_index_np(corpus.doc_terms, corpus.n_terms, compress=True)
    raw = build_text_index_np(corpus.doc_terms, corpus.n_terms, compress=False)
    offs = np.asarray(raw.offsets)
    decoded = _decode_all_terms(comp)
    for t in range(corpus.n_terms):
        np.testing.assert_array_equal(
            decoded[t], np.asarray(raw.postings)[offs[t] : offs[t + 1]]
        )


def test_probe_term_matches_uncompressed():
    """The packed probe (block-head bisection + one-block decode) agrees
    with the CSR binary search on membership AND impacts."""
    corpus = make_corpus(n_docs=400, n_terms=90, seed=22)
    comp = build_text_index_np(corpus.doc_terms, corpus.n_terms, compress=True)
    raw = build_text_index_np(corpus.doc_terms, corpus.n_terms, compress=False)
    rng = np.random.default_rng(23)
    doc_ids = jnp.asarray(rng.integers(0, 400, (256,)).astype(np.int32))
    for t in [0, 1, 17, 89]:
        m_c, i_c = probe_term(comp, jnp.int32(t), doc_ids)
        m_r, i_r = probe_term(raw, jnp.int32(t), doc_ids)
        np.testing.assert_array_equal(np.asarray(m_c), np.asarray(m_r))
        np.testing.assert_array_equal(np.asarray(i_c), np.asarray(i_r))


@pytest.mark.parametrize("layout", ["docid", "impact"])
def test_doc_plane_matches_csr_and_packed_store(bit_extract_decode, layout):
    """Every doc_plane row holds its block's CSR doc ids below blk_len and
    the pad past it, and agrees on every block with a bit-extracting
    decode of the packed words, PForDelta exception blocks included."""
    corpus = make_corpus(n_docs=2000, n_terms=150, seed=3)
    comp = build_text_index_np(
        corpus.doc_terms, corpus.n_terms, compress=True, layout=layout
    )
    raw = build_text_index_np(
        corpus.doc_terms, corpus.n_terms, compress=False, layout=layout
    )
    assert int(np.asarray(comp.blk_n_exc).sum()) > 0  # exception blocks
    plane = np.asarray(comp.doc_plane)
    NB = comp.blk_first.shape[0]
    assert plane.shape == (NB, POSTING_BLOCK) and plane.dtype == np.int32
    assert raw.doc_plane.shape == (0, POSTING_BLOCK)  # raw ids are the store
    blk_pos, blk_len = np.asarray(comp.blk_pos), np.asarray(comp.blk_len)
    live = np.arange(POSTING_BLOCK)[None, :] < blk_len[:, None]
    csr = np.asarray(raw.postings)[
        np.where(live, blk_pos[:, None] + np.arange(POSTING_BLOCK), 0)
    ]
    np.testing.assert_array_equal(np.where(live, plane, 0), np.where(live, csr, 0))
    assert (plane[~live] == DOC_PLANE_PAD).all()
    packed = np.asarray(bit_extract_decode(comp, jnp.arange(NB, dtype=jnp.int32)))
    np.testing.assert_array_equal(np.where(live, packed, 0), np.where(live, plane, 0))


@pytest.mark.parametrize("compress", ["none", "f16"])
@pytest.mark.parametrize("layout", ["docid", "impact"])
def test_plane_rows_counts_doc_plane_reads(compress, layout):
    """``plane_rows`` counts the doc_plane rows a batch row reads: the
    scan's mask scatters and probe rounds, pruned TEXT-FIRST's select and
    probes; 0 where the raw postings are the store."""
    from repro.core import algorithms as alg
    from repro.kernels.text_probe.ops import window_size

    corpus = make_corpus(n_docs=1200, n_terms=150, seed=11)
    mc = 16
    eng = GeoSearchEngine.build(
        corpus.doc_terms, corpus.doc_rects, corpus.doc_amps, corpus.n_terms,
        pagerank=corpus.pagerank, grid=32, compress=compress, layout=layout,
        budgets=QueryBudgets(max_candidates=mc, top_k=10, prune=True),
    )
    trace = make_query_trace(corpus, n_queries=24, seed=12)
    text = eng.index.text
    scan = eng.query(trace, "scan")
    tf = eng.query(trace, "text_first")
    scan_rows = np.asarray(scan.stats["plane_rows"])
    tf_rows = np.asarray(tf.stats["plane_rows"])
    assert scan_rows.shape == tf_rows.shape == (24,)
    if compress == "none":
        assert (scan_rows == 0).all() and (tf_rows == 0).all()
        return
    d = trace.terms.shape[1]
    segs = text.max_term_segments if layout == "impact" else 1
    mask_blocks = min(alg.SCAN_MASK_BLOCKS, text.max_term_blocks)
    rounds = np.asarray(scan.stats["scan_rounds"])
    assert rounds.max() > 0
    np.testing.assert_array_equal(
        scan_rows, d * mask_blocks + rounds * d * mc * segs
    )
    Cs = min(mc, window_size(text.max_term_blocks) * POSTING_BLOCK)
    assert (tf_rows == Cs * (1 + d * segs)).all() and (tf_rows > 0).all()


# ---------------------------------------------------------------------------
# amplitude store: int8 quantization round-trip
# ---------------------------------------------------------------------------

def test_quantize_amps_roundtrip_properties():
    """Negative amps, an all-zero block, and a ragged tail: decode error is
    bounded by scale/2, signs survive, zero blocks decode to exact zeros."""
    rng = np.random.default_rng(31)
    T = 2 * SCALE_BLOCK + 37  # ragged tail block
    amps = rng.uniform(-2.0, 2.0, T).astype(np.float32)
    amps[SCALE_BLOCK : 2 * SCALE_BLOCK] = 0.0  # all-zero block
    q, scale = quantize_amps_np(amps)
    assert q.dtype == np.int8 and scale.dtype == np.float32
    assert q.shape == (T,) and scale.shape == (3,)
    dec = q.astype(np.float32) * np.repeat(scale, SCALE_BLOCK)[:T]
    err = np.abs(dec - amps)
    bound = np.repeat(scale, SCALE_BLOCK)[:T] * 0.5 + 1e-7
    assert (err <= bound).all()
    # zero block: exact zeros with the sentinel scale
    assert scale[1] == 1.0 and (dec[SCALE_BLOCK : 2 * SCALE_BLOCK] == 0.0).all()
    # signs preserved wherever the quantized value is nonzero
    nz = q != 0
    assert (np.sign(dec[nz]) == np.sign(amps[nz])).all()


def test_quantize_amps_single_element():
    q, scale = quantize_amps_np(np.asarray([-0.75], np.float32))
    assert q.shape == (1,) and scale.shape == (1,)
    assert q[0] == -127 and np.isclose(q[0] * scale[0], -0.75)


@pytest.mark.parametrize("mode", ["none", "f16", "int8"])
def test_text_block_max_impact_bounds_decoded_values(mode):
    """``blk_max_impact`` is computed from the *stored* (post-quantization)
    impact values, so it upper-bounds — exactly equals the max of — every
    decoded impact in its block across compress modes, including all-zero
    blocks (idf-zero term), empty terms, and ragged tail blocks."""
    rng = np.random.default_rng(44)
    n_terms = 60
    docs = [
        rng.integers(0, 50, size=int(rng.integers(1, 40))).astype(np.int32)
        for _ in range(300)
    ]
    docs.append(np.full((200,), 3, np.int32))  # hot ragged multi-block term
    idf = np.log(1.0 + len(docs) / np.maximum(
        np.bincount(np.concatenate(docs), minlength=n_terms), 1.0
    ))
    idf[7] = 0.0  # term 7's blocks store all-zero impacts
    idx = build_text_index_np(
        docs, n_terms, idf=idf,
        compress=(mode != "none"),
        impact_dtype=(np.float16 if mode != "none" else None),
    )
    blk_pos = np.asarray(idx.blk_pos)
    blk_len = np.asarray(idx.blk_len)
    imp = np.asarray(idx.impacts).astype(np.float32)
    bmi = np.asarray(idx.blk_max_impact)
    bto = np.asarray(idx.blk_term_off)
    assert (bto[1:] >= bto[:-1]).all()  # empty terms → zero blocks
    assert blk_pos.shape[0] > 0
    saw_ragged = saw_zero = False
    for b in range(blk_pos.shape[0]):
        vals = imp[blk_pos[b] : blk_pos[b] + blk_len[b]]
        want = float(vals.max()) if len(vals) else 0.0
        assert bmi[b] == np.float32(want), b
        assert (vals <= bmi[b]).all(), b
        saw_ragged |= 0 < blk_len[b] < POSTING_BLOCK
        saw_zero |= len(vals) > 0 and want == 0.0
    assert saw_ragged and saw_zero


def test_spatial_block_metadata_from_decoded_values():
    """int8 build computes block-max bounds from the dequantized amps (not
    the raw f32 inputs), so pruning bounds stay safe under quantization."""
    rng = np.random.default_rng(33)
    T = 600
    lo = rng.uniform(0, 0.9, (T, 2)).astype(np.float32)
    rects = np.concatenate([lo, lo + 0.05], axis=1).astype(np.float32)
    amps = rng.uniform(0, 1, T).astype(np.float32)
    doc_rects = rects[:, None, :]
    doc_amps = amps[:, None]
    idx = build_spatial_index_np(doc_rects, doc_amps, grid=16, compress="int8")
    sc = np.asarray(idx.tp_amp_scale)
    dec = np.asarray(idx.tp_amps).astype(np.float32) * np.repeat(sc, SCALE_BLOCK)[:T]
    _, want_amp, want_mass = block_metadata_np(
        np.asarray(idx.tp_rects).astype(np.float32), dec, idx.block_size
    )
    np.testing.assert_array_equal(np.asarray(idx.blk_max_amp), want_amp)
    np.testing.assert_array_equal(np.asarray(idx.blk_max_mass), want_mass)
    # doc-id column narrows to i16 when the corpus fits
    assert np.asarray(idx.tp_doc_ids).dtype == np.int16
    assert idx.tp_bytes < 12.0  # < f16's 12 B/toe-print


# ---------------------------------------------------------------------------
# kernel ≡ ref on compressed inputs (prune × fused grid)
# ---------------------------------------------------------------------------

def _compressed_store(rng, T, mode):
    lo = rng.uniform(0, 0.9, (T, 2)).astype(np.float32)
    wh = rng.uniform(0.01, 0.08, (T, 2)).astype(np.float32)
    rects = np.concatenate([lo, lo + wh], axis=1).astype(np.float16)
    amps = rng.uniform(-0.2, 1.0, T).astype(np.float32)
    if mode == "int8":
        store, scale = quantize_amps_np(amps)
        dec = store.astype(np.float32) * np.repeat(scale, SCALE_BLOCK)[:T]
    else:
        store, scale = amps.astype(np.float16), None
        dec = store.astype(np.float32)
    return rects, store, scale, dec


@pytest.mark.parametrize("mode", ["f16", "int8"])
def test_kernel_matches_ref_on_compressed_store(mode):
    """In-kernel decode of the compressed planes bit-matches the jnp
    reference that dequantizes with the same astype-then-multiply order."""
    rng = np.random.default_rng(41 if mode == "f16" else 43)
    T, budget, k = 5000, 2048, 4
    rects, store, scale, _ = _compressed_store(rng, T, mode)
    ss = np.sort(rng.integers(0, T, k)).astype(np.int32)
    ee = np.minimum(ss + rng.integers(1, budget + 500, k), T).astype(np.int32)
    ss[k // 2] = INVALID
    ee[k // 2] = INVALID
    qr = jnp.asarray(np.array([[0.2, 0.2, 0.6, 0.6], [0.5, 0.5, 0.9, 0.9]], np.float32))
    qa = jnp.ones((2,))
    sc = None if scale is None else jnp.asarray(scale)
    args = (jnp.asarray(rects), jnp.asarray(store), jnp.asarray(ss), jnp.asarray(ee), qr, qa)
    got = sweep_score(*args, budget, tp_amp_scale=sc)
    want = sweep_score_ref(*args, budget, tp_amp_scale=sc)
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))


@pytest.mark.parametrize("mode", ["f16", "int8"])
@pytest.mark.parametrize("bs,C,floor", [(128, 1024, 0.0), (256, 512, 0.02)])
def test_pruned_kernel_matches_ref_on_compressed_store(mode, bs, C, floor):
    """The manual-DMA pruned kernel decodes compressed blocks identically
    to the oracle — scores, valid, streamed, and both block counters."""
    rng = np.random.default_rng(1000 + bs + (1 if mode == "int8" else 0))
    T, budget, k = 5000, 2048, 4
    rects, store, scale, dec = _compressed_store(rng, T, mode)
    bm, ba, bmass = block_metadata_np(rects.astype(np.float32), dec, bs)
    ss = np.sort(rng.integers(0, T, k)).astype(np.int32)
    ee = np.minimum(ss + rng.integers(1, budget + 500, k), T).astype(np.int32)
    qr = jnp.asarray(np.array([[0.2, 0.2, 0.6, 0.6], [0.5, 0.5, 0.9, 0.9]], np.float32))
    qa = jnp.ones((2,))
    sc = None if scale is None else jnp.asarray(scale)
    args = (
        jnp.asarray(rects), jnp.asarray(store),
        jnp.asarray(bm), jnp.asarray(ba), jnp.asarray(bmass),
        jnp.asarray(ss), jnp.asarray(ee), qr, qa,
    )
    got = sweep_score_pruned(*args, budget, C, bs, floor, tp_amp_scale=sc)
    want = sweep_score_pruned_ref(*args, budget, C, bs, floor, tp_amp_scale=sc)
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))  # valid
    np.testing.assert_array_equal(np.asarray(got[2]), np.asarray(want[2]))  # streamed
    assert int(got[3]) == int(want[3]) and int(got[4]) == int(want[4])
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))


# ---------------------------------------------------------------------------
# end-to-end: recall floors and the byte-accounting drop
# ---------------------------------------------------------------------------

def _recall_vs(a, b):
    ai, bi = np.asarray(a.ids), np.asarray(b.ids)
    va = ai >= 0
    found = (
        (ai[:, :, None] == bi[:, None, :]) & va[:, :, None] & (bi[:, None, :] >= 0)
    ).any(-1)
    return found.sum() / max(va.sum(), 1)


@pytest.fixture(scope="module")
def smoke_corpus_and_trace():
    corpus = make_corpus(n_docs=1200, n_terms=400, seed=9)
    trace = pad_trace_batch(make_zipf_trace(corpus, n_queries=64, pool_size=48, seed=10))
    return corpus, trace


def _engine(corpus, compress, **bud_kw):
    budgets = QueryBudgets(
        max_candidates=1024, max_tiles=256, k_sweeps=8, sweep_budget=256,
        top_k=10, **bud_kw,
    )
    return GeoSearchEngine.build(
        corpus.doc_terms, corpus.doc_rects, corpus.doc_amps, corpus.n_terms,
        pagerank=corpus.pagerank, grid=32, budgets=budgets, compress=compress,
    )


@pytest.mark.parametrize("mode", ["f16", "int8"])
@pytest.mark.parametrize("fused", [False, True])
def test_compressed_recall_vs_uncompressed_oracle(
    smoke_corpus_and_trace, mode, fused
):
    """recall@10 ≥ 0.99 vs the uncompressed engine at both precisions."""
    corpus, trace = smoke_corpus_and_trace
    un = _engine(corpus, "none").query(trace, "k_sweep", fused=fused)
    co = _engine(corpus, mode).query(trace, "k_sweep", fused=fused)
    assert _recall_vs(un, co) >= 0.99


def test_compressed_bytes_drop_2x_at_recall_floor(smoke_corpus_and_trace):
    """The acceptance bar: on the zipf smoke trace the compressed store
    streams ≤ half the bytes (postings + spatial) at recall@10 ≥ 0.99."""
    corpus, trace = smoke_corpus_and_trace
    un = _engine(corpus, "none").query(trace, "k_sweep")

    def tot(r):
        return float(np.asarray(r.stats["bytes_postings"], np.float64).sum()) + float(
            np.asarray(r.stats["bytes_spatial"], np.float64).sum()
        )

    for mode in ["f16", "int8"]:
        co = _engine(corpus, mode).query(trace, "k_sweep")
        assert _recall_vs(un, co) >= 0.99, mode
        assert tot(co) <= 0.5 * tot(un), f"{mode}: {tot(co)} vs {tot(un)}"


def test_compressed_prune_skips_blocks_and_bytes(smoke_corpus_and_trace):
    """Pruning composes with compression: skipped blocks charge no spatial
    bytes on the compressed store either, and the pruned compressed run
    streams fewer bytes than BOTH the unpruned compressed and the pruned
    uncompressed runs."""
    corpus, trace = smoke_corpus_and_trace

    def tot(r, k):
        return float(np.asarray(r.stats[k], np.float64).sum())

    un_c = _engine(corpus, "int8").query(trace, "k_sweep")
    pr_c = _engine(corpus, "int8", prune=True).query(trace, "k_sweep")
    pr_u = _engine(corpus, "none", prune=True).query(trace, "k_sweep")
    assert tot(pr_c, "blocks_skipped") > 0
    assert tot(pr_c, "bytes_spatial") < tot(un_c, "bytes_spatial")
    assert tot(pr_c, "bytes_spatial") < tot(pr_u, "bytes_spatial")
    assert tot(pr_c, "bytes_postings") < tot(pr_u, "bytes_postings")
    assert _recall_vs(pr_u, pr_c) >= 0.99
