"""End-to-end algorithm tests: the three paper algorithms and the exact
scan vs the exact oracle, graceful budget degradation, Pallas-scorer
equivalence."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import GeoSearchEngine, QueryBudgets
from repro.corpus import make_corpus, make_query_trace


@pytest.fixture(scope="module")
def engine_and_trace():
    corpus = make_corpus(n_docs=500, n_terms=120, seed=3)
    eng = GeoSearchEngine.build(
        corpus.doc_terms, corpus.doc_rects, corpus.doc_amps, corpus.n_terms,
        pagerank=corpus.pagerank, grid=32,
        budgets=QueryBudgets(
            max_candidates=512, max_tiles=256, k_sweeps=4, sweep_budget=1024, top_k=10
        ),
    )
    trace = make_query_trace(corpus, n_queries=24, seed=7)
    return eng, trace


ALGOS = ["text_first", "geo_first", "k_sweep", "scan"]


@pytest.mark.parametrize("algo", ALGOS)
def test_recall_vs_oracle(engine_and_trace, algo):
    eng, trace = engine_and_trace
    rec = eng.recall_at_k(trace, algo)
    assert rec >= 0.95, f"{algo} recall {rec}"


@pytest.mark.parametrize("algo", ALGOS)
def test_results_respect_semantics(engine_and_trace, algo):
    """Every returned doc must contain all query terms AND its footprint
    must intersect the query footprint (paper §III.B)."""
    eng, trace = engine_and_trace
    res = eng.query(trace, algo)
    ids = np.asarray(res.ids)
    scores = np.asarray(res.scores)
    text = eng.index.text
    offs = np.asarray(text.offsets)
    posts = np.asarray(text.postings)
    doc_rects = np.asarray(eng.index.spatial.doc_rects)
    q_terms = np.asarray(trace.terms)
    q_rects = np.asarray(trace.rects)
    for b in range(ids.shape[0]):
        for j, d in enumerate(ids[b]):
            if d < 0:
                continue
            assert np.isfinite(scores[b, j])
            for t in q_terms[b]:
                if t < 0:
                    continue
                sl = posts[offs[t] : offs[t + 1]]
                assert d in sl, f"doc {d} missing term {t}"
            inter = 0.0
            for r in doc_rects[d]:
                for q in q_rects[b]:
                    w = min(r[2], q[2]) - max(r[0], q[0])
                    h = min(r[3], q[3]) - max(r[1], q[1])
                    inter += max(w, 0) * max(h, 0)
            assert inter > 0, f"doc {d} no geo overlap"


def test_scores_sorted_descending(engine_and_trace):
    eng, trace = engine_and_trace
    for algo in ALGOS:
        s = np.asarray(eng.query(trace, algo).scores)
        finite = np.where(np.isfinite(s), s, -1e30)  # −inf diffs are nan
        assert (np.diff(finite, axis=1) <= 1e-6).all()


def test_budget_degradation_graceful():
    """Tiny budgets must not crash or return invalid docs — only lose recall."""
    corpus = make_corpus(n_docs=300, n_terms=80, seed=5)
    eng = GeoSearchEngine.build(
        corpus.doc_terms, corpus.doc_rects, corpus.doc_amps, corpus.n_terms,
        pagerank=corpus.pagerank, grid=16,
        budgets=QueryBudgets(
            max_candidates=16, max_tiles=8, k_sweeps=1, sweep_budget=32, top_k=5
        ),
    )
    trace = make_query_trace(corpus, n_queries=8, seed=2)
    for algo in ALGOS:
        res = eng.query(trace, algo)
        ids = np.asarray(res.ids)
        assert ((ids >= -1) & (ids < 300)).all()


@pytest.mark.parametrize("compress", ["none", "f16"])
@pytest.mark.parametrize("layout", ["docid", "impact"])
@pytest.mark.parametrize("masks", ["scatter", "bitmap", "none"])
def test_scan_equals_oracle(monkeypatch, compress, layout, masks):
    """The scan's answers are the oracle's, ids and score bits, over
    several rounds (max_candidates far below the corpus), whether its term
    masks come from scattered posting lists, from bitmaps, or not at all
    (lists too long and no bitmap)."""
    from repro.core import algorithms as alg

    monkeypatch.setattr(alg, "SCAN_MASK_BLOCKS", 1024 if masks == "scatter" else 1)
    corpus = make_corpus(n_docs=1200, n_terms=150, seed=11)
    eng = GeoSearchEngine.build(
        corpus.doc_terms, corpus.doc_rects, corpus.doc_amps, corpus.n_terms,
        pagerank=corpus.pagerank, grid=32, compress=compress, layout=layout,
        n_bitmap_terms=150 if masks == "bitmap" else 0,
        budgets=QueryBudgets(max_candidates=16, top_k=10),
    )
    trace = make_query_trace(corpus, n_queries=24, seed=12)
    got = eng.query(trace, "scan")
    want = eng.oracle(trace, 10)
    np.testing.assert_array_equal(np.asarray(got.ids), np.asarray(want.ids))
    np.testing.assert_array_equal(np.asarray(got.scores), np.asarray(want.scores))
    rounds = np.asarray(got.stats["scan_rounds"])
    if masks == "scatter":
        # every term's exact impacts in the bound: one round certifies
        assert (rounds <= 1).all()
    else:
        assert rounds.max() > 1  # the budget forced more than one round
    assert (rounds <= -(-1200 // 16)).all()


def test_exact_build_gives_long_lists_bitmaps():
    """Exact budgets build a bitmap for every term the scan cannot
    scatter, and each bitmap holds exactly its term's documents."""
    from repro.core import algorithms as alg

    corpus = make_corpus(n_docs=3000, n_terms=60, seed=4)
    eng = GeoSearchEngine.build(
        corpus.doc_terms, corpus.doc_rects, corpus.doc_amps, corpus.n_terms,
        pagerank=corpus.pagerank, grid=16,
        budgets=QueryBudgets(max_candidates=16, exact=True),
    )
    text = eng.index.text
    offs = np.asarray(text.offsets)
    df = np.diff(offs)
    ids = np.asarray(text.bitmap_term_ids)
    assert set(ids) == set(np.flatnonzero(df >= alg.SCAN_BITMAP_MIN_DF))
    bits = np.unpackbits(
        np.asarray(text.bitmaps).view(np.uint8), axis=1, bitorder="little"
    )
    posts = np.asarray(text.postings)
    for row, t in enumerate(ids):
        assert np.array_equal(np.flatnonzero(bits[row]), np.sort(posts[offs[t] : offs[t + 1]]))


def test_pallas_scorer_matches_jnp(engine_and_trace):
    from repro.kernels.geo_score.ops import geo_score_toeprints

    eng, trace = engine_and_trace
    a = eng.query(trace, "k_sweep")
    b = eng.query(trace, "k_sweep", tp_scorer=geo_score_toeprints)
    np.testing.assert_array_equal(np.asarray(a.ids), np.asarray(b.ids))
    np.testing.assert_allclose(
        np.asarray(a.scores), np.asarray(b.scores), rtol=1e-5, atol=1e-6
    )


def test_ksweep_stats_account_io(engine_and_trace):
    eng, trace = engine_and_trace
    res = eng.query(trace, "k_sweep")
    stats = {k: np.asarray(v) for k, v in res.stats.items()}
    assert (stats["sweeps"] <= eng.budgets.k_sweeps).all()
    assert (stats["sweep_slack"] >= 0).all()
    assert (
        stats["bytes_spatial"]
        == stats["sweeps"] * eng.budgets.sweep_budget * (16 + 4 + 4)
    ).all()


def test_quantized_impacts_similar_ranking(engine_and_trace):
    """Lossy-compressed (f16) impacts preserve top-k (paper future work).

    Quantization goes through the one compression entry point
    (``build_text_index_np(..., impact_dtype=...)``, what ``compress``
    modes use) instead of the deprecated post-build shim.
    """
    from repro.core.engine import GeoIndex
    from repro.core.text_index import build_text_index_np

    eng, trace = engine_and_trace
    corpus = make_corpus(n_docs=500, n_terms=120, seed=3)  # fixture's corpus
    q_index = GeoIndex(
        text=build_text_index_np(
            corpus.doc_terms, corpus.n_terms, impact_dtype=jnp.float16
        ),
        spatial=eng.index.spatial,
        pagerank=eng.index.pagerank,
    )
    eng2 = GeoSearchEngine(index=q_index, budgets=eng.budgets, weights=eng.weights)
    a = eng.query(trace, "k_sweep")
    b = eng2.query(trace, "k_sweep")
    # top-1 must agree on ≥90% of queries
    agree = (np.asarray(a.ids)[:, 0] == np.asarray(b.ids)[:, 0]).mean()
    assert agree >= 0.9


def _per_doc_oracle(eng, batch, k):
    """The oracle as a per-document scan: every document probed for every
    query term (``text_score_of_docs``) — the reference for the
    term-at-a-time oracle."""
    from repro.core import footprint as fp
    from repro.core import ranking
    from repro.core.text_index import text_score_of_docs

    idx = eng.index
    all_docs = jnp.arange(idx.spatial.n_docs, dtype=jnp.int32)

    def one(terms, q_rects, q_amps):
        match, tscore = text_score_of_docs(idx.text, terms, all_docs)
        g = fp.geo_score(idx.spatial.doc_rects, idx.spatial.doc_amps, q_rects, q_amps)
        qm = fp.query_mass(q_rects, q_amps)
        score = ranking.combine_scores(eng.weights, tscore, g, idx.pagerank, qm)
        return ranking.top_k(jnp.where(match, score, -jnp.inf), all_docs, k)

    return jax.vmap(one)(batch.terms, batch.rects, batch.amps)


@pytest.mark.parametrize("layout", ["docid", "impact"])
@pytest.mark.parametrize("compress", ["none", "f16", "int8"])
def test_oracle_matches_per_doc_scan(layout, compress):
    """Ids identical to the per-document scan, including a repeated term,
    a one-term query and an all-padding query.  Text scores are the same
    bits; the final scores may differ in the last bit, where XLA fuses the
    weighted sum differently in the two programs."""
    from repro.corpus import TraceQuery, make_zipf_trace, pad_trace_batch

    corpus = make_corpus(n_docs=700, n_terms=90, seed=21)
    eng = GeoSearchEngine.build(
        corpus.doc_terms, corpus.doc_rects, corpus.doc_amps, corpus.n_terms,
        pagerank=corpus.pagerank, grid=32, compress=compress, layout=layout,
    )
    trace = make_zipf_trace(corpus, n_queries=20, pool_size=20, seed=22)
    r, a = trace[0].rects, trace[0].amps
    trace += [
        TraceQuery(terms=np.array([1, 1], np.int32), rects=r, amps=a),
        TraceQuery(terms=np.array([0], np.int32), rects=r, amps=a),
        TraceQuery(terms=np.zeros((0,), np.int32), rects=r, amps=a),
    ]
    batch = pad_trace_batch(trace)
    got = eng.oracle(batch, 10)
    want_ids, want_scores = _per_doc_oracle(eng, batch, 10)
    np.testing.assert_array_equal(np.asarray(got.ids), np.asarray(want_ids))
    np.testing.assert_allclose(
        np.asarray(got.scores), np.asarray(want_scores), rtol=2e-7, atol=0
    )
