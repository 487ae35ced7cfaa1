"""Block-max pruned TEXT-FIRST: kernel/ref bit-match across compression
modes, select-stage safety, prune=False bit-identity, recall floors on
the prune × fused grid, deterministic block skipping with probe/byte
accounting, and the serving-layer threading."""
from dataclasses import replace

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import GeoSearchEngine, QueryBudgets, ranking
from repro.core import text_index as T
from repro.core.distributed import HashPartitioner
from repro.core.engine import GeoIndex
from repro.corpus import TraceQuery, make_corpus, make_query_trace, pad_trace_batch
from repro.kernels.text_probe.ops import text_probe_pruned, window_size
from repro.kernels.text_probe.ref import text_probe_pruned_ref


# ---------------------------------------------------------------------------
# corpora: a natural zipf corpus and a bimodal hot-term corpus whose
# driver posting list provably triggers θ-adaptive block skipping
# ---------------------------------------------------------------------------

def _hot_corpus(n_docs=2560, n_short=1024, n_terms=64, seed=0):
    """Terms 0 and 1 appear in EVERY doc; docs < ``n_short`` are 2-term
    docs (impact idf/√2) and the rest are 64-term docs (impact idf/8).

    Postings are docID-ordered, so the driver list's first 8 blocks (one
    kernel tile, 1024 postings) hold only high-impact postings: after the
    first tile the running θ provably exceeds every later block's bound
    and the remaining blocks are skipped — deterministically."""
    rng = np.random.default_rng(seed)
    docs = []
    for d in range(n_docs):
        if d < n_short:
            docs.append(np.array([0, 1], np.int32))
        else:
            fill = rng.integers(2, n_terms, size=62).astype(np.int32)
            docs.append(np.concatenate([np.array([0, 1], np.int32), fill]))
    rects = np.tile(
        np.array([[0.1, 0.1, 0.9, 0.9]], np.float32), (n_docs, 1, 1)
    )
    amps = np.ones((n_docs, 1), np.float32)
    return docs, rects, amps, n_terms


def _hot_trace(n_queries=8):
    q = TraceQuery(
        terms=np.array([0, 1], np.int32),
        rects=np.array([[0.2, 0.2, 0.8, 0.8]], np.float32),
        amps=np.ones((1,), np.float32),
    )
    return pad_trace_batch([q] * n_queries)


def _hot_engine(C, seed=0, **bud_kw):
    docs, rects, amps, n_terms = _hot_corpus(seed=seed)
    budgets = QueryBudgets(
        max_candidates=C, max_tiles=64, k_sweeps=4, sweep_budget=256,
        top_k=10, **bud_kw,
    )
    return GeoSearchEngine.build(
        docs, rects, amps, n_terms, pagerank=np.zeros(len(docs), np.float32),
        grid=16, budgets=budgets,
    )


def _engine(corpus, C, grid=32, **bud_kw):
    budgets = QueryBudgets(
        max_candidates=C, max_tiles=256, k_sweeps=4, sweep_budget=1024,
        top_k=10, **bud_kw,
    )
    return GeoSearchEngine.build(
        corpus.doc_terms, corpus.doc_rects, corpus.doc_amps, corpus.n_terms,
        pagerank=corpus.pagerank, grid=grid, budgets=budgets,
    )


def _with_budgets(eng, **kw):
    """Fresh engine sharing the built index (its own compiled-fn cache)."""
    return GeoSearchEngine(
        index=eng.index, budgets=replace(eng.budgets, **kw), weights=eng.weights
    )


def _recall_vs(a, b):
    ai, bi = np.asarray(a.ids), np.asarray(b.ids)
    va = ai >= 0
    found = (
        (ai[:, :, None] == bi[:, None, :]) & va[:, :, None] & (bi[:, None, :] >= 0)
    ).any(-1)
    return found.sum() / max(va.sum(), 1)


# ---------------------------------------------------------------------------
# kernel vs ref: bit-match across stored dtypes × posting compression
# ---------------------------------------------------------------------------

def _probe_args(text, t0, w_text, rest_ub):
    plane = text.imp_plane
    b0 = text.blk_term_off[t0]
    nb = text.blk_term_off[t0 + 1] - b0
    return plane, text.blk_max_impact, text.blk_len, jnp.int32(b0), nb


@pytest.mark.parametrize("compress", [False, True])
@pytest.mark.parametrize("impact_dtype", [None, jnp.float16])
@pytest.mark.parametrize("C,floor_frac", [(256, 0.0), (2048, 0.0), (256, 0.4)])
def test_pruned_kernel_matches_ref(compress, impact_dtype, C, floor_frac):
    """The Pallas probe kernel and the jnp reference agree bit-for-bit on
    scores, masks, AND the per-block skip counters — on f32 and f16
    stored impacts, compressed and uncompressed posting layouts, and a
    multi-tile (max_term_blocks > 8) driver list."""
    docs, _, _, n_terms = _hot_corpus(n_docs=2560)
    text = T.build_text_index_np(
        docs, n_terms, compress=compress, impact_dtype=impact_dtype
    )
    assert text.max_term_blocks > 8  # multi-tile window, ragged tail
    w_text = jnp.float32(1.0)
    for t0, rest_ub in [(0, 0.7), (1, 0.0), (5, 1.3)]:
        plane, bmi, blens, b0, nb = _probe_args(text, t0, w_text, rest_ub)
        tmax = float(np.asarray(text.blk_max_impact).max())
        floor = jnp.float32(floor_frac * (tmax + rest_ub))
        args = (plane, bmi, blens, b0, nb, w_text, jnp.float32(rest_ub), floor)
        kw = dict(max_candidates=C, max_term_blocks=text.max_term_blocks)
        got = text_probe_pruned(*args, **kw)
        want = text_probe_pruned_ref(*args, **kw)
        for g, w, name in zip(got, want, ["opt", "valid", "streamed",
                                          "blocks_scored", "blocks_active"]):
            np.testing.assert_array_equal(
                np.asarray(g), np.asarray(w), err_msg=f"t0={t0} {name}"
            )


def test_kernel_select_safety_property():
    """θ never overshoots: any valid driver posting whose optimistic score
    beats max(C_eff-th largest optimistic, floor) must be streamed."""
    docs, _, _, n_terms = _hot_corpus(n_docs=2560, seed=3)
    text = T.build_text_index_np(docs, n_terms)
    w_text, rest_ub = 1.0, 0.35
    plane, bmi, blens, b0, nb = _probe_args(text, 0, jnp.float32(w_text), rest_ub)
    for C, floor in [(256, 0.0), (256, 0.5), (1024, 0.0), (4096, 0.0)]:
        opt, valid, streamed, b_scored, b_active = text_probe_pruned(
            plane, bmi, blens, b0, nb, jnp.float32(w_text),
            jnp.float32(rest_ub), jnp.float32(floor),
            max_candidates=C, max_term_blocks=text.max_term_blocks,
        )
        valid = np.asarray(valid)
        streamed = np.asarray(streamed)
        # true optimistic score of every window position (skipped or not)
        n_win = window_size(text.max_term_blocks)
        rows = np.clip(int(b0) + np.arange(n_win), 0, bmi.shape[0] - 1)
        imp = np.asarray(plane, np.float32)[rows]
        true_opt = (w_text * imp + rest_ub).reshape(-1)
        c_eff = max(1, -(-C // 1024)) * 1024
        pos = np.sort(true_opt[valid])[::-1]
        theta_cap = pos[c_eff - 1] if len(pos) >= c_eff else 0.0
        must_keep = valid & (true_opt > max(theta_cap, floor))
        assert streamed[must_keep].all(), (C, floor)
        # streamed scores are exact (not bounds)
        kept = valid & streamed
        np.testing.assert_allclose(
            np.asarray(opt)[kept], true_opt[kept], rtol=1e-6, atol=1e-7
        )
        assert int(b_scored) <= int(b_active)


# ---------------------------------------------------------------------------
# prune=False bit-identity: the unpruned path never reads block-max
# metadata, so zeroing it cannot change ids, scores, or stats
# ---------------------------------------------------------------------------

def test_prune_false_ignores_block_metadata():
    corpus = make_corpus(n_docs=500, n_terms=120, seed=3)
    eng = _engine(corpus, C=512)
    trace = make_query_trace(corpus, n_queries=16, seed=7)
    a = eng.query(trace, "text_first")
    zeroed = replace(
        eng.index.text, blk_max_impact=jnp.zeros_like(eng.index.text.blk_max_impact)
    )
    eng2 = GeoSearchEngine(
        index=GeoIndex(
            text=zeroed, spatial=eng.index.spatial, pagerank=eng.index.pagerank
        ),
        budgets=eng.budgets, weights=eng.weights,
    )
    b = eng2.query(trace, "text_first")
    np.testing.assert_array_equal(np.asarray(a.ids), np.asarray(b.ids))
    np.testing.assert_array_equal(np.asarray(a.scores), np.asarray(b.scores))
    assert set(a.stats) == set(b.stats)
    for k in a.stats:
        np.testing.assert_array_equal(
            np.asarray(a.stats[k]), np.asarray(b.stats[k]), err_msg=k
        )
    # the unpruned path reports the new counters as zeros/constants
    assert float(np.asarray(a.stats["text_blocks_skipped"]).sum()) == 0
    assert float(np.asarray(a.stats["probes_saved"]).sum()) == 0


def test_pruned_matches_unpruned_when_covering():
    """With the candidate budget covering every driver list (C ≥ max df)
    and no floor, θ stays at 0, no block is skipped, and the pruned path
    returns EXACTLY the unpruned top-k — ids AND scores, ref and fused."""
    corpus = make_corpus(n_docs=400, n_terms=100, seed=11)
    eng = _engine(corpus, C=1024)
    trace = make_query_trace(corpus, n_queries=24, seed=12)
    un = eng.query(trace, "text_first")
    eng_p = _with_budgets(eng, prune=True)
    pr = eng_p.query(trace, "text_first")
    prf = eng_p.query(trace, "text_first", fused=True)
    np.testing.assert_array_equal(np.asarray(pr.ids), np.asarray(prf.ids))
    np.testing.assert_array_equal(np.asarray(pr.scores), np.asarray(prf.scores))
    np.testing.assert_array_equal(np.asarray(un.ids), np.asarray(pr.ids))
    np.testing.assert_array_equal(np.asarray(un.scores), np.asarray(pr.scores))


@pytest.mark.parametrize("prune", [False, True])
@pytest.mark.parametrize("fused", [False, True])
def test_prune_recall_floor_vs_oracle(prune, fused):
    """recall@10 ≥ 0.95 vs the exact oracle across the prune × fused grid."""
    corpus = make_corpus(n_docs=600, n_terms=150, seed=4)
    eng = _engine(corpus, C=512, prune=prune)
    trace = make_query_trace(corpus, n_queries=24, seed=5)
    rec = eng.recall_at_k(trace, "text_first", fused=fused)
    assert rec >= 0.95, f"prune={prune} fused={fused} recall {rec}"


@pytest.mark.parametrize("prune", [False, True])
def test_recall_over_fixed_seeds_vs_oracle(prune):
    """Over the fixed corpus seeds 0–7 (trace seed = corpus seed + 1):
    every query whose driver list fits the budget (df ≤ C) is answered
    exactly, so recall is lost only to the budget's truncation of longer
    driver lists; and the pruned traversal, which keeps the driver's best
    C postings rather than its first C, holds recall@10 ≥ 0.95 pooled over
    the eight seeds (the unpruned head-of-list cut reads ≈ 0.944 there)."""
    C = 512
    want, got = [], []
    for seed in range(8):
        corpus = make_corpus(n_docs=600, n_terms=150, seed=seed)
        eng = _engine(corpus, C=C, prune=prune)
        trace = make_query_trace(corpus, n_queries=24, seed=seed + 1)
        w = np.asarray(eng.oracle(trace, 10).ids)
        g = np.asarray(eng.query(trace, "text_first").ids)
        off = np.asarray(eng.index.text.offsets)
        terms = np.asarray(trace.terms)
        for q in range(terms.shape[0]):
            t = terms[q][terms[q] >= 0]
            if (off[t + 1] - off[t]).min() <= C:
                np.testing.assert_array_equal(
                    np.sort(w[q]), np.sort(g[q]), err_msg=f"seed {seed} query {q}"
                )
        want.append(w)
        got.append(g)
    if prune:
        rec = ranking.topk_recall_np(np.concatenate(want), np.concatenate(got))
        assert rec >= 0.95, f"pooled recall {rec}"


def test_prune_budget_degradation_graceful():
    """Tiny budgets with pruning must not crash or return invalid docs."""
    corpus = make_corpus(n_docs=300, n_terms=80, seed=5)
    eng = _engine(
        corpus, C=16, grid=16, prune=True, prune_eps=1e-3,
    )
    trace = make_query_trace(corpus, n_queries=8, seed=2)
    for fused in [False, True]:
        ids = np.asarray(eng.query(trace, "text_first", fused=fused).ids)
        assert ((ids >= -1) & (ids < 300)).all()


# ---------------------------------------------------------------------------
# stats: deterministic skipping, probe/byte savings (acceptance numbers)
# ---------------------------------------------------------------------------

def test_pruned_stats_skip_blocks_and_cut_io():
    """On the bimodal hot-term corpus the pruned traversal skips every
    post-first-tile block, and cuts n_probes AND bytes_postings ≥ 2× vs
    an unpruned traversal that needs C ≥ df for the same answers —
    at recall@10 ≥ 0.99."""
    trace = _hot_trace(8)
    un = _hot_engine(C=4096).query(trace, "text_first")
    eng_p = _hot_engine(C=256, prune=True)
    pr = eng_p.query(trace, "text_first")
    prf = eng_p.query(trace, "text_first", fused=True)

    def tot(r, k):
        return float(np.asarray(r.stats[k], np.float64).sum())

    np.testing.assert_array_equal(np.asarray(pr.ids), np.asarray(prf.ids))
    for k in pr.stats:
        np.testing.assert_array_equal(
            np.asarray(pr.stats[k]), np.asarray(prf.stats[k]), err_msg=k
        )
    assert _recall_vs(un, pr) >= 0.99
    assert tot(pr, "text_blocks_skipped") > 0
    assert tot(pr, "text_blocks_skipped") < tot(pr, "text_blocks_total")
    assert tot(pr, "probes_saved") > 0
    assert tot(un, "n_probes") >= 2.0 * tot(pr, "n_probes")
    assert tot(un, "bytes_postings") >= 2.0 * tot(pr, "bytes_postings")
    # unpruned path reports no skips and no savings
    assert tot(un, "text_blocks_skipped") == 0
    assert tot(un, "probes_saved") == 0


def test_prune_eps_floor_monotone():
    """Raising prune_eps only increases savings (probes monotone down)."""
    trace = _hot_trace(4)
    probes = []
    for eps in [0.0, 1e-2, 0.5]:
        eng = _hot_engine(C=256, prune=True, prune_eps=eps)
        res = eng.query(trace, "text_first")
        probes.append(float(np.asarray(res.stats["n_probes"], np.float64).sum()))
    assert probes[0] >= probes[1] >= probes[2]


# ---------------------------------------------------------------------------
# serving-layer threading
# ---------------------------------------------------------------------------

def test_sharded_executor_text_prune_matches_single():
    """A pruned TEXT-FIRST ShardedExecutor(S=1, hash) reproduces the
    single-device pruned engine and reports the new counter keys."""
    from repro.serving import ShardedExecutor, SingleDeviceExecutor

    corpus = make_corpus(n_docs=400, n_terms=100, seed=11)
    budgets = QueryBudgets(
        max_candidates=512, max_tiles=64, k_sweeps=4, sweep_budget=128,
        top_k=5, prune=True,
    )
    eng = GeoSearchEngine.build(
        corpus.doc_terms, corpus.doc_rects, corpus.doc_amps, corpus.n_terms,
        pagerank=corpus.pagerank, grid=16, budgets=budgets,
    )
    single = SingleDeviceExecutor(eng, "text_first", fused=True)
    sharded = ShardedExecutor.build(
        corpus.doc_terms, corpus.doc_rects, corpus.doc_amps, corpus.n_terms,
        pagerank=corpus.pagerank, n_shards=1, partitioner=HashPartitioner(),
        grid=16, budgets=budgets, algorithm="text_first", fused=True,
    )
    trace = make_query_trace(corpus, n_queries=16, seed=12)
    a = single.run(trace)
    b = sharded.run(trace)
    np.testing.assert_array_equal(np.asarray(a.ids), np.asarray(b.ids))
    for key in ["text_blocks_skipped", "text_blocks_total", "probes_saved",
                "n_probes", "bytes_postings"]:
        np.testing.assert_allclose(
            float(np.asarray(a.stats[key], np.float64).sum()),
            float(np.asarray(b.stats[key], np.float64).sum()),
            rtol=1e-6, err_msg=key,
        )


def test_composition_auto_prune_compress_routing_smoke(tmp_path):
    """prune × compress × routing × workers, composed: one open-loop serve
    with ``--algorithm auto --prune --compress int8 --routing footprint
    --workers 2`` holds the recall floor vs the exact oracle, all four
    telemetry exports validate, and per-plan audit counters are populated."""
    import json
    import math

    from repro.core import ranking
    from repro.core.distributed import RegionRangePartitioner
    from repro.corpus import make_zipf_trace, stamp_arrivals
    from repro.obs import Telemetry, validate_trace
    from repro.serving import DeadlineBatcher, GeoServer
    from repro.serving.factory import make_executor

    corpus = make_corpus(n_docs=500, n_terms=120, seed=19)
    budgets = QueryBudgets(
        max_candidates=512, max_tiles=64, k_sweeps=4, sweep_budget=256,
        top_k=10, prune=True,
    )
    tel = Telemetry()
    ex = make_executor(
        "sharded", corpus, algorithm="auto", budgets=budgets,
        partitioner=RegionRangePartitioner(), routing="footprint",
        n_shards=2, grid=16, fused=True, compress="int8", telemetry=tel,
    )
    srv = GeoServer(
        ex, cache=None,
        batcher=DeadlineBatcher(
            max_batch=8, max_terms=8, max_rects=4, max_wait_s=2e-3
        ),
        n_workers=2, telemetry=tel,
    )
    trace = stamp_arrivals(
        make_zipf_trace(corpus, n_queries=48, pool_size=24, seed=20),
        "poisson", rate_qps=500.0, seed=21,
    )
    rep = srv.run_trace(trace, warmup=False, arrival="poisson")
    assert rep.n_queries == 48
    assert rep.stats and any(
        k.startswith("bytes_") and float(np.asarray(v, np.float64).sum()) > 0
        for k, v in rep.stats.items()
    )
    # recall@10 vs the exact (uncompressed, unpruned) oracle
    batch = pad_trace_batch(trace)
    oracle_eng = GeoSearchEngine.build(
        corpus.doc_terms, corpus.doc_rects, corpus.doc_amps, corpus.n_terms,
        pagerank=corpus.pagerank, grid=16, budgets=budgets,
    )
    rec = ranking.topk_recall_np(
        np.asarray(oracle_eng.oracle(batch).ids), np.asarray(ex.run(batch).ids)
    )
    assert rec >= 0.9, rec
    # all four telemetry exports validate
    assert validate_trace(tel.tracer.to_trace_events()) == []
    js = tel.metrics.to_json()
    assert js["counters"]["server.queries_total"] >= 48
    assert "server_queries_total" in tel.metrics.to_prometheus()
    assert len(tel.events) > 0
    tel.events.to_jsonl(str(tmp_path / "events.jsonl"))
    tel.audit.to_jsonl(str(tmp_path / "audit.jsonl"))
    assert (tmp_path / "audit.jsonl").exists()
    assert json.loads((tmp_path / "events.jsonl").read_text().splitlines()[0])
    # per-plan counters: every executed plan joined with measured stats
    assert len(tel.audit.records) > 0
    assert len(tel.audit.joined) == len(tel.audit.records)
    for r in tel.audit.records:
        assert r.measured is not None
        errs = r.errors()
        assert all(e >= 0 and math.isfinite(e) for e in errs.values())
    summary = tel.audit.error_summary()
    assert summary and all(math.isfinite(v) for v in summary.values())


def test_mesh_executor_text_prune_fused_matches_single():
    """The SPMD mesh executor runs the pruned text-probe kernel inside its
    shard_map step and agrees with the single-device engine — including
    the pruning savings counters."""
    import jax
    from jax.sharding import Mesh

    from repro.serving import MeshExecutor, SingleDeviceExecutor

    corpus = make_corpus(n_docs=256, n_terms=64, seed=11)
    budgets = QueryBudgets(
        max_candidates=256, max_tiles=64, k_sweeps=4, sweep_budget=128,
        top_k=5, prune=True,
    )
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    meshx = MeshExecutor.build(
        corpus.doc_terms, corpus.doc_rects, corpus.doc_amps, corpus.n_terms,
        pagerank=corpus.pagerank, mesh=mesh, partitioner=HashPartitioner(),
        grid=16, budgets=budgets, algorithm="text_first", fused=True,
    )
    eng = GeoSearchEngine.build(
        corpus.doc_terms, corpus.doc_rects, corpus.doc_amps, corpus.n_terms,
        pagerank=corpus.pagerank, grid=16, budgets=budgets,
    )
    single = SingleDeviceExecutor(eng, "text_first", fused=True)
    batch = make_query_trace(corpus, n_queries=8, seed=12)
    a = single.run(batch)
    b = meshx.run(batch)
    np.testing.assert_array_equal(np.asarray(a.ids), np.asarray(b.ids))
    assert set(b.stats) == set(a.stats)
    for key in a.stats:
        np.testing.assert_allclose(
            float(np.asarray(b.stats[key], np.float64).sum()),
            float(np.asarray(a.stats[key], np.float64).sum()),
            rtol=1e-6, err_msg=key,
        )
