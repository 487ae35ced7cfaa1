"""Cost-based query planner tests: cost-model properties, plan-choice
goldens, the mixture-trace acceptance gate, plan-homogeneous batching, and
plan threading through the executors and the serving report."""
from dataclasses import replace

import numpy as np
import pytest

from repro.core import GeoSearchEngine, Planner, QueryBudgets, QueryPlan
from repro.core.distributed import HashPartitioner
from repro.core.planner import COST_KEYS, QueryFeatures
from repro.corpus import (
    make_corpus,
    make_mixture_trace,
    make_query_trace,
    make_uniform_trace,
    make_zipf_trace,
    pad_trace_batch,
)
from repro.serving import GeoServer, ShapeBucketedBatcher, SingleDeviceExecutor
from repro.serving.batcher import PendingQuery


# ---------------------------------------------------------------------------
# shared engines (module scope: index builds + jit compiles amortize)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def small_engine():
    corpus = make_corpus(600, 300, seed=5)
    budgets = QueryBudgets(
        max_candidates=512, max_tiles=256, k_sweeps=4, sweep_budget=256, top_k=5
    )
    eng = GeoSearchEngine.build(
        corpus.doc_terms, corpus.doc_rects, corpus.doc_amps, corpus.n_terms,
        pagerank=corpus.pagerank, grid=32, m_intervals=4, budgets=budgets,
    )
    return corpus, eng


@pytest.fixture(scope="module")
def mixture_engine():
    """The acceptance-gate setup: tight spatial index, serve-scale budgets."""
    n_docs = 2500
    corpus = make_corpus(n_docs, 1000, seed=9)
    budgets = QueryBudgets(
        max_candidates=2048, max_tiles=1024, k_sweeps=8,
        sweep_budget=max(n_docs // 8, 256), top_k=10,
    )
    eng = GeoSearchEngine.build(
        corpus.doc_terms, corpus.doc_rects, corpus.doc_amps, corpus.n_terms,
        pagerank=corpus.pagerank, grid=128, m_intervals=8, budgets=budgets,
    )
    return corpus, eng


def _trace_cost(res) -> float:
    """The acceptance objective: inverted-index probes + posting bytes."""
    return float(
        np.asarray(res.stats["n_probes"], np.float64).sum()
        + np.asarray(res.stats["bytes_postings"], np.float64).sum()
    )


# ---------------------------------------------------------------------------
# cost-model properties
# ---------------------------------------------------------------------------

def _feat(**kw) -> QueryFeatures:
    base = dict(n_terms=2, df_min=10.0, df_sum=50.0, tp_est=100.0,
                tp_span=100.0, area=0.01)
    base.update(kw)
    return QueryFeatures(**base)


def test_cost_model_monotone_in_postings(small_engine):
    """More postings behind a query → predicted text bytes never shrink."""
    _, eng = small_engine
    model = eng.planner.model
    plan = QueryPlan("text_first", eng.budgets)
    last = -1.0
    for df_min in [0, 1, 5, 50, 500, 5000, 50000]:
        est = model.estimate(plan, _feat(df_min=float(df_min)))
        assert est["bytes_postings"] >= last
        assert est["n_probes"] >= 0
        last = est["bytes_postings"]


def test_cost_model_monotone_in_footprint(small_engine):
    """Bigger footprint coverage → spatial plans never predicted cheaper."""
    _, eng = small_engine
    model = eng.planner.model
    for algo in ["geo_first", "k_sweep"]:
        plan = QueryPlan(algo, eng.budgets)
        last_b, last_s = -1.0, -1.0
        for tp in [0, 10, 100, 1000, 10000, 100000]:
            est = model.estimate(plan, _feat(tp_est=float(tp), tp_span=float(tp)))
            assert est["bytes_postings"] >= last_b, algo
            assert est["bytes_spatial"] >= last_s, algo
            last_b, last_s = est["bytes_postings"], est["bytes_spatial"]


def test_cost_model_truncation_risk(small_engine):
    """Queries a plan's budgets cannot cover carry a truncation charge."""
    _, eng = small_engine
    model = eng.planner.model
    bud = eng.budgets
    covered = _feat(df_min=10.0, tp_est=10.0, tp_span=10.0)
    huge = _feat(
        df_min=bud.max_candidates * 10.0,
        tp_est=bud.max_candidates * 10.0,
        tp_span=bud.k_sweeps * bud.sweep_budget * 10.0,
    )
    for algo in ["text_first", "geo_first", "k_sweep"]:
        for prune in (False, True):
            plan = QueryPlan(algo, replace(bud, prune=prune))
            assert model.truncation(plan, covered) == 0.0, (algo, prune)
            assert model.truncation(plan, huge) > 0.0, (algo, prune)


def test_exact_covers_only_provable_plans(small_engine):
    """Under ``exact`` only plans whose budgets provably cover the query
    are admissible: TEXT-FIRST while the driver list fits, the scan
    always, GEO-FIRST and K-SWEEP never (their coverage is estimated)."""
    _, eng = small_engine
    model = eng.planner.model
    bud = replace(eng.budgets, exact=True)
    planner = Planner(model=model, candidates=Planner.make_candidates(bud))
    assert [p.algorithm for p in planner.candidates][-1] == "scan"
    fits = _feat(df_min=float(bud.max_candidates), tp_est=10.0, tp_span=10.0)
    over = replace(fits, df_min=bud.max_candidates + 1.0)
    for prune in (False, True):
        for algo in ["text_first", "geo_first", "k_sweep", "scan"]:
            plan = QueryPlan(algo, replace(bud, prune=prune))
            assert model.covers(plan, fits) == (algo in ("text_first", "scan"))
            assert model.covers(plan, over) == (algo == "scan")
            if not model.covers(plan, over):
                assert planner.cost(plan, over) == float("inf")
    # a prune_eps floor drops candidates, so it never covers
    eps = QueryPlan("text_first", replace(bud, prune=True, prune_eps=0.1))
    assert not model.covers(eps, fits)


def test_exact_auto_answers_like_the_oracle():
    """With budgets far below the corpus, ``auto`` under ``exact`` answers
    every query as the oracle does (the scan takes what TEXT-FIRST's
    budget cannot cover), while plain ``auto`` loses answers."""
    corpus = make_corpus(3000, 200, seed=13)
    budgets = QueryBudgets(
        max_candidates=64, max_tiles=16, k_sweeps=2, sweep_budget=64, top_k=10,
        prune=True,
    )
    eng = GeoSearchEngine.build(
        corpus.doc_terms, corpus.doc_rects, corpus.doc_amps, corpus.n_terms,
        pagerank=corpus.pagerank, grid=32, budgets=replace(budgets, exact=True),
    )
    batch = make_query_trace(corpus, n_queries=32, seed=14)
    want = eng.oracle(batch, 10)
    got = eng.query(batch, "auto")
    np.testing.assert_array_equal(np.asarray(got.ids), np.asarray(want.ids))
    np.testing.assert_array_equal(np.asarray(got.scores), np.asarray(want.scores))
    labels = {p.label for p in eng.planner.plan_rows(batch)}
    assert labels == {"text_first+prune", "scan"}, labels
    plain = replace(eng, budgets=budgets)
    assert plain.recall_at_k(batch, "auto") < 0.9


def test_exact_auto_sharded_serving_matches_oracle():
    """Served through ``GeoServer`` over footprint-routed doc shards, the
    exact mode still returns the oracle's ids for every query."""
    from repro.core.distributed import RegionRangePartitioner
    from repro.core.ranking import topk_recall_np
    from repro.serving import DeadlineBatcher
    from repro.serving.factory import make_executor

    corpus = make_corpus(3000, 200, seed=19)
    budgets = QueryBudgets(
        max_candidates=64, max_tiles=16, k_sweeps=2, sweep_budget=64, top_k=10,
        prune=True, exact=True,
    )
    ex = make_executor(
        "sharded", corpus, algorithm="auto", budgets=budgets, n_shards=2,
        partitioner=RegionRangePartitioner(), routing="footprint", grid=16,
        fused=True, compress="f16",
    )
    trace = make_zipf_trace(corpus, n_queries=48, pool_size=24, seed=20)
    rep = GeoServer(
        ex, cache=None,
        batcher=DeadlineBatcher(max_batch=8, max_terms=8, max_rects=4),
    ).run_trace(trace, collect_results=True)
    assert set(rep.plan_queries) <= {"text_first+prune+fused", "scan"}
    assert rep.plan_stats["scan"]["scan_rounds"] > 0
    oracle = GeoSearchEngine.build(
        corpus.doc_terms, corpus.doc_rects, corpus.doc_amps, corpus.n_terms,
        pagerank=corpus.pagerank, grid=16, budgets=budgets, compress="f16",
    )
    want = np.asarray(oracle.oracle(pad_trace_batch(trace)).ids)
    got = np.stack([r.ids for r in rep.results])
    assert topk_recall_np(want, got) == 1.0


def test_cost_model_calibration_scales(small_engine):
    """Calibration fits clipped per-(algorithm, counter) scales against the
    measured counters and is idempotent-safe to re-run."""
    corpus, eng = small_engine
    planner = Planner.from_engine(eng)
    batch = make_query_trace(corpus, n_queries=16, seed=6)
    model = planner.model
    model.calibrate(eng, batch, planner.candidates)
    assert model.scales  # something was fit
    for (algo, key), s in model.scales.items():
        assert key in COST_KEYS
        assert 1.0 / 16.0 <= s <= 16.0, (algo, key, s)
    once = dict(model.scales)
    model.calibrate(eng, batch, planner.candidates)
    for k, v in once.items():
        assert model.scales[k] == pytest.approx(v), k


# ---------------------------------------------------------------------------
# plan choice (golden on seeded corpora)
# ---------------------------------------------------------------------------

def test_plan_choice_goldens(mixture_engine):
    """Rare-term × huge-footprint queries plan TEXT-FIRST; hot-term ×
    tiny-footprint queries plan a spatial-first pipeline."""
    corpus, eng = mixture_engine
    planner = eng.planner
    rare = pad_trace_batch(
        make_mixture_trace(corpus, n_queries=24, rare_frac=1.0, seed=21)
    )
    hot = pad_trace_batch(
        make_mixture_trace(corpus, n_queries=24, rare_frac=0.0, seed=22)
    )
    rare_plans = [p.algorithm for p in planner.plan_rows(rare)]
    hot_plans = [p.algorithm for p in planner.plan_rows(hot)]
    assert rare_plans.count("text_first") >= 0.75 * len(rare_plans)
    spatial = [a for a in hot_plans if a in ("geo_first", "k_sweep")]
    assert len(spatial) >= 0.75 * len(hot_plans)
    assert hot_plans.count("geo_first") > 0


def test_plan_keyed_compile_cache(small_engine):
    """Plans key the engine's compiled-fn cache: same plan never recompiles,
    distinct plans coexist against one index."""
    from dataclasses import replace

    corpus, eng = small_engine
    batch = make_query_trace(corpus, n_queries=8, seed=7)
    bud = replace(eng.budgets, top_k=3)  # distinct from every other test
    before = len(eng.__dict__.get("_fn_cache", {}))
    eng.query(batch, plan=QueryPlan("text_first", bud))
    eng.query(batch, plan=QueryPlan("text_first", bud))  # equal plan: cached
    mid = len(eng._fn_cache)
    assert mid == before + 1
    eng.query(batch, plan=QueryPlan("geo_first", bud))
    assert len(eng._fn_cache) == mid + 1


# ---------------------------------------------------------------------------
# the acceptance gate: planner vs every fixed algorithm on the mixture
# ---------------------------------------------------------------------------

def test_auto_beats_every_fixed_algorithm_on_mixture(mixture_engine):
    """ISSUE 5 acceptance: on the bimodal mixture trace, ``auto`` spends
    >= 1.3x fewer probes + posting bytes than the best fixed algorithm,
    at recall@10 >= 0.95 vs the exact oracle."""
    corpus, eng = mixture_engine
    batch = pad_trace_batch(make_mixture_trace(corpus, n_queries=96, seed=10))
    costs = {
        a: _trace_cost(eng.query(batch, a))
        for a in ["text_first", "geo_first", "k_sweep", "auto"]
    }
    best_fixed = min(costs[a] for a in ["text_first", "geo_first", "k_sweep"])
    assert best_fixed >= 1.3 * costs["auto"], costs
    assert eng.recall_at_k(batch, "auto") >= 0.95
    # and the planner actually mixes plans (it is not one fixed winner)
    labels = {p.algorithm for p in eng.planner.plan_rows(batch)}
    assert len(labels) >= 2


def test_auto_recall_not_worse_than_fixed(mixture_engine):
    """Per-query selection must not sacrifice quality: auto recall@10 is at
    least the best fixed algorithm's on zipf / uniform / mixture traces
    (small tolerance — the planner optimizes I/O under a *predicted*
    truncation-risk term, so exact ties can land a hair under the best
    fixed recall while still clearing the 0.95 absolute floor)."""
    corpus, eng = mixture_engine
    traces = {
        "zipf": make_zipf_trace(corpus, n_queries=64, pool_size=24, seed=3),
        "uniform": make_uniform_trace(corpus, n_queries=64, seed=4),
        "mixture": make_mixture_trace(corpus, n_queries=64, seed=5),
    }
    for name, tr in traces.items():
        batch = pad_trace_batch(tr)
        fixed = max(
            eng.recall_at_k(batch, a)
            for a in ["text_first", "geo_first", "k_sweep"]
        )
        auto = eng.recall_at_k(batch, "auto")
        assert auto >= fixed - 0.025, (name, auto, fixed)
        assert auto >= 0.95, (name, auto)


# ---------------------------------------------------------------------------
# plan-homogeneous batching
# ---------------------------------------------------------------------------

def test_batcher_buckets_are_plan_homogeneous(small_engine):
    """Every emitted batch holds queries of exactly one plan, carries that
    plan, and no query is dropped across plans."""
    _, eng = small_engine
    rng = np.random.default_rng(0)
    plan_a = QueryPlan("text_first", eng.budgets)
    plan_b = QueryPlan("geo_first", eng.budgets)
    b = ShapeBucketedBatcher(max_batch=4, max_terms=8, max_rects=4)
    by_qid = {}
    batches = []
    for qid in range(40):
        plan = [plan_a, plan_b, None][rng.integers(0, 3)]
        by_qid[qid] = plan
        d = int(rng.integers(1, 9))
        r = int(rng.integers(1, 5))
        lo = rng.uniform(0, 0.8, (r, 2)).astype(np.float32)
        q = PendingQuery(
            qid,
            rng.integers(0, 100, d).astype(np.int32),
            np.concatenate([lo, lo + 0.1], axis=1).astype(np.float32),
            np.ones((r,), np.float32),
            plan,
        )
        batches.extend(b.add(q))
    batches.extend(b.flush())
    seen = []
    for raw in batches:
        for qid in raw.qids:
            assert by_qid[qid] == raw.plan  # homogeneity
        seen.extend(raw.qids)
    assert sorted(seen) == list(range(40))  # exactly-once delivery


# ---------------------------------------------------------------------------
# plans through executors and the serving report
# ---------------------------------------------------------------------------

def test_deadline_batcher_tied_deadlines_across_plans(small_engine):
    """Two plan-distinct buckets expiring at the same instant must flush
    without comparing the (unorderable) QueryPlan bucket keys."""
    from repro.serving import DeadlineBatcher

    _, eng = small_engine
    plan_a = QueryPlan("text_first", eng.budgets)
    plan_b = QueryPlan("geo_first", eng.budgets)
    b = DeadlineBatcher(max_batch=8, max_terms=8, max_rects=4, max_wait_s=1e-3)
    terms = np.array([1, 2], np.int32)
    rects = np.array([[0.1, 0.1, 0.2, 0.2]], np.float32)
    amps = np.ones((1,), np.float32)
    b.add(PendingQuery(0, terms, rects, amps, plan_a), now=0.0)
    b.add(PendingQuery(1, terms, rects, amps, plan_b), now=0.0)  # same t
    ripe = b.due(1.0)  # both overdue at once: must not raise
    assert sorted(q for raw in ripe for q in raw.qids) == [0, 1]
    assert {raw.plan for raw in ripe} == {plan_a, plan_b}


def test_sharded_executor_runs_plans(small_engine):
    """A plan handed to the sharded executor reaches every shard engine and
    merges to the same global top-k as the single-device run."""
    from repro.serving import ShardedExecutor

    corpus = make_corpus(n_docs=256, n_terms=80, seed=3)
    budgets = QueryBudgets(
        max_candidates=1024, max_tiles=256, k_sweeps=4,
        sweep_budget=1024, top_k=5,
    )
    eng = GeoSearchEngine.build(
        corpus.doc_terms, corpus.doc_rects, corpus.doc_amps, corpus.n_terms,
        pagerank=corpus.pagerank, grid=16, budgets=budgets,
    )
    sharded = ShardedExecutor.build(
        corpus.doc_terms, corpus.doc_rects, corpus.doc_amps, corpus.n_terms,
        pagerank=corpus.pagerank, n_shards=2, partitioner=HashPartitioner(),
        grid=16, budgets=budgets, algorithm="auto",
    )
    assert sharded.planner is not None
    batch = make_query_trace(corpus, n_queries=8, seed=4)
    terms = np.asarray(batch.terms)
    rects = np.asarray(batch.rects)
    amps = np.asarray(batch.amps)
    plan = sharded.plan_query(terms[0], rects[0], amps[0])
    assert isinstance(plan, QueryPlan)
    want = eng.query(batch, plan=plan)
    got = sharded.run(batch, plan=plan)
    w_ids, w_sc = np.asarray(want.ids), np.asarray(want.scores)
    g_ids, g_sc = np.asarray(got.ids), np.asarray(got.scores)
    for row in range(w_ids.shape[0]):
        wo = np.lexsort((w_ids[row], -w_sc[row]))
        go = np.lexsort((g_ids[row], -g_sc[row]))
        assert np.array_equal(w_ids[row][wo], g_ids[row][go])


def test_serve_report_per_plan_breakdown(small_engine):
    """ISSUE 5 acceptance: the serving report attributes query counts,
    latency percentiles and byte counters per plan under --algo auto."""
    corpus, eng = small_engine
    executor = SingleDeviceExecutor(eng, "auto")
    trace = make_mixture_trace(corpus, n_queries=48, seed=11)
    server = GeoServer(
        executor, cache=None,
        batcher=ShapeBucketedBatcher(max_batch=8, max_terms=8, max_rects=4),
    )
    rep = server.run_trace(trace)
    assert rep.n_queries == 48
    assert sum(rep.plan_queries.values()) == 48  # every miss attributed
    assert len(rep.plan_queries) >= 2  # the planner genuinely mixed
    for label, n in rep.plan_queries.items():
        assert n > 0
        assert rep.plan_percentile_ms(label, 50) >= 0.0
        assert rep.plan_percentile_ms(label, 99) >= rep.plan_percentile_ms(
            label, 50
        )
        assert len(rep.plan_latencies_s[label]) == n
        assert any(
            k.startswith("bytes_") and v > 0
            for k, v in rep.plan_stats[label].items()
        )
    assert "plans:" in rep.summary()


def test_fixed_algorithm_serving_attributes_single_plan(small_engine):
    """Fixed-algorithm serving reports exactly one plan label (the
    executor's algorithm) — the planner stage is bypassed."""
    corpus, eng = small_engine
    server = GeoServer(
        SingleDeviceExecutor(eng, "k_sweep"), cache=None,
        batcher=ShapeBucketedBatcher(max_batch=8, max_terms=8, max_rects=4),
    )
    rep = server.run_trace(make_zipf_trace(corpus, n_queries=32, pool_size=8, seed=12))
    assert set(rep.plan_queries) == {"k_sweep"}
    assert rep.plan_queries["k_sweep"] == 32


# ---------------------------------------------------------------------------
# tp_span bbox grid (ISSUE 6 satellite): exact vs the old all-blocks scan
# ---------------------------------------------------------------------------

def _tp_span_bruteforce(model, rects, amps) -> float:
    """The pre-grid O(NB) scan: test every metadata block's MBR against
    every valid footprint rect, sum toe-print counts of the hits."""
    r = np.asarray(rects, np.float64).reshape(-1, 4)
    a = np.asarray(amps, np.float64).reshape(-1)
    r = r[(r[:, 2] > r[:, 0]) & (r[:, 3] > r[:, 1]) & (a > 0)]
    if not len(r) or not len(model.blk_mbr):
        return 0.0
    m = model.blk_mbr.astype(np.float64)
    hit = (
        (np.minimum(m[None, :, 2], r[:, None, 2])
         >= np.maximum(m[None, :, 0], r[:, None, 0]))
        & (np.minimum(m[None, :, 3], r[:, None, 3])
           >= np.maximum(m[None, :, 1], r[:, None, 1]))
    ).any(axis=0)
    return float(np.minimum((hit * model.blk_count).sum(), model.n_toeprints))


def test_tp_span_grid_matches_bruteforce_scan(mixture_engine):
    """The coarse bbox grid must reproduce the all-blocks MBR scan's
    tp_span bit for bit — it only narrows *candidates*, never the sum —
    while testing far fewer blocks than N_blocks x n_queries."""
    corpus, eng = mixture_engine
    model = eng.planner.model
    assert len(model.blk_mbr) > 0  # the fixture actually exercises blocks
    trace = make_mixture_trace(corpus, n_queries=64, seed=21)
    model.tp_span_probes = 0
    tested = 0
    for q in trace:
        f = model.features(q.terms, q.rects, q.amps)
        ts = _tp_span_bruteforce(model, q.rects, q.amps)
        assert f.tp_span == max(ts, f.tp_est), (f.tp_span, ts, f.tp_est)
        tested += 1
    assert tested == 64
    # the probe counter advanced, and the grid did real narrowing:
    # far fewer candidate blocks than the full scan would have touched
    assert 0 < model.tp_span_probes < 64 * len(model.blk_mbr)


def test_tp_span_probe_metric_published(small_engine):
    from repro.obs import MetricsRegistry

    corpus, eng = small_engine
    model = eng.planner.model
    reg = MetricsRegistry()
    model.metrics = reg
    try:
        q = make_zipf_trace(corpus, n_queries=1, pool_size=1, seed=2)[0]
        before = model.tp_span_probes
        model.features(q.terms, q.rects, q.amps)
        gained = model.tp_span_probes - before
        assert reg.counter("planner.tp_span_probe").value == gained
    finally:
        model.metrics = None


def test_explain_matches_plan_query(mixture_engine):
    """explain() is a faithful audit of plan_query: same features, same
    costs, same chosen label, for every mixture query."""
    corpus, eng = mixture_engine
    planner = eng.planner
    for q in make_mixture_trace(corpus, n_queries=32, seed=22):
        exp = planner.explain(q.terms, q.rects, q.amps)
        plan = planner.plan_query(q.terms, q.rects, q.amps)
        assert exp["chosen"] == plan.label
        assert set(exp["candidates"]) == {p.label for p in planner.candidates}
        chosen = exp["candidates"][exp["chosen"]]
        assert chosen["cost"] == min(c["cost"] for c in exp["candidates"].values())
        for c in exp["candidates"].values():
            assert set(COST_KEYS) <= set(c)
