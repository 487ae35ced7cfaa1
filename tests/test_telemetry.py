"""Telemetry property tests: spans, metrics, audit, validation (ISSUE 6).

The central contract: telemetry is an *observer*.  With a
:class:`~repro.obs.Telemetry` handle attached, the serving report is
bit-identical to a telemetry-off run, and every exported artifact is
internally consistent with that report:

(a) the tracer's per-query stage spans — in record order — equal the
    report's ``latencies_s`` / ``batch_wait_s`` / ``queue_wait_s`` /
    ``service_s`` lists **exactly** (``==``, not allclose), across the
    full workers x coalesce x deadline x arrival grid of
    ``tests/test_multiworker_serving.py``;
(b) the exported Chrome/Perfetto trace is well-formed: spans well-nested
    (LIFO b/e pairing per id), per-track X events non-overlapping and
    monotone, stage boundaries contiguous and ordered;
(c) the metrics histograms reconstruct p50/p99 to within one log bucket
    of the report's exact ``percentile_ms``;
(d) the planner audit joins measured counters for every executed plan
    and its relative-error summary is finite.
"""
import dataclasses
import math
import re

import pytest

from repro.obs import (
    EventLog,
    Histogram,
    MetricsRegistry,
    PlannerAudit,
    SpanRecorder,
    Telemetry,
    validate_trace,
)
from repro.serving import DeadlineBatcher, GeoServer, LRUCache

from test_multiworker_serving import (
    RowExecutor,
    _check_decomposition,
    _pool_query,
    _random_trace,
    _service,
)


def _tel_server(workers=1, coalesce=False, max_wait_s=2e-3, cache=None,
                max_batch=8):
    tel = Telemetry()
    srv = GeoServer(
        RowExecutor(),
        cache=cache,
        batcher=DeadlineBatcher(
            max_batch=max_batch, max_terms=8, max_rects=4, max_wait_s=max_wait_s
        ),
        n_workers=workers,
        coalesce=coalesce,
        telemetry=tel,
    )
    return srv, tel


# ---------------------------------------------------------------------------
# (a) span sums == report decomposition, exactly, across the full grid
# ---------------------------------------------------------------------------

def _check_spans(rep, tel, n: int) -> None:
    tot, bw, qw, svc = tel.tracer.stage_sums()
    # exact equality: the tracer records the *same floats* the report does
    assert tot == rep.latencies_s
    assert bw == rep.batch_wait_s
    assert qw == rep.queue_wait_s
    assert svc == rep.service_s
    # stage boundaries are contiguous and ordered for every query
    for q in tel.tracer.queries:
        t_arr, t_flush, t_start, t_done = q.boundaries()
        assert t_arr <= t_flush <= t_start <= t_done
        if q.kind == "hit":
            assert q.batch_wait == q.queue_wait == 0.0
    # the exported trace is well-formed (nesting, monotone tracks, pairing)
    assert validate_trace(tel.tracer.to_trace_events()) == []
    # metrics agree with the report's counts
    reg = tel.metrics
    assert reg.counter("server.queries_total").value == n
    assert reg.histogram("server.latency_ms").n == n
    assert reg.counter("server.cache_hits_total").value == rep.cache_hits
    assert reg.counter("server.cache_misses_total").value == rep.cache_misses
    assert reg.counter("server.coalesced_total").value == rep.coalesced
    flushes = sum(
        reg.counter("batcher.flush_total", {"reason": r}).value
        for r in ("fill", "deadline", "drain")
    )
    assert flushes == rep.n_batches
    # batch spans: one per executed batch, sequential per worker
    assert len(tel.tracer.batches) == rep.n_batches
    by_worker: dict[int, float] = {}
    for b in tel.tracer.batches:
        assert b.flush_t <= b.start_t <= b.done_t
        assert b.start_t >= by_worker.get(b.worker, 0.0)
        by_worker[b.worker] = b.done_t


def test_spans_match_report_across_grid():
    for seed in range(4):
        kind = ("poisson", "bursty")[seed % 2]
        with_cache = seed % 3 == 0
        for workers in (1, 2, 4):
            for coalesce in (False, True):
                for wait in (0.0, 2e-3, float("inf")):
                    trace = _random_trace(seed, kind=kind)
                    cache = LRUCache(64) if with_cache else None
                    srv, tel = _tel_server(workers, coalesce, wait, cache)
                    rep = srv.run_trace(
                        trace, warmup=False, arrival=kind,
                        service_time=_service,
                    )
                    _check_decomposition(rep, len(trace))
                    _check_spans(rep, tel, len(trace))


def test_telemetry_is_pure_observer():
    """Attaching telemetry changes no serving outcome, bit for bit."""
    trace = _random_trace(7, n=250, pool=16, rate=1500.0)
    plain = GeoServer(
        RowExecutor(), cache=LRUCache(64),
        batcher=DeadlineBatcher(max_batch=8, max_terms=8, max_rects=4,
                                max_wait_s=2e-3),
        n_workers=2, coalesce=True,
    )
    rep0 = plain.run_trace(
        trace, warmup=False, arrival="poisson", service_time=_service
    )
    srv, _ = _tel_server(workers=2, coalesce=True, cache=LRUCache(64))
    rep1 = srv.run_trace(
        trace, warmup=False, arrival="poisson", service_time=_service
    )
    assert rep0.latencies_s == rep1.latencies_s
    assert rep0.batch_wait_s == rep1.batch_wait_s
    assert rep0.queue_wait_s == rep1.queue_wait_s
    assert rep0.service_s == rep1.service_s
    assert rep0.n_batches == rep1.n_batches
    assert rep0.cache_hits == rep1.cache_hits
    assert rep0.coalesced == rep1.coalesced


def test_closed_loop_spans_and_events():
    qs = [_pool_query(i, d=3, r=1) for i in range(6)]
    trace = qs + [dataclasses.replace(qs[0])]
    srv, tel = _tel_server(coalesce=True, max_wait_s=float("inf"),
                           max_batch=4, cache=LRUCache(16))
    rep = srv.run_trace(trace, warmup=False)
    _check_spans(rep, tel, len(trace))
    evs = {e["ev"] for e in tel.events.events}
    assert {"flush", "dispatch", "complete"} <= evs
    assert len(tel.events) > 0


# ---------------------------------------------------------------------------
# (c) histogram percentiles within one bucket of the exact report values
# ---------------------------------------------------------------------------

def test_histogram_percentiles_within_one_bucket_of_report():
    trace = _random_trace(11, n=400, rate=900.0)
    srv, tel = _tel_server(workers=2, max_wait_s=1e-3)
    rep = srv.run_trace(
        trace, warmup=False, arrival="poisson", service_time=_service
    )
    pairs = [
        ("server.latency_ms", rep.percentile_ms),
        ("server.batch_wait_ms", lambda p: rep.stage_percentile_ms("batch_wait", p)),
        ("server.queue_wait_ms", lambda p: rep.stage_percentile_ms("queue_wait", p)),
        ("server.service_ms", lambda p: rep.stage_percentile_ms("service", p)),
    ]
    for name, exact_ms in pairs:
        h = tel.metrics.histogram(name)
        assert h.n == len(trace)
        for p in (50, 90, 99):
            assert h.same_or_adjacent_bucket(h.quantile(p), exact_ms(p)), (
                name, p, h.quantile(p), exact_ms(p),
            )


def test_histogram_quantile_basics():
    h = Histogram()
    for v in [1.0, 2.0, 4.0, 8.0, 100.0]:
        h.observe(v)
    assert h.n == 5 and h.sum == 115.0
    assert h.same_or_adjacent_bucket(h.quantile(50), 4.0)
    assert h.same_or_adjacent_bucket(h.quantile(100), 100.0)
    assert math.isnan(Histogram().quantile(50))
    # bucket edges partition [lo, inf): index of an edge == right bucket
    for i in range(1, 40):
        lo, hi = h.bucket_bounds(i)
        assert h._index(lo * 1.0000001) == i
        assert lo < hi


def test_metrics_exports():
    reg = MetricsRegistry()
    reg.inc("server.queries_total", 3)
    reg.inc("batcher.flush_total", reason="fill")
    reg.set("batcher.pad_slots", 7)
    for v in (1.0, 2.0, 3.0):
        reg.observe("server.latency_ms", v)
    prom = reg.to_prometheus()
    assert "# TYPE server_queries_total counter" in prom
    assert "server_queries_total 3" in prom
    assert 'batcher_flush_total{reason="fill"} 1' in prom
    assert "batcher_pad_slots 7" in prom
    assert "server_latency_ms_count 3" in prom
    assert 'le="+Inf"' in prom
    js = reg.to_json()
    assert js["counters"]["server.queries_total"] == 3
    h = js["histograms"]["server.latency_ms"]
    assert h["count"] == 3 and h["sum"] == 6.0
    assert sum(b[2] for b in h["buckets"]) == 3


# ---------------------------------------------------------------------------
# (d) planner audit on a real auto engine
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def auto_engine():
    from repro.core import GeoSearchEngine, QueryBudgets
    from repro.corpus import make_corpus

    corpus = make_corpus(600, 300, seed=5)
    budgets = QueryBudgets(
        max_candidates=512, max_tiles=256, k_sweeps=4,
        sweep_budget=256, top_k=5,
    )
    eng = GeoSearchEngine.build(
        corpus.doc_terms, corpus.doc_rects, corpus.doc_amps, corpus.n_terms,
        pagerank=corpus.pagerank, grid=32, m_intervals=4, budgets=budgets,
    )
    return corpus, eng


def test_audit_joins_every_executed_plan(auto_engine):
    from repro.corpus import make_zipf_trace, stamp_arrivals
    from repro.serving import SingleDeviceExecutor

    corpus, eng = auto_engine
    trace = stamp_arrivals(
        make_zipf_trace(corpus, n_queries=24, pool_size=12, seed=3),
        "poisson", rate_qps=800.0, seed=4,
    )
    tel = Telemetry()
    srv = GeoServer(
        SingleDeviceExecutor(eng, "auto"),
        cache=None,
        batcher=DeadlineBatcher(max_batch=8, max_terms=16, max_rects=4,
                                max_wait_s=2e-3),
        telemetry=tel,
    )
    srv.run_trace(trace, warmup=False, arrival="poisson")
    audit = tel.audit
    assert len(audit.records) > 0
    assert len(audit.joined) == len(audit.records)  # every plan joined
    for rec in audit.records:
        assert rec.chosen in rec.candidates
        assert rec.measured is not None
        errs = rec.errors()
        assert set(errs) == {"n_probes", "bytes_postings", "bytes_spatial"}
        assert all(e >= 0 and math.isfinite(e) for e in errs.values())
    summary = audit.error_summary()
    assert summary and all(math.isfinite(v) for v in summary.values())
    # the engine-side metrics got populated through the same handle
    assert tel.metrics.counter("planner.tp_span_probe").value > 0
    assert tel.metrics.counter("engine.compiled_fns_total").value > 0


# ---------------------------------------------------------------------------
# (b) trace validation: malformed traces are rejected
# ---------------------------------------------------------------------------

def test_validate_trace_rejects_malformed():
    ok = {"traceEvents": [
        {"name": "q", "ph": "b", "pid": 1, "tid": 1, "ts": 0, "cat": "c",
         "id": 1},
        {"name": "q", "ph": "e", "pid": 1, "tid": 1, "ts": 5, "cat": "c",
         "id": 1},
        {"name": "x", "ph": "X", "pid": 1, "tid": 2, "ts": 0, "dur": 3},
    ]}
    assert validate_trace(ok) == []
    assert validate_trace({"nope": []})  # missing traceEvents
    # unclosed async span
    assert validate_trace({"traceEvents": [
        {"name": "q", "ph": "b", "pid": 1, "tid": 1, "ts": 0, "cat": "c",
         "id": 1},
    ]})
    # mismatched b/e name
    assert validate_trace({"traceEvents": [
        {"name": "a", "ph": "b", "pid": 1, "tid": 1, "ts": 0, "cat": "c",
         "id": 1},
        {"name": "b", "ph": "e", "pid": 1, "tid": 1, "ts": 1, "cat": "c",
         "id": 1},
    ]})
    # negative dur
    assert validate_trace({"traceEvents": [
        {"name": "x", "ph": "X", "pid": 1, "tid": 1, "ts": 0, "dur": -1},
    ]})
    # non-monotone X events on one track
    assert validate_trace({"traceEvents": [
        {"name": "x", "ph": "X", "pid": 1, "tid": 1, "ts": 10, "dur": 1},
        {"name": "y", "ph": "X", "pid": 1, "tid": 1, "ts": 0, "dur": 1},
    ]})


def test_span_recorder_trace_round_trip(tmp_path):
    rec = SpanRecorder()
    rec.annotate(5, plan_algo="k_sweep")
    rec.query(5, 0, "executed", "ksweep", 0.0, 1e-3, 4e-4, 1e-4, 5e-4)
    rec.query(-1, 1, "hit", None, 2e-3, 1e-6, 0.0, 0.0, 1e-6)
    rec.batch(0, 4e-4, 5e-4, 1e-3, "ksweep", 1, (8, 8, 4))
    rec.span("shard 0", "query[ksweep]", 0.001, 0.002, {"rows": 8})
    trace = rec.to_trace_events()
    assert validate_trace(trace) == []
    names = {e["name"] for e in trace["traceEvents"]}
    assert {"query", "batch_wait", "queue_wait", "service", "lookup",
            "batch[ksweep]", "query[ksweep]"} <= names
    # staged args landed on the query span
    q = next(e for e in trace["traceEvents"]
             if e["name"] == "query" and e["ph"] == "b")
    assert q["args"]["plan_algo"] == "k_sweep"
    p = tmp_path / "trace.json"
    rec.write(str(p))
    import json

    assert validate_trace(json.loads(p.read_text())) == []


# ---------------------------------------------------------------------------
# satellite 2: empty-stage percentiles are NaN, not 0.0
# ---------------------------------------------------------------------------

def test_empty_percentiles_are_nan_and_summary_omits_them():
    srv = GeoServer(RowExecutor(), cache=LRUCache(4),
                    batcher=DeadlineBatcher(max_batch=4, max_terms=8,
                                            max_rects=4, max_wait_s=0.0))
    q = _pool_query(0, d=3, r=1)
    rep = srv.run_trace([q, dataclasses.replace(q)], warmup=False)
    assert rep.cache_hits == 1
    fresh = type(rep)()
    assert math.isnan(fresh.stage_percentile_ms("batch_wait", 99))
    assert math.isnan(fresh.plan_percentile_ms("ksweep", 99))
    assert math.isnan(rep.plan_percentile_ms("no_such_plan", 50))
    # summary never renders a NaN
    assert "nan" not in fresh.summary().lower()
    assert "nan" not in rep.summary().lower()


# ---------------------------------------------------------------------------
# event log + audit unit behavior
# ---------------------------------------------------------------------------

def test_event_log_and_audit_units(tmp_path):
    log = EventLog()
    log.emit(0.1, "flush", reason="fill", n_real=4)
    log.emit(0.2, "evict", n=2)
    assert len(log) == 2
    p = tmp_path / "events.jsonl"
    log.to_jsonl(str(p))
    lines = p.read_text().splitlines()
    assert len(lines) == 2 and '"ev": "flush"' in lines[0]

    audit = PlannerAudit()
    audit.record(
        qid=1, idx=0, features={"df_min": 3.0},
        candidates={"ksweep": {"algorithm": "k_sweep", "n_probes": 10.0,
                               "bytes_postings": 100.0,
                               "bytes_spatial": 50.0, "cost": 1.0}},
        chosen="ksweep", t_plan=0.0,
    )
    assert audit.joined == []
    audit.join(1, {"n_probes": 20.0, "bytes_postings": 100.0,
                   "bytes_spatial": 0.0})
    assert len(audit.joined) == 1
    errs = audit.records[0].errors()
    assert errs["n_probes"] == pytest.approx(0.5)
    assert errs["bytes_postings"] == 0.0
    assert errs["bytes_spatial"] == pytest.approx(50.0)  # denom floor 1
    summary = audit.error_summary()
    assert summary[("k_sweep", "n_probes")] == pytest.approx(0.5)
    out = tmp_path / "audit.jsonl"
    audit.to_jsonl(str(out))
    assert len(out.read_text().splitlines()) == 1


# ---------------------------------------------------------------------------
# host stages (SpanRecorder.stage) and the program's named scopes
# ---------------------------------------------------------------------------

BATCH_STAGES = ["geo.dispatch", "geo.result", "geo.stats", "geo.deliver"]


def _stage_spans(rec):
    return [s for s in rec.exec_spans if s.track == "server"]


def test_closed_loop_stages_nest_in_order():
    """Every executed batch records ``geo.batch`` with its four children,
    in order and inside it; every planned miss one ``geo.plan``; the report
    sums them per run and the export stays valid."""
    qs = [_pool_query(i, d=3, r=1) for i in range(6)]
    trace = qs + [dataclasses.replace(qs[0])]
    srv, tel = _tel_server(max_wait_s=float("inf"), max_batch=4,
                           cache=LRUCache(16))
    rep = srv.run_trace(trace, warmup=False)
    spans = _stage_spans(tel.tracer)
    batches = [i for i, s in enumerate(spans) if s.name == "geo.batch"]
    assert len(batches) == rep.n_batches == 2
    assert sum(s.name == "geo.plan" for s in spans) == rep.cache_misses == 6
    for i in batches:
        parent, kids = spans[i], spans[i + 1 : i + 5]
        assert [k.name for k in kids] == BATCH_STAGES
        assert parent.args == {"plan": "fixed"}  # RowExecutor: no plans
        for a, b in zip(kids, kids[1:]):
            assert a.t1 <= b.t0  # in order, not overlapping
        assert parent.t0 <= kids[0].t0 and kids[-1].t1 <= parent.t1
    for s in spans:
        if s.name == "geo.plan":  # a plan stage never sits inside a batch
            assert not any(
                p.t0 <= s.t0 < p.t1 for p in spans if p.name == "geo.batch"
            )
    assert set(rep.stage_s) == {"geo.plan", "geo.batch", *BATCH_STAGES}
    for name, total in rep.stage_s.items():
        assert total == pytest.approx(
            sum(s.t1 - s.t0 for s in spans if s.name == name)
        )
    trace_json = tel.tracer.to_trace_events()
    assert validate_trace(trace_json) == []
    assert {"geo.batch", "geo.plan", *BATCH_STAGES} <= {
        e["name"] for e in trace_json["traceEvents"]
    }


def test_open_loop_stages_nest_and_export_validly():
    trace = _random_trace(3, n=120, pool=12)
    srv, tel = _tel_server(workers=2, coalesce=True, cache=LRUCache(8))
    rep = srv.run_trace(trace, warmup=False, arrival="poisson",
                        service_time=_service)
    spans = _stage_spans(tel.tracer)
    assert sum(s.name == "geo.batch" for s in spans) == rep.n_batches
    assert validate_trace(tel.tracer.to_trace_events()) == []


class _CountingAnnotation:
    entered = 0

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        type(self).entered += 1
        return self

    def __exit__(self, *exc):
        return False


@pytest.mark.parametrize("with_tracer", [False, True])
def test_profiler_annotations_only_with_a_tracer(monkeypatch, with_tracer):
    import repro.obs.tracing as tracing

    monkeypatch.setattr(_CountingAnnotation, "entered", 0)
    monkeypatch.setattr(tracing, "TraceAnnotation", _CountingAnnotation)
    qs = [_pool_query(i, d=2, r=1) for i in range(8)]
    tel = Telemetry(metrics=MetricsRegistry(), tracer=None, audit=None,
                    events=None)
    if with_tracer:
        tel = Telemetry()
    srv = GeoServer(RowExecutor(), batcher=DeadlineBatcher(
        max_batch=4, max_terms=8, max_rects=4, max_wait_s=float("inf")),
        telemetry=tel)
    rep = srv.run_trace(qs, warmup=False)
    assert rep.n_batches == 2
    if with_tracer:  # one plan stage per miss, five stages per batch
        assert _CountingAnnotation.entered == 8 + 5 * 2
    else:
        assert _CountingAnnotation.entered == 0 and rep.stage_s == {}
    # and with no telemetry at all
    plain = GeoServer(RowExecutor(), batcher=DeadlineBatcher(
        max_batch=4, max_terms=8, max_rects=4, max_wait_s=float("inf")))
    before = _CountingAnnotation.entered
    assert plain.run_trace(qs, warmup=False).stage_s == {}
    assert _CountingAnnotation.entered == before


def test_stages_are_marked_on_the_profiler_clock(tmp_path):
    """The stages reach a profiler trace as host events, nested as on the
    recorder's track."""
    import glob
    import os

    import jax
    from jax.profiler import ProfileData

    qs = [_pool_query(i, d=2, r=1) for i in range(4)]
    srv, tel = _tel_server(max_wait_s=float("inf"), max_batch=4)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        srv.run_trace(qs, warmup=False)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"), recursive=True)
    evs = sorted(
        (int(ev.start_ns), -int(ev.duration_ns), ev.name)
        for plane in ProfileData.from_file(path).planes
        if plane.name.startswith("/host:")
        for line in plane.lines
        for ev in line.events
        if ev.name.startswith("geo.")
    )
    names = [n for _, _, n in evs]
    assert names.count("geo.plan") == 4
    assert names[names.index("geo.batch"):][:5] == ["geo.batch", *BATCH_STAGES]
    assert names == [s.name for s in _stage_spans(tel.tracer)]


def _tiny_exact_engine():
    from repro.core.algorithms import QueryBudgets
    from repro.core.engine import GeoSearchEngine
    from repro.corpus import make_corpus

    c = make_corpus(n_docs=2048, n_terms=256, max_rects=4, doc_len=16, seed=3)
    budgets = QueryBudgets(max_candidates=128, max_tiles=64, k_sweeps=4,
                           sweep_budget=256, exact=True, prune=True)
    return GeoSearchEngine.build(
        c.doc_terms, c.doc_rects, c.doc_amps, 256, pagerank=c.pagerank,
        grid=32, budgets=budgets, compress=True,
    )


def _program_text(engine, plan) -> str:
    import jax.numpy as jnp

    from repro.core.algorithms import QueryBatch

    batch = QueryBatch(terms=jnp.full((4, 3), -1, jnp.int32),
                       rects=jnp.zeros((4, 2, 4), jnp.float32),
                       amps=jnp.zeros((4, 2), jnp.float32))
    engine.__dict__.pop("_fn_cache", None)
    fn = engine._compiled(plan, ())
    return fn.lower(engine.index, batch).compile().as_text()


SCOPES = {
    "scan": {"scan.geo", "scan.mask", "scan.order", "scan.probe"},
    "text_first": {"text_first.bounds", "text_first.walk", "text_first.select",
                   "text_first.probe", "text_first.rank"},
}
# the outermost geo scope of an op's name path, through transform wrappers:
# ``jit(geo_scan)/vmap(scan.probe)/while/body/add`` -> ``scan.probe``
TOP_SCOPE = re.compile(r"(?:^|/)(?:\w+\()*((?:scan|text_first)\.[a-z]+)\b")


def _top_scopes(text: str) -> list[str]:
    out = []
    for op_name in re.findall(r'op_name="([^"]*)"', text):
        m = TOP_SCOPE.search(op_name)
        out.append(m.group(1) if m else "")
    return out


def _instructions(text: str) -> list[str]:
    """The program's instructions, without metadata or the debug tables."""
    return [
        re.sub(r", metadata=\{[^}]*\}", "", line)
        for line in text.splitlines()
        if line.startswith(("  ", "%", "ENTRY", "}"))
    ]


@pytest.mark.parametrize("algorithm", ["scan", "text_first"])
def test_compiled_programs_name_their_phases(algorithm):
    """The plan's program runs as ``jit_geo_<plan>``, its ops carry every
    phase's scope, and the scopes change no instruction."""
    from repro.core.engine import program_name
    from repro.core.planner import QueryPlan

    eng = _tiny_exact_engine()
    plan = QueryPlan(algorithm, eng.budgets)
    text = _program_text(eng, plan)
    assert re.search(rf"^HloModule jit_{program_name(plan)}\b", text, re.M)
    assert program_name(plan) == "geo_" + plan.label.replace("+", "_")
    scopes = _top_scopes(text)
    assert set(scopes) - {""} == SCOPES[algorithm]
    # scopes are metadata: the same instructions without them
    import contextlib

    import jax

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
        bare = _program_text(eng, plan)
    assert set(_top_scopes(bare)) == {""}
    assert _instructions(bare) == _instructions(text)
