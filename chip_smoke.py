#!/usr/bin/env python3
"""Smoke test of the geo serving path on a TPU, at one chip's real size.

    python chip_smoke.py          # one chip: the geoweb shard, served
    python chip_smoke.py --mesh   # four chips: the doc-sharded mesh only

The default run builds one chip's share of the paper's deployment
(``configs/geoweb.py``: 2^26 documents over the fewest doc shards the
int32 guard allows, S = 8, so 2^23 per chip, cut as the CUTS below say)
from a seed, places the index on the chip, and serves a zipf trace through
the normal entry points — ``serving.factory.make_executor`` with the
cost-based planner (``--algorithm auto``) under geoweb's exact budgets
(TEXT-FIRST where its budget covers the query, the exhaustive scan
otherwise), block-max pruning and the fused Pallas kernels on, then
``GeoServer.run_trace``.  It checks the answers:
the fused kernels' top-k ids equal the plain-jnp path's on the same index;
every served answer holds the query's terms and overlaps its footprint;
and the served recall@10 against ``algorithms.oracle`` holds the floor the
serving tests use, both on a corpus small enough for the budgets to cover
every query and on the shard itself.

``--mesh`` runs only the four-chip phase: four doc shards, one per chip,
behind ``MeshExecutor`` with footprint routing, against ``ShardedExecutor``
over the same shards.

Every phase prints one line; the last line is a JSON object
``{"ok": true, "device": {...}}``.  Any failure exits non-zero without it,
and so does a run that finds no TPU: nothing falls back to the CPU.  All
phases run in this one process (a chip belongs to one process).  JAX's
compile cache goes where ``JAX_COMPILATION_CACHE_DIR`` says, else to
``.jax_cache/`` in this checkout.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

SEED = 0
N_CHIP = (1 << 26) // 8  # geoweb's docs per chip at S = 8 shards
# scale cuts (no width is cut), each with its reason
CUTS = [
    (
        "n_docs",
        N_CHIP,
        N_CHIP // 2,
        "host memory: building this shard at 2^22 docs peaked at 33.63 GiB "
        "of the one-chip host's 40 GiB (the build grows linearly in docs)",
    ),
]
N_DOCS = CUTS[0][2]
N_QUERIES = 384  # zipf trace served
N_RECALL = 64  # distinct queries checked against the exact oracle
RECALL_FLOOR = 0.9  # tests/test_text_prune.py composition smoke (auto+prune+fused)
TILE_CHECK_DOCS = 1 << 12  # the tile kernel's check against the host build
COVER_DOCS = 1 << 12  # recall gate: docs ≤ max_candidates, so budgets cover
MESH_DOCS = 1 << 20  # --mesh: 2^18 per chip, so the host-loop reference fits one chip


def say(**kw) -> None:
    print(" ".join(f"{k}={v}" for k, v in kw.items()), flush=True)


class Clock:
    def __init__(self):
        self.t = time.perf_counter()

    def lap(self) -> float:
        now = time.perf_counter()
        dt, self.t = now - self.t, now
        return round(dt, 2)


def peak_rss_gib() -> float:
    import resource

    return round(resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2**20, 2)


def tpu_devices():
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX found {devs[0].platform} devices")
    return devs


def geoweb_budgets():
    from dataclasses import replace

    from repro.configs.geoweb import CONFIG

    # pruning on (it subsumes the config's early_termination); the
    # config's exact mode stays on
    return replace(CONFIG.budgets, prune=True, early_termination=False)


def make_geoweb_corpus(n_docs: int):
    from repro.configs.geoweb import CONFIG
    from repro.corpus import make_corpus
    from repro.corpus.synth import doc_len_for_postings

    doc_len = doc_len_for_postings(CONFIG.avg_postings_per_doc, CONFIG.n_terms)
    corpus = make_corpus(
        n_docs=n_docs, n_terms=CONFIG.n_terms, max_rects=CONFIG.doc_major_rects,
        doc_len=doc_len, seed=SEED,
    )
    return corpus, doc_len


def count_kernels(lowered_fn, *args) -> int:
    return lowered_fn.lower(*args).compile().as_text().count("tpu_custom_call")


def check_tile_kernel(clock) -> None:
    """The spatial build's tile-interval kernel equals its host reference
    on a corpus small enough for the host (the shard's own table is built
    only by the kernel)."""
    import numpy as np

    from repro.configs.geoweb import CONFIG
    from repro.core import geometry
    from repro.core.spatial_index import tile_intervals_np
    from repro.corpus import make_corpus
    from repro.kernels.tile_intervals.ops import tile_intervals

    small = make_corpus(n_docs=TILE_CHECK_DOCS, n_terms=1024,
                        max_rects=CONFIG.doc_major_rects, seed=SEED)
    rects = small.doc_rects.reshape(-1, 4)
    rects = rects[rects[:, 2] > rects[:, 0]]
    cells = geometry.rect_cell_bounds_np(rects, CONFIG.grid)
    got = tile_intervals(*cells, CONFIG.grid, CONFIG.m_intervals)
    want = tile_intervals_np(rects, CONFIG.grid, CONFIG.m_intervals)
    same = all(np.array_equal(a, b) for a, b in zip(got, want))
    say(phase="tile_kernel_vs_host", toe_prints=len(rects), equal=same,
        seconds=clock.lap())
    if not same:
        raise SystemExit("the tile-interval kernel disagrees with the host")


B = 8  # queries per served batch: one (batch, terms, rects) bucket


def serve(ex, trace):
    """Serve ``trace`` through ``GeoServer`` (closed loop, no cache)."""
    from repro.configs.geoweb import CONFIG
    from repro.serving import DeadlineBatcher, GeoServer

    batcher = DeadlineBatcher(
        max_batch=B, max_terms=CONFIG.d_terms, max_rects=CONFIG.q_rects,
        term_buckets=[CONFIG.d_terms], rect_buckets=[CONFIG.q_rects],
        batch_sizes=[B],
    )
    rep = GeoServer(ex, cache=None, batcher=batcher).run_trace(
        trace, arrival="closed", collect_results=True
    )
    if rep.n_queries != len(trace) or any(r is None for r in rep.results):
        raise SystemExit("the server dropped queries")
    return rep


def distinct(trace):
    seen, out = set(), []
    for q in trace:
        if id(q) not in seen:
            seen.add(id(q))
            out.append(q)
    return out


def batches(queries):
    from repro.configs.geoweb import CONFIG
    from repro.corpus import pad_trace_batch

    for i in range(0, len(queries), B):
        yield i, pad_trace_batch(queries[i : i + B], CONFIG.d_terms, CONFIG.q_rects)


def oracle_recall(ex, trace, rep) -> float:
    """recall@10 of the served answers to N_RECALL distinct queries
    against ``algorithms.oracle`` (every document scored, no budgets)."""
    import numpy as np

    from repro.configs.geoweb import CONFIG
    from repro.core import ranking

    check = distinct(trace)[:N_RECALL]
    if len(check) < N_RECALL:
        raise SystemExit(f"only {len(check)} distinct queries in the trace")
    pos = {id(q): i for i, q in enumerate(trace)}
    got = np.stack([rep.results[pos[id(q)]].ids for q in check])
    want = np.concatenate([
        np.asarray(ex.engine.oracle(b, CONFIG.budgets.top_k).ids)
        for _, b in batches(check)
    ])
    return ranking.topk_recall_np(want, got)


def check_answers(ex, trace, rep) -> int:
    """Every served answer holds every query term and overlaps the query
    footprint (the paper's query semantics, as
    tests/test_algorithms.py::test_results_respect_semantics checks), and
    each answer list is sorted by score.  Returns the answers checked."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import footprint as fp
    from repro.core.text_index import text_score_of_docs

    @jax.jit
    def holds(idx, batch, ids):
        def one(terms, q_rects, q_amps, ids):
            safe = jnp.clip(ids, 0, idx.spatial.n_docs - 1)
            match, _ = text_score_of_docs(idx.text, terms, safe)
            g = fp.geo_score(
                idx.spatial.doc_rects[safe], idx.spatial.doc_amps[safe],
                q_rects, q_amps,
            )
            return match & (g > 0)

        return jax.vmap(one)(batch.terms, batch.rects, batch.amps, ids)

    n = 0
    for i, batch in batches(trace):
        res = rep.results[i : i + B]
        ids = np.stack([r.ids for r in res] + [res[0].ids] * (B - len(res)))
        sc = np.stack([r.scores for r in res] + [res[0].scores] * (B - len(res)))
        live = np.isfinite(sc)
        ok = np.asarray(holds(ex.engine.index, batch, ids))
        if not ok[live].all():
            raise SystemExit(f"a served answer breaks the query semantics (batch {i // B})")
        if (np.diff(np.where(live, sc, -1e30), axis=1) > 0).any():
            raise SystemExit(f"served answers are not sorted by score (batch {i // B})")
        n += int(live[: len(res)].sum())
    return n


def make_geoweb_executor(corpus):
    from repro.configs.geoweb import CONFIG
    from repro.core.spatial_index import normalize_compress
    from repro.serving.factory import make_executor

    return make_executor(
        "single", corpus, algorithm="auto", budgets=geoweb_budgets(),
        weights=CONFIG.weights, grid=CONFIG.grid,
        m_intervals=CONFIG.m_intervals, fused=True,
        compress=normalize_compress(CONFIG.compress),
    )


def check_covering_recall(clock) -> None:
    """The served answers equal the oracle's where the budgets cover the
    queries: the same path at COVER_DOCS documents (≤ max_candidates)."""
    from repro.configs.geoweb import CONFIG
    from repro.corpus import make_zipf_trace

    corpus, _ = make_geoweb_corpus(COVER_DOCS)
    trace = make_zipf_trace(
        corpus, n_queries=N_QUERIES, d_terms=CONFIG.d_terms,
        q_rects=CONFIG.q_rects, seed=SEED + 1,
    )
    ex = make_geoweb_executor(corpus)
    rep = serve(ex, trace)
    recall = oracle_recall(ex, trace, rep)
    say(phase="recall", scope="covering", n_docs=COVER_DOCS, queries=N_RECALL,
        recall_at_10=round(recall, 4), floor=RECALL_FLOOR, seconds=clock.lap())
    if recall < RECALL_FLOOR:
        raise SystemExit(f"recall@10 {recall:.4f} below {RECALL_FLOOR}")


def one_chip() -> dict:
    import jax
    import numpy as np

    from repro.configs.geoweb import CONFIG
    from repro.corpus import make_zipf_trace, pad_trace_batch

    devs = tpu_devices()
    tpu = devs[0]
    for name, full, cut, why in CUTS:
        say(phase="cut", what=name, config=full, run=cut, why=repr(why))
    clock = Clock()
    check_tile_kernel(clock)
    check_covering_recall(clock)
    corpus, doc_len = make_geoweb_corpus(N_DOCS)
    trace = make_zipf_trace(
        corpus, n_queries=N_QUERIES, d_terms=CONFIG.d_terms,
        q_rects=CONFIG.q_rects, seed=SEED + 1,
    )
    say(phase="corpus", n_docs=N_DOCS, doc_len=doc_len, queries=len(trace),
        seconds=clock.lap())

    # build on the host (index arrays staged in host memory), then place
    try:
        host = jax.devices("cpu")[0]
    except RuntimeError:  # no CPU backend: arrays land on the chip directly
        host = tpu
    with jax.default_device(host):
        ex = make_geoweb_executor(corpus)
    idx = ex.engine.index
    say(phase="build", postings=idx.text.n_postings,
        postings_per_doc=round(idx.text.n_postings / N_DOCS, 2),
        toe_prints=idx.spatial.n_toeprints, grid=idx.spatial.grid,
        m_intervals=idx.spatial.m_intervals, host_peak_rss_gib=peak_rss_gib(),
        seconds=clock.lap())
    ex.engine.index = jax.block_until_ready(jax.device_put(idx, tpu))
    del idx
    stats = tpu.memory_stats() or {}
    say(phase="device_put", bytes_in_use=stats.get("bytes_in_use"),
        bytes_limit=stats.get("bytes_limit"), seconds=clock.lap())

    probe = pad_trace_batch(trace[:B], CONFIG.d_terms, CONFIG.q_rects)
    plans = ex.planner.candidates
    for plan in plans:
        fn = ex.engine._compiled(plan, ())
        n = count_kernels(fn, ex.engine.index, probe)
        say(phase="compile", plan=plan.label, tpu_custom_call=n, seconds=clock.lap())
        if plan.fused and n == 0:
            raise SystemExit(f"plan {plan.label} compiled without a Pallas kernel")

    rep = serve(ex, trace)
    mix = dict(sorted(rep.plan_queries.items()))
    scan = rep.plan_stats.get("scan", {})
    say(phase="serve", queries=rep.n_queries, batches=rep.n_batches,
        plan_mix=json.dumps(mix, separators=(",", ":")),
        scan_rounds=int(scan.get("scan_rounds", 0)),
        scan_candidates=int(scan.get("candidates", 0)), seconds=clock.lap())

    # fused kernels vs the plain-jnp path, plan by plan, on the same index
    from dataclasses import replace

    for plan in [p for p in plans if p.fused]:
        rows = [
            q for q in distinct(trace)
            if ex.plan_query(q.terms, q.rects, q.amps) == plan
        ]
        rows = (rows or distinct(trace))[:B]
        batch = pad_trace_batch(rows + rows[:1] * (B - len(rows)), CONFIG.d_terms,
                                CONFIG.q_rects)
        a = ex.engine.query(batch, plan=plan)
        b = ex.engine.query(batch, plan=replace(plan, fused=False))
        same = bool(np.array_equal(np.asarray(a.ids), np.asarray(b.ids)))
        sa, sb = np.asarray(a.scores), np.asarray(b.scores)
        fin = np.isfinite(sb)
        close = bool(np.array_equal(np.isfinite(sa), fin)) and bool(
            np.allclose(sa[fin], sb[fin], rtol=1e-3, atol=1e-6)
        )
        say(phase="fused_vs_jnp", plan=plan.label, rows=len(rows), ids_equal=same,
            scores_close=close, seconds=clock.lap())
        if not (same and close):
            raise SystemExit(f"fused {plan.label} disagrees with the jnp path")

    say(phase="semantics", answers=check_answers(ex, trace, rep), ok=True,
        seconds=clock.lap())
    recall = oracle_recall(ex, trace, rep)
    say(phase="recall", scope="shard", n_docs=N_DOCS, queries=N_RECALL,
        recall_at_10=round(recall, 4), floor=RECALL_FLOOR, seconds=clock.lap())
    if recall < RECALL_FLOOR:
        raise SystemExit(f"shard recall@10 {recall:.4f} below {RECALL_FLOOR}")
    return {"platform": tpu.platform, "kind": tpu.device_kind, "count": len(devs)}


def four_chips() -> dict:
    import jax
    import numpy as np

    from repro.configs.geoweb import CONFIG
    from repro.core.distributed import RegionRangePartitioner
    from repro.core.spatial_index import normalize_compress
    from repro.corpus import make_zipf_trace, pad_trace_batch
    from repro.launch.mesh import make_host_mesh
    from repro.serving.factory import make_executor

    devs = tpu_devices()
    if len(devs) != 4:
        raise SystemExit(f"--mesh needs 4 chips, found {len(devs)}")
    clock = Clock()
    corpus, doc_len = make_geoweb_corpus(MESH_DOCS)
    trace = make_zipf_trace(
        corpus, n_queries=64, d_terms=CONFIG.d_terms, q_rects=CONFIG.q_rects,
        seed=SEED + 1,
    )
    say(phase="corpus", n_docs=MESH_DOCS, doc_len=doc_len, seconds=clock.lap())
    common = dict(
        algorithm="k_sweep", budgets=geoweb_budgets(), weights=CONFIG.weights,
        partitioner=RegionRangePartitioner(), routing="footprint",
        grid=CONFIG.grid, m_intervals=CONFIG.m_intervals, fused=True,
        compress=normalize_compress(CONFIG.compress),
    )
    mesh = make_host_mesh((4, 1), ("data", "model"))
    meshx = make_executor("mesh", corpus, mesh=mesh, **common)
    leaves = jax.tree.leaves(jax.block_until_ready(meshx._index))
    index_bytes = sum(x.nbytes for x in leaves)
    per_dev = [(d.memory_stats() or {}).get("bytes_in_use", 0) for d in devs]
    say(phase="mesh_build", shards=meshx.n_shards, index_bytes=index_bytes,
        bytes_in_use=json.dumps(per_dev, separators=(",", ":")),
        seconds=clock.lap())
    # one shard per chip: every chip holds about a quarter of the index
    # (replicated, each would hold all of it; on device 0, the rest none)
    if min(per_dev) * 2 < max(per_dev) or max(per_dev) * 2 > index_bytes:
        raise SystemExit(f"shards are not one per chip: {per_dev}")
    ref = make_executor("sharded", corpus, n_shards=4, **common)
    say(phase="reference_build", shards=ref.n_shards, seconds=clock.lap())
    equal = 0
    for i in range(0, len(trace), 8):
        batch = pad_trace_batch(trace[i : i + 8], CONFIG.d_terms, CONFIG.q_rects)
        a = np.asarray(meshx.run(batch).ids)
        b = np.asarray(ref.run(batch).ids)
        if not np.array_equal(a, b):
            raise SystemExit(f"mesh ids differ from ShardedExecutor at batch {i // 8}")
        equal += a.shape[0]
    say(phase="mesh_vs_sharded", queries=equal, ids_equal=True, seconds=clock.lap())
    d = devs[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument(
        "--mesh", action="store_true",
        help="run only the four-chip doc-sharded mesh phase",
    )
    args = ap.parse_args()
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))
    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    devs = tpu_devices()
    say(phase="device", platform=devs[0].platform, kind=repr(devs[0].device_kind),
        count=len(devs), compile_cache=cache)
    device = four_chips() if args.mesh else one_chip()
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
