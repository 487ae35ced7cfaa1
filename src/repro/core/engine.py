"""GeoSearchEngine: build / hold indexes, execute batched geo queries.

This is the public API of the paper's system.  It owns

* a ``TextIndex`` (CSR inverted index + impacts + optional block bitmaps),
* a ``SpatialIndex`` (Morton toe-print store + tile-interval grid + doc-major
  footprint mirror),
* per-document global scores (PageRank),
* query ``Budgets`` and ranking weights,

and exposes ``query(batch, algorithm=...)`` — a jit-compiled, batched query
pipeline — plus ``oracle`` for exact evaluation.

Execution is *plan-driven*: every call resolves to a
:class:`~repro.core.planner.QueryPlan` (algorithm + budgets + kernel knobs)
and the compiled-function cache is keyed by plan, so callers can hold
several pipeline variants against one index without recompiling or mutating
engine state.  ``algorithm="auto"`` routes through the engine's cost-based
:class:`~repro.core.planner.Planner`, which picks the cheapest plan per
query from posting-list lengths and footprint coverage estimates.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, replace
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import algorithms as alg
from repro.core import ranking
from repro.core.planner import Planner, QueryPlan
from repro.core.spatial_index import SpatialIndex, build_spatial_index_np
from repro.core.text_index import TextIndex, build_text_index_np


def program_name(plan: QueryPlan) -> str:
    """The name a plan's jitted program runs under: ``geo_`` and the plan's
    label, each character outside ``[A-Za-z0-9_]`` made ``_``
    (``text_first+prune+fused`` → ``geo_text_first_prune_fused``)."""
    return "geo_" + re.sub(r"\W", "_", plan.label)


@jax.tree_util.register_dataclass
@dataclass(frozen=True)
class GeoIndex:
    """The full index state — a single pytree, shardable under pjit/shard_map."""

    text: TextIndex
    spatial: SpatialIndex
    pagerank: jax.Array  # f32[N]


@dataclass
class GeoSearchEngine:
    index: GeoIndex
    budgets: alg.QueryBudgets
    weights: ranking.RankWeights

    # ------------------------------------------------------------------
    # construction
    # ------------------------------------------------------------------
    @staticmethod
    def build(
        doc_terms: list[np.ndarray],
        doc_rects: np.ndarray,
        doc_amps: np.ndarray,
        n_terms: int,
        pagerank: np.ndarray | None = None,
        grid: int = 64,
        m_intervals: int = 2,
        n_bitmap_terms: int = 0,
        budgets: alg.QueryBudgets | None = None,
        weights: ranking.RankWeights | None = None,
        compress: "bool | str" = False,
        block_size: int = 128,
        idf: np.ndarray | None = None,
        layout: str = "docid",
    ) -> "GeoSearchEngine":
        # idf: corpus-global IDF override for shard engines (see
        # build_text_index_np — keeps impacts partition-independent)
        # layout: posting order — "docid" (reference) or "impact"
        # (descending-impact segments; see text_index module docstring)
        from repro.core.spatial_index import normalize_compress

        mode = normalize_compress(compress)
        # one compression entry point: the builder quantizes impacts (f16
        # under any compressed mode) BEFORE computing blk_max_impact, so
        # pruning bounds are taken over the stored values
        budgets = budgets or alg.QueryBudgets()
        text = build_text_index_np(
            doc_terms, n_terms, n_bitmap_terms, idf=idf,
            compress=(mode != "none"),
            impact_dtype=(np.float16 if mode != "none" else None),
            layout=layout,
            # exact answers: the scan masks long posting lists by bitmap
            bitmap_min_df=alg.SCAN_BITMAP_MIN_DF if budgets.exact else None,
        )
        spatial = build_spatial_index_np(
            doc_rects, doc_amps, grid, m_intervals, compress=mode,
            block_size=block_size,
        )
        n = len(doc_terms)
        if pagerank is None:
            pagerank = np.full((n,), 0.1, dtype=np.float32)
        # sweeps cannot exceed the store
        budgets = replace(
            budgets, sweep_budget=min(budgets.sweep_budget, spatial.n_toeprints)
        )
        return GeoSearchEngine(
            index=GeoIndex(text=text, spatial=spatial, pagerank=jnp.asarray(pagerank)),
            budgets=budgets,
            weights=weights or ranking.RankWeights(),
        )

    # ------------------------------------------------------------------
    # query execution
    # ------------------------------------------------------------------
    def query(
        self,
        batch: alg.QueryBatch,
        algorithm: str = "k_sweep",
        plan: QueryPlan | None = None,
        **kw,
    ) -> alg.TopKResult:
        """Run one batch under a plan.

        ``plan=None`` builds the default plan for ``algorithm`` from the
        engine's own budgets (bit-identical to the pre-plan API).
        ``algorithm="auto"`` asks the engine's planner for a per-query plan
        and gathers each row's result from its assigned plan's run.
        """
        if plan is None:
            if algorithm == "auto":
                return self._query_auto(batch, **kw)
            plan = QueryPlan(
                algorithm, self.budgets, fused=bool(kw.pop("fused", False))
            )
        else:
            kw.pop("fused", None)  # the plan owns the fused flag
        fn = self._compiled(plan, tuple(sorted(kw.items())))
        return fn(self.index, batch)

    @property
    def planner(self) -> Planner:
        """Lazily-built cost-based planner over this engine's index."""
        p = self.__dict__.get("_planner")
        if p is None:
            p = Planner.from_engine(self)
            self.__dict__["_planner"] = p
        return p

    def _query_auto(self, batch: alg.QueryBatch, **kw) -> alg.TopKResult:
        """Per-query plan dispatch at the engine level.

        The serving layer dispatches plan-homogeneous batches (one compile
        and one execution per plan × shape); here, against a single padded
        batch, we emulate that: each *distinct* chosen plan runs on the
        whole batch and every row's ids/scores/stats are gathered from its
        assigned plan's run — so the per-query counters are exactly what
        per-query dispatch would have measured, at the price of executing
        each selected pipeline over the full batch.
        """
        fused = bool(kw.pop("fused", False))
        plans = self.planner.plan_rows(batch)
        if fused:  # route rows with a fused Pallas pipeline through it
            plans = [
                replace(p, fused=True)
                if p.algorithm == "k_sweep"
                or (p.algorithm == "text_first" and p.budgets.prune)
                else p
                for p in plans
            ]
        uniq: list[QueryPlan] = []
        for p in plans:
            if p not in uniq:
                uniq.append(p)
        if len(uniq) == 1:
            return self.query(batch, plan=uniq[0], **kw)
        results = {p: self.query(batch, plan=p, **kw) for p in uniq}
        rows = [np.asarray([plan == p for plan in plans]) for p in uniq]
        ids = np.zeros_like(np.asarray(results[uniq[0]].ids))
        scores = np.zeros_like(np.asarray(results[uniq[0]].scores))
        keys = sorted({k for r in results.values() for k in r.stats})
        B = batch.batch
        stats = {k: np.zeros((B,), np.float64) for k in keys}
        for p, sel in zip(uniq, rows):
            res = results[p]
            ids[sel] = np.asarray(res.ids)[sel]
            scores[sel] = np.asarray(res.scores)[sel]
            for k in keys:  # absent counters contribute 0 for this plan
                if k in res.stats:
                    v = np.asarray(res.stats[k], np.float64)
                    stats[k][sel] = v[sel] if v.ndim else v
        return alg.TopKResult(
            ids=jnp.asarray(ids),
            scores=jnp.asarray(scores),
            stats={k: jnp.asarray(v) for k, v in stats.items()},
        )

    def oracle(self, batch: alg.QueryBatch, k: int | None = None) -> alg.TopKResult:
        k = k or self.budgets.top_k
        cache = self.__dict__.setdefault("_oracle_fns", {})
        if k not in cache:  # one program per k, not one per call
            cache[k] = jax.jit(
                lambda idx, b: alg.oracle(
                    idx.text, idx.spatial, idx.pagerank, b, k, self.weights
                )
            )
        return cache[k](self.index, batch)

    def _compiled(self, plan: QueryPlan, kw_key) -> Callable:
        """Plan-keyed compiled-function cache (one jit program per plan)."""
        cache = self.__dict__.setdefault("_fn_cache", {})
        key = (plan, kw_key)
        if key not in cache:
            # metrics registry is attached by the serving layer's
            # attach_telemetry; each distinct plan x kw jit program counts
            m = getattr(self, "metrics", None)
            if m is not None:
                m.inc("engine.compiled_fns_total")
            fn = alg.get_algorithm(plan.algorithm)
            kw = {**plan.engine_kw(), **dict(kw_key)}
            # a plan's budgets may come from another shard's engine: sweeps
            # can never exceed THIS index's toe-print store
            budgets = replace(
                plan.budgets,
                sweep_budget=min(
                    plan.budgets.sweep_budget, self.index.spatial.n_toeprints
                ),
            )

            def run(index: GeoIndex, batch: alg.QueryBatch):
                return fn(
                    index.text,
                    index.spatial,
                    index.pagerank,
                    batch,
                    budgets,
                    self.weights,
                    **kw,
                )

            # the program carries its plan's name (``jit_geo_scan``), so its
            # runs name themselves in a profiler trace
            run.__name__ = run.__qualname__ = program_name(plan)
            cache[key] = jax.jit(run)
        return cache[key]

    # ------------------------------------------------------------------
    # evaluation
    # ------------------------------------------------------------------
    def recall_at_k(
        self,
        batch: alg.QueryBatch,
        algorithm: str = "k_sweep",
        k: int | None = None,
        **kw,
    ) -> float:
        """Recall@k of an algorithm vs the exact oracle (``kw`` forwarded
        to the algorithm, e.g. ``fused=True``)."""
        k = k or self.budgets.top_k
        got = self.query(batch, algorithm, **kw)
        want = self.oracle(batch, k)
        return ranking.topk_recall_np(want.ids, got.ids)
