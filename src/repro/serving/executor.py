"""Batch executors: single-device, doc-sharded scatter-gather, SPMD mesh.

The executor is the serving layer's view of the engine: it takes a padded
:class:`~repro.core.algorithms.QueryBatch` and returns a
:class:`~repro.core.algorithms.TopKResult` with *global* doc ids.

* :class:`SingleDeviceExecutor` wraps one :class:`GeoSearchEngine`.
* :class:`ShardedExecutor` partitions the corpus doc-wise into ``S`` shards
  with a :class:`~repro.core.distributed.Partitioner` strategy object
  (hash round-robin, Morton-contiguous, or KD region ranges), builds one
  engine per shard, **scatters** each batch to the shards it can reach,
  and **gathers** the per-shard local top-k lists into a global top-k by
  a k-way merge.  Per-query merge traffic is O(k · S), independent of
  corpus size — the property that lets the architecture scale out.
* :class:`MeshExecutor` is the SPMD twin: one ``shard_map`` serve step per
  plan, with the per-stage byte counters *measured inside the step* and
  psum-reduced over the doc axes.

Footprint routing (``routing="footprint"``): each shard carries a
coverage-grid SAT of its toe prints (:mod:`repro.core.distributed`).
:meth:`ShardedExecutor.route_batch` tests every query footprint against
every shard's SAT; ``run`` then *skips* shards no query touches — result-
preserving because ``require_geo`` ranking scores a doc −inf when its geo
score is 0, so an unreachable shard can only return empty lists.  The mesh
executor gets the same semantics from ``make_serve_fn(with_routing=True)``,
which masks untouched shards inside the jit'd step.  Both report
``shards_touched`` (per query) and ``shards_visited`` (per batch) stats in
footprint mode; ``routing="broadcast"`` (the default) keeps the original
visit-everything behaviour and stat keys.

Plan-driven execution: every executor accepts ``run(batch, plan=...)``
with a :class:`~repro.core.planner.QueryPlan`, and ``algorithm="auto"``
builds a cost-based planner over the executor's corpus so the serving
layer can ask :meth:`plan_query` for each query's cheapest pipeline
before batching (plan-homogeneous buckets → one compile per plan×shape).
Fixed-algorithm executors return ``None`` from :meth:`plan_query` and run
exactly as before.

Telemetry: every executor exposes :meth:`attach_telemetry` (the server
calls it when built with a :class:`~repro.obs.Telemetry` handle) and
routes its engines' compile counters / the planner's probe counters into
the metrics registry.  With a tracer attached, :class:`ShardedExecutor`
records one **wall-clock** span per shard, from the shard's dispatch to
its host pull.  The single-device and mesh executors record none: a span
round their dispatch would end before the device finishes (the server's
``geo.dispatch`` and ``geo.result`` stages time the call and the wait).
``telemetry=None`` (the default) leaves ``run`` untouched.
"""
from __future__ import annotations

from dataclasses import replace

import jax
import numpy as np

from repro.core import algorithms as alg
from repro.core import ranking
from repro.core.distributed import (
    MortonPartitioner,
    Partitioner,
    _require_partitioner,
    _valid_rects_np,
    coverage_grid_np,
    coverage_sat_np,
    footprint_touch_np,
)
from repro.core.engine import GeoSearchEngine
from repro.core.planner import CostModel, Planner, QueryPlan
from repro.core.text_index import global_idf_np

ROUTINGS = ("broadcast", "footprint")


def _check_routing(routing: str) -> str:
    if routing not in ROUTINGS:
        raise ValueError(f"routing must be one of {ROUTINGS}, got {routing!r}")
    return routing


def _reject_partition_kwarg(kw: dict) -> None:
    """The ``partition="hash"|"geo"`` string flag is gone — fail loudly
    instead of letting the stale kwarg leak into engine query kwargs."""
    if "partition" in kw:
        raise TypeError(
            "partition= strings were replaced by the Partitioner API: pass "
            "partitioner=HashPartitioner() / MortonPartitioner() / "
            "RegionRangePartitioner() (strings resolve only at the CLI "
            "boundary via repro.core.distributed.resolve_partitioner)"
        )


class SingleDeviceExecutor:
    """Run batches through one engine; the trivial executor."""

    def __init__(self, engine: GeoSearchEngine, algorithm: str = "k_sweep", **kw):
        self.engine = engine
        self.algorithm = algorithm
        self.kw = kw
        self.planner: Planner | None = None
        if algorithm == "auto":
            self.planner = Planner.from_engine(
                engine, fused=bool(kw.get("fused", False))
            )

    @property
    def top_k(self) -> int:
        return self.engine.budgets.top_k

    def attach_telemetry(self, telemetry) -> None:
        if telemetry and telemetry.metrics is not None:
            self.engine.metrics = telemetry.metrics
            if self.planner is not None:
                self.planner.model.metrics = telemetry.metrics

    def plan_query(self, terms, rects, amps) -> QueryPlan | None:
        """Cheapest plan for one query; ``None`` when the algorithm is fixed."""
        if self.planner is None:
            return None
        return self.planner.plan_query(terms, rects, amps)

    def run(
        self, batch: alg.QueryBatch, plan: QueryPlan | None = None
    ) -> alg.TopKResult:
        if plan is not None:
            return self.engine.query(batch, plan=plan, **self.kw)
        return self.engine.query(batch, self.algorithm, **self.kw)


class ShardedExecutor:
    """Doc-sharded scatter-gather execution over per-shard engines.

    Shard dispatch is *overlapped* by default: every routed shard's query
    is submitted back-to-back (jax dispatch is asynchronous, so the device
    work for shard ``s+1`` starts while shard ``s`` still computes) and the
    host synchronizes exactly once, when the merge pulls the per-shard
    top-k lists.  ``overlap=False`` restores the strictly sequential loop
    (each shard runs to completion before the next is dispatched) — the
    two paths are bit-identical in results and per-stage counters, which
    ``tests/test_serving.py`` pins.
    """

    def __init__(
        self,
        engines,
        global_ids,
        algorithm: str = "k_sweep",
        routing: str = "broadcast",
        overlap: bool = True,
        **kw,
    ):
        _reject_partition_kwarg(kw)
        self.engines: list[GeoSearchEngine] = engines
        self.global_ids: list[np.ndarray] = global_ids  # per shard: local → global
        self.algorithm = algorithm
        self.routing = _check_routing(routing)
        self.overlap = overlap
        self._coverage_sats: np.ndarray | None = None  # lazy f32[S, G+1, G+1]
        self.kw = kw
        self.telemetry = None
        self.planner: Planner | None = None
        if algorithm == "auto":
            # corpus-global features: df and tile coverage summed over the
            # shards, block metadata concatenated
            model = CostModel.from_shards(
                [e.index for e in engines], engines[0].budgets
            )
            self.planner = Planner(
                model=model,
                candidates=Planner.make_candidates(
                    engines[0].budgets, fused=bool(kw.get("fused", False))
                ),
            )

    @property
    def n_shards(self) -> int:
        return len(self.engines)

    @property
    def top_k(self) -> int:
        return self.engines[0].budgets.top_k

    def attach_telemetry(self, telemetry) -> None:
        self.telemetry = telemetry
        if telemetry and telemetry.metrics is not None:
            for eng in self.engines:
                eng.metrics = telemetry.metrics
            if self.planner is not None:
                self.planner.model.metrics = telemetry.metrics

    def plan_query(self, terms, rects, amps) -> QueryPlan | None:
        if self.planner is None:
            return None
        return self.planner.plan_query(terms, rects, amps)

    # ------------------------------------------------------------------
    @staticmethod
    def build(
        doc_terms: list[np.ndarray],
        doc_rects: np.ndarray,
        doc_amps: np.ndarray,
        n_terms: int,
        pagerank: np.ndarray,
        n_shards: int,
        partitioner: Partitioner | None = None,
        grid: int = 64,
        budgets: alg.QueryBudgets | None = None,
        weights: ranking.RankWeights | None = None,
        algorithm: str = "k_sweep",
        routing: str = "broadcast",
        compress: "bool | str" = False,
        layout: str = "docid",
        overlap: bool = True,
        **kw,
    ) -> "ShardedExecutor":
        _reject_partition_kwarg(kw)
        budgets = budgets or alg.QueryBudgets()
        partitioner = _require_partitioner(partitioner, default=MortonPartitioner)
        shard_ids = np.asarray(partitioner.assign(doc_rects, n_shards))
        idf_global = global_idf_np(doc_terms, n_terms)
        engines, gids = [], []
        for s in range(n_shards):
            # ascending global ids in-shard: local tie-breaks match global
            sel = np.flatnonzero(shard_ids == s)
            # global IDF built in directly: impacts round to f32 once from
            # partition-independent statistics, so per-doc scores are
            # bit-identical across shard layouts (routing equivalence gate)
            eng = GeoSearchEngine.build(
                [doc_terms[i] for i in sel],
                doc_rects[sel],
                doc_amps[sel],
                n_terms,
                pagerank=pagerank[sel],
                grid=grid,
                budgets=budgets,
                weights=weights,
                idf=idf_global,
                compress=compress,
                layout=layout,
            )
            engines.append(eng)
            gids.append(sel.astype(np.int32))
        return ShardedExecutor(
            engines, gids, algorithm, routing=routing, overlap=overlap, **kw
        )

    # ------------------------------------------------------------------
    def _coverage(self) -> np.ndarray:
        """Stacked per-shard coverage SATs ``f32[S, G+1, G+1]`` (lazy)."""
        if self._coverage_sats is None:
            from repro.core.spatial_index import SCALE_BLOCK

            sats = []
            for eng in self.engines:
                sp = eng.index.spatial
                amps = np.asarray(sp.tp_amps).astype(np.float32)
                if sp.tp_amp_scale.shape[0]:  # decode int8 amp stores
                    sc = np.asarray(sp.tp_amp_scale)
                    amps = amps * np.repeat(sc, SCALE_BLOCK)[: amps.shape[0]]
                sats.append(
                    coverage_sat_np(
                        coverage_grid_np(
                            np.asarray(sp.tp_rects).astype(np.float32), amps
                        )
                    )
                )
            self._coverage_sats = np.stack(sats)
        return self._coverage_sats

    def route_batch(self, batch: alg.QueryBatch) -> tuple[np.ndarray, np.ndarray]:
        """Footprint-routing decision for a batch.

        Returns ``(visit bool[S], touched f64[B])``: which shards to
        scatter the batch to (any query's footprints reach them) and how
        many shards each query's own footprints touch.
        """
        touch = footprint_touch_np(
            self._coverage(), np.asarray(batch.rects), np.asarray(batch.amps)
        )  # [S, B]
        return touch.any(axis=1), touch.sum(axis=0, dtype=np.float64)

    def run(
        self, batch: alg.QueryBatch, plan: QueryPlan | None = None
    ) -> alg.TopKResult:
        """Scatter the batch to the routed shards; gather + merge top-k."""
        all_ids, all_scores = [], []
        stats_acc: dict[str, np.ndarray] = {}
        visit = np.ones(self.n_shards, dtype=bool)
        if self.routing == "footprint":
            visit, touched = self.route_batch(batch)
            if not _valid_rects_np(batch.rects, batch.amps).any():
                # all-padding batch (server warmup): broadcast so every
                # shard engine still compiles during the warmup pass
                visit[:] = True
            stats_acc["shards_touched"] = touched
            stats_acc["shards_visited"] = np.float64(visit.sum())
            if not visit.any():
                b, k = batch.terms.shape[0], self.top_k
                return alg.TopKResult(
                    ids=np.full((b, k), -1, dtype=np.int32),
                    scores=np.full((b, k), -np.inf, dtype=np.float32),
                    stats=stats_acc,
                )
        tracer = self.telemetry.tracer if self.telemetry else None
        label = plan.label if plan is not None else self.algorithm
        # phase 1 — scatter: dispatch every routed shard's query.  jax
        # dispatch is asynchronous, so with overlap the device work of all
        # shards is in flight before any result is pulled to host
        pending = []
        for shard, (eng, gid) in enumerate(zip(self.engines, self.global_ids)):
            if not visit[shard]:
                continue
            t0 = tracer.wall_now() if tracer is not None else 0.0
            if plan is not None:
                # each shard engine re-clamps the plan's sweep budget to
                # its own toe-print store inside _compiled
                res = eng.query(batch, plan=plan, **self.kw)
            else:
                res = eng.query(batch, self.algorithm, **self.kw)
            if not self.overlap:
                # sequential reference path: shard s completes before
                # shard s+1 dispatches
                jax.block_until_ready((res.ids, res.scores))
            pending.append((shard, gid, res, t0))
        # phase 2 — gather: the single host sync point per shard result
        for shard, gid, res, t0 in pending:
            ids = np.asarray(res.ids)
            scores = np.asarray(res.scores).copy()
            valid = ids >= 0
            g = np.where(valid, gid[np.clip(ids, 0, len(gid) - 1)], -1)
            scores[~valid] = -np.inf
            all_ids.append(g)
            all_scores.append(scores)
            for key, v in res.stats.items():
                v = np.asarray(v, dtype=np.float64)
                stats_acc[key] = stats_acc.get(key, 0.0) + v
            if tracer is not None:
                # span runs from this shard's dispatch to its host pull —
                # under overlap, shard spans legitimately overlap in time
                tracer.span(
                    f"shard {shard}", f"query[{label}]", t0, tracer.wall_now(),
                    args={"batch": int(batch.terms.shape[0])},
                )
        k = all_ids[0].shape[-1]
        ids = np.concatenate(all_ids, axis=-1)  # [B, S*k]
        scores = np.concatenate(all_scores, axis=-1)
        # gather: global top-k, ties broken by lower global docID
        order = np.lexsort((ids, -scores), axis=-1)[:, :k]
        m_ids = np.take_along_axis(ids, order, axis=-1)
        m_scores = np.take_along_axis(scores, order, axis=-1)
        m_ids = np.where(np.isfinite(m_scores), m_ids, -1)
        return alg.TopKResult(ids=m_ids, scores=m_scores, stats=stats_acc)


class MeshExecutor:
    """SPMD executor: one ``shard_map`` serve step per plan over a mesh.

    The mesh-parallel twin of :class:`ShardedExecutor` — the same doc-wise
    partitioning, but all shards execute concurrently on their own devices
    and the top-k merge runs as ``all_gather`` collectives inside the jit'd
    step (:func:`repro.core.distributed.make_serve_fn`).  The doc/query
    mesh axes are resolved from the logical sharding rules
    (:mod:`repro.sharding.specs`: ``docs`` → ('pod','data'), ``queries`` →
    ('model',)), so the same code follows whatever mesh topology is in use.

    Requires a multi-device runtime (or ``XLA_FLAGS=
    --xla_force_host_platform_device_count=N``); exercised by the
    subprocess tests in ``tests/test_distributed.py``.

    Per-stage byte counters are **measured inside the step**: each shard's
    per-query stats vectors are psum-reduced over the doc axes and ride
    back with the ids/scores (``make_serve_fn(with_stats=True)``), so mesh
    serving reports exact traffic — the same numbers the host-side
    executors measure, asserted equal in ``tests/test_serving.py``.

    Serve steps are compiled lazily per plan: the fixed-algorithm step at
    construction, and one step per distinct :class:`QueryPlan` the planner
    selects under ``algorithm="auto"``.
    """

    def __init__(
        self,
        mesh,
        serve_fn,
        sharded_index,
        top_k: int,
        budgets: alg.QueryBudgets | None = None,
        algorithm: str = "k_sweep",
        n_rect_slots: int = 4,
        block_size: int = 128,
        weights: ranking.RankWeights | None = None,
        doc_axes: tuple[str, ...] = ("data",),
        query_axis: str = "model",
        fused: bool = False,
        routing: str = "broadcast",
    ):
        self.mesh = mesh
        self._index = sharded_index
        self.top_k = top_k
        self.budgets = budgets or alg.QueryBudgets(top_k=top_k)
        self.algorithm = algorithm
        self.n_rect_slots = n_rect_slots  # doc footprint slots (R)
        self.block_size = block_size  # block-max metadata granularity
        self.weights = weights or ranking.RankWeights()
        self.doc_axes = doc_axes
        self.query_axis = query_axis
        self.fused = fused
        self.routing = _check_routing(routing)
        # plan (or None = the construction-time fixed config) → serve step
        self._serve_fns: dict = {None: serve_fn}
        self.telemetry = None
        self.planner: Planner | None = None
        if algorithm == "auto":
            self.planner = Planner(
                model=CostModel.from_sharded_index(sharded_index, self.budgets),
                candidates=Planner.make_candidates(self.budgets, fused=fused),
            )

    @staticmethod
    def build(
        doc_terms: list[np.ndarray],
        doc_rects: np.ndarray,
        doc_amps: np.ndarray,
        n_terms: int,
        pagerank: np.ndarray,
        mesh,
        partitioner: Partitioner | None = None,
        grid: int = 64,
        budgets: alg.QueryBudgets | None = None,
        weights: ranking.RankWeights | None = None,
        algorithm: str = "k_sweep",
        fused: bool = False,
        routing: str = "broadcast",
        compress: "bool | str" = False,
        layout: str = "docid",
        **kw,
    ) -> "MeshExecutor":
        from jax.sharding import NamedSharding, PartitionSpec

        from repro.core.distributed import (
            make_serve_fn,
            shard_corpus_np,
            sharded_index_specs,
        )
        from repro.sharding.specs import DEFAULT_RULES

        _reject_partition_kwarg(kw)
        if kw:
            raise TypeError(f"unexpected keyword arguments: {sorted(kw)}")
        budgets = budgets or alg.QueryBudgets()
        partitioner = _require_partitioner(partitioner, default=MortonPartitioner)
        doc_axes = tuple(a for a in DEFAULT_RULES["docs"] if a in mesh.axis_names)
        query_axis = next(a for a in DEFAULT_RULES["queries"] if a in mesh.axis_names)
        n_shards = 1
        for a in doc_axes:
            n_shards *= mesh.shape[a]
        sharded = shard_corpus_np(
            doc_terms, doc_rects, doc_amps, pagerank, n_terms,
            n_shards, partitioner, grid=grid, compress=compress,
            layout=layout,
        )
        # each device holds its own shard: place every field by its spec
        # (left on one device, every step would re-shard the whole index)
        specs = sharded_index_specs(
            doc_axes, grid, n_terms, block_size=sharded.block_size,
            coverage_grid=sharded.coverage_grid,
            max_term_blocks=sharded.max_term_blocks, layout=sharded.layout,
            max_term_segments=sharded.max_term_segments,
        )
        sharded = jax.device_put(
            sharded,
            jax.tree.map(
                lambda s: NamedSharding(mesh, s), specs,
                is_leaf=lambda s: isinstance(s, PartitionSpec),
            ),
        )
        # sweeps cannot exceed a shard's toe-print store (same clamp as
        # GeoSearchEngine.build applies for the single-index case)
        budgets = replace(
            budgets,
            sweep_budget=min(budgets.sweep_budget, sharded.tp_rects.shape[1]),
        )
        weights = weights or ranking.RankWeights()
        serve_algorithm = "k_sweep" if algorithm == "auto" else algorithm
        serve = make_serve_fn(
            mesh, budgets, weights,
            doc_axes=doc_axes, query_axis=query_axis,
            algorithm=serve_algorithm, grid=grid, n_terms=n_terms,
            fused=fused, block_size=sharded.block_size,
            with_stats=True, with_routing=routing == "footprint",
            max_term_blocks=sharded.max_term_blocks,
            layout=sharded.layout,
            max_term_segments=sharded.max_term_segments,
        )
        return MeshExecutor(
            mesh, serve, sharded, budgets.top_k,
            budgets=budgets, algorithm=algorithm,
            n_rect_slots=doc_rects.shape[1],
            block_size=sharded.block_size,
            weights=weights, doc_axes=doc_axes, query_axis=query_axis,
            fused=fused, routing=routing,
        )

    @property
    def n_shards(self) -> int:
        return self._index.n_shards

    def attach_telemetry(self, telemetry) -> None:
        self.telemetry = telemetry
        if telemetry and telemetry.metrics is not None:
            if self.planner is not None:
                self.planner.model.metrics = telemetry.metrics

    def plan_query(self, terms, rects, amps) -> QueryPlan | None:
        if self.planner is None:
            return None
        return self.planner.plan_query(terms, rects, amps)

    def _serve_for(self, plan: QueryPlan | None):
        """The (lazily compiled) shard_map serve step for a plan."""
        if plan in self._serve_fns:
            return self._serve_fns[plan]
        if self.telemetry and self.telemetry.metrics is not None:
            self.telemetry.metrics.inc("engine.compiled_fns_total")
        from repro.core.distributed import make_serve_fn

        budgets = replace(
            plan.budgets,
            sweep_budget=min(
                plan.budgets.sweep_budget, self._index.tp_rects.shape[1]
            ),
        )
        serve = make_serve_fn(
            self.mesh, budgets, self.weights,
            doc_axes=self.doc_axes, query_axis=self.query_axis,
            algorithm=plan.algorithm, grid=self._index.grid,
            n_terms=self._index.n_terms, fused=plan.fused,
            block_size=self._index.block_size, with_stats=True,
            with_routing=self.routing == "footprint",
            max_term_blocks=self._index.max_term_blocks,
            layout=self._index.layout,
            max_term_segments=self._index.max_term_segments,
        )
        self._serve_fns[plan] = serve
        return serve

    def run(
        self, batch: alg.QueryBatch, plan: QueryPlan | None = None
    ) -> alg.TopKResult:
        serve = self._serve_for(plan)
        with self.mesh:
            out = serve(self._index, batch)
        if len(out) == 3:
            ids, scores, stats = out
        else:  # hand-built executor around a stats-less make_serve_fn
            (ids, scores), stats = out, {}
        return alg.TopKResult(
            ids=ids,
            scores=scores,
            stats={k: np.asarray(v) for k, v in stats.items()},
        )
