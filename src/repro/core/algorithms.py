"""The paper's three query-processing algorithms, plus an exact scan,
batched & jit-safe.

All share the signature::

    (text_index, spatial_index, pagerank, query, budgets, weights)
        -> TopKResult(ids [B,k], scores [B,k], stats {str: [B] or scalar})

`stats` counts the observable the paper optimizes — bytes moved per pipeline
stage (disk traffic in 2010 = HBM traffic here) — so benchmarks can report
both wall time and modeled I/O.

Algorithms (paper §IV):

* TEXT-FIRST  — inverted index first, then fetch footprints by docID.
* GEO-FIRST   — spatial structure first (tile grid standing in for the
                memory-resident R*-tree), then filter by text, then fetch.
* K-SWEEP     — tile intervals → ≤ k coalesced sweeps → bulk contiguous
                fetch → docID translation → text filter → precise scoring.

Their static budgets truncate a query that overflows them.  SCAN answers
any query exactly (dense footprint pass, then rounds of probes in
optimistic-score order); the planner routes to it under
``QueryBudgets.exact``.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial

import jax
import jax.numpy as jnp

from repro.core import footprint as fp
from repro.core import ranking, spatial_index as sidx, text_index as tidx
from repro.core.spatial_index import INVALID

# UNCOMPRESSED reference record sizes.  The live byte stats below use the
# per-index properties instead (SpatialIndex.tp_bytes / doc_bytes,
# TextIndex.posting_bytes), which report the *stored* — possibly
# compressed — sizes; these constants remain the fixed uncompressed
# baseline for compression-ratio reporting.
TP_BYTES = 4 * 4 + 4 + 4  # rect + amp + docid per toe print
POSTING_BYTES = 4 + 4  # docid + impact

# ---------------------------------------------------------------------------
# algorithm registry
# ---------------------------------------------------------------------------
# One uniform dispatch surface instead of ad-hoc string→fn maps scattered
# through the engine / distributed / executor layers.  Every registered fn
# shares the module-docstring signature; callers resolve by name via
# ``get_algorithm`` (which raises with the valid menu on a typo) and the
# planner enumerates ``ALGORITHMS`` to build its candidate plans.

ALGORITHMS: dict[str, "object"] = {}


def register_algorithm(name: str):
    """Class-of-service decorator: add a query algorithm to the registry."""

    def deco(fn):
        ALGORITHMS[name] = fn
        return fn

    return deco


def get_algorithm(name: str):
    """Resolve a registered algorithm by name (clear error on a typo)."""
    try:
        return ALGORITHMS[name]
    except KeyError:
        raise ValueError(
            f"unknown algorithm {name!r}; registered: {sorted(ALGORITHMS)} "
            "(plus 'auto' at the engine/serving layer, which routes through "
            "the cost-based planner)"
        ) from None


@jax.tree_util.register_dataclass
@dataclass(frozen=True)
class QueryBudgets:
    """Static shape budgets (early-termination style approximations)."""

    max_candidates: int = field(default=1024, metadata=dict(static=True))
    max_tiles: int = field(default=64, metadata=dict(static=True))
    k_sweeps: int = field(default=4, metadata=dict(static=True))
    sweep_budget: int = field(default=2048, metadata=dict(static=True))
    top_k: int = field(default=10, metadata=dict(static=True))
    # geo-score early termination in K-SWEEP (paper future work; lossy —
    # keeps only the max_candidates strongest toe prints before text probing,
    # but only AFTER paying the full stream + score cost)
    early_termination: bool = field(default=False, metadata=dict(static=True))
    # block-max pruned K-SWEEP: skip whole sweep blocks whose precomputed
    # upper bound (SpatialIndex blk_* columns) cannot beat the running
    # partial top-max_candidates threshold θ — the candidates never get
    # scored, probed, or sorted, and bytes_spatial counts only the blocks
    # actually streamed.  Subsumes early_termination (the top-C cut is part
    # of the pruned select stage).
    prune: bool = field(default=False, metadata=dict(static=True))
    # pruned select stage: additionally drop candidates whose partial geo
    # score is ≤ prune_eps × query_mass (their normalized geo contribution
    # is below prune_eps).  0 keeps every positive candidate — lossless for
    # the final top-k whenever max_candidates covers the survivors.
    prune_eps: float = field(default=0.0, metadata=dict(static=True))
    # exact answers under ``algorithm="auto"``: the planner picks a budgeted
    # plan only where its budgets provably cover the query, and the
    # exhaustive ``scan`` otherwise (``planner.CostModel.covers``)
    exact: bool = field(default=False, metadata=dict(static=True))


@jax.tree_util.register_dataclass
@dataclass(frozen=True)
class QueryBatch:
    """A batch of geo queries (fixed shapes).

    terms:  i32[B, d]   (−1 padded)
    rects:  f32[B, Qr, 4] query footprint rectangles (empty-rect padded)
    amps:   f32[B, Qr]
    """

    terms: jax.Array
    rects: jax.Array
    amps: jax.Array

    @property
    def batch(self) -> int:
        return self.terms.shape[0]


@jax.tree_util.register_dataclass
@dataclass(frozen=True)
class TopKResult:
    ids: jax.Array  # i32[B, k], −1 padded
    scores: jax.Array  # f32[B, k]
    stats: dict[str, jax.Array]


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _geo_score_docs(spatial, doc_ids, valid, q_rects, q_amps, geo_scorer):
    """Gather doc-major footprints and score them against the query."""
    safe = jnp.where(valid, doc_ids, 0)
    rects = spatial.doc_rects[safe]  # [C, R, 4]
    amps = jnp.where(valid[:, None], spatial.doc_amps[safe], 0.0)
    g = geo_scorer(rects, amps, q_rects, q_amps)
    return jnp.where(valid, g, 0.0)


def _default_doc_scorer(rects, amps, q_rects, q_amps):
    return fp.geo_score(rects, amps, q_rects, q_amps)


def _count_unique(ids: jax.Array, valid: jax.Array) -> jax.Array:
    """Number of distinct ids among the valid positions (fixed shape)."""
    big = jnp.int32(2**31 - 1)
    s = jnp.sort(jnp.where(valid, ids, big))
    nxt = jnp.concatenate([s[1:], jnp.full((1,), -2, jnp.int32)])
    return jnp.sum(((s != nxt) & (s != big)).astype(jnp.int32))


def _probe_plane_rows(text: tidx.TextIndex) -> int:
    """``doc_plane`` rows one ``probe_term`` key reads from a compressed
    index: one a segment searched under the impact layout, else one."""
    return text.max_term_segments if text.layout == "impact" else 1


def _sorted_dedupe(ids: jax.Array, valid: jax.Array):
    """Sort ids (invalid → +inf sentinel) and mark the last element of each
    run — a fixed-shape dedupe.

    Deliberately cumsum-free: the old run-sum helper accumulated per-doc
    values through an associative-scan prefix *difference* (``cs - before``),
    whose rounding residue (~1e-10) could leak into docs whose exact total
    was 0 — the documented ``require_geo`` leak.  Both callers only ever
    needed the dedupe, and the final geo score is recomputed exactly from
    each doc's own footprint rows (see step 6 of ``k_sweep``), so no
    prefix-sum ever touches a score that feeds ``require_geo``.

    Returns (sorted_ids, last_of_run & valid).
    """
    big = jnp.int32(2**31 - 1)
    ids_s = jnp.sort(jnp.where(valid, ids, big))
    nxt = jnp.concatenate([ids_s[1:], jnp.full((1,), -2, jnp.int32)])
    last = (ids_s != nxt) & (ids_s != big)
    return ids_s, last


# ---------------------------------------------------------------------------
# TEXT-FIRST (paper §IV.A)
# ---------------------------------------------------------------------------

@register_algorithm("text_first")
def text_first(
    text: tidx.TextIndex,
    spatial: sidx.SpatialIndex,
    pagerank: jax.Array,
    query: QueryBatch,
    budgets: QueryBudgets,
    weights: ranking.RankWeights = ranking.RankWeights(),
    geo_scorer=_default_doc_scorer,
    fused: bool = False,  # Pallas fused probe+score+select (kernels/text_probe)
) -> TopKResult:
    """TEXT-FIRST: drive the intersection with the shortest posting list,
    probe the other terms, fetch footprints for the survivors.

    ``budgets.prune`` switches the driver traversal to the block-max
    pruned probe → score → select pipeline (the text-side twin of the
    pruned K-SWEEP): each 128-posting driver block's upper bound
    ``w_text · blk_max_impact + rest_ub`` (rest = other terms' max
    impacts + geo + pagerank bounds) is tested against a running partial
    top-``max_candidates`` threshold θ, and blocks that cannot beat it
    are skipped before their bytes stream.  ``fused=True`` runs it as one
    Pallas kernel (``kernels/text_probe``) with per-block DMA elision;
    otherwise the bit-matching pure-jnp oracle is used.  The unpruned
    path is kept bit-identical as the correctness reference, with
    ``bytes_postings`` counting only the blocks actually streamed and
    ``text_blocks_skipped`` / ``text_blocks_total`` / ``probes_saved``
    reporting the pruning yield.
    """
    if budgets.prune:
        return _text_first_pruned(
            text, spatial, pagerank, query, budgets, weights, geo_scorer, fused
        )
    R = spatial.doc_rects.shape[1]

    def one(terms, q_rects, q_amps):
        cand, valid, tscore = tidx.conjunction_candidates(
            text, terms, budgets.max_candidates
        )
        g = _geo_score_docs(spatial, cand, valid, q_rects, q_amps, geo_scorer)
        qm = fp.query_mass(q_rects, q_amps)
        score = ranking.combine_scores(
            weights, tscore, g, pagerank[jnp.where(valid, cand, 0)], qm
        )
        score = jnp.where(valid, score, -jnp.inf)
        ids, vals = ranking.top_k(score, cand, budgets.top_k)
        n_c = jnp.sum(valid.astype(jnp.int32))
        n_terms_real = jnp.sum((terms >= 0).astype(jnp.int32))
        # disk/HBM access model: candidate footprints live in the docID-
        # sorted file; nearby candidates coalesce into one run, gaps seek
        # (paper SIV.A "reasonable disk access policy").
        cand_sorted = jnp.sort(jnp.where(valid, cand, jnp.int32(2**31 - 1)))
        gap = cand_sorted[1:] - cand_sorted[:-1]
        new_run = (gap > 64) & (cand_sorted[1:] != jnp.int32(2**31 - 1))
        fetch_runs = jnp.sum(new_run.astype(jnp.int32)) + (n_c > 0).astype(jnp.int32)
        # stored (possibly compressed) record sizes — static per index
        pb = text.posting_bytes
        db = spatial.doc_bytes
        stats = {
            "candidates": n_c,
            # footprints fetched for every textual candidate (doc-major file)
            "bytes_spatial": n_c * jnp.float32(R * db),
            "bytes_postings": n_c * jnp.float32(pb)
            + jnp.float32(budgets.max_candidates * pb),
            "fetch_runs": fetch_runs,
            "seeks": fetch_runs + n_terms_real,  # + one seek per posting list
            "n_probes": n_c * jnp.maximum(n_terms_real - 1, 0),
            # unpruned baseline: the full max_candidates driver window
            # streams, nothing is skipped (pruned-path counterparts)
            "text_blocks_total": jnp.full(
                (),
                -(-budgets.max_candidates // tidx.POSTING_BLOCK),
                jnp.int32,
            ),
            "text_blocks_skipped": jnp.int32(0),
            "probes_saved": jnp.int32(0),
            "bytes_seq": jnp.full((), budgets.max_candidates * pb, jnp.float32),
            "bytes_random": n_c * jnp.float32(R * db)
            + n_c * jnp.maximum(n_terms_real - 1, 0) * 32,
        }
        return ids, vals, stats

    ids, vals, stats = jax.vmap(one)(query.terms, query.rects, query.amps)
    return TopKResult(ids, vals, stats)


def _text_first_pruned(
    text: tidx.TextIndex,
    spatial: sidx.SpatialIndex,
    pagerank: jax.Array,
    query: QueryBatch,
    budgets: QueryBudgets,
    weights: ranking.RankWeights,
    geo_scorer,
    fused: bool,
) -> TopKResult:
    """Block-max pruned TEXT-FIRST (see ``text_first``'s docstring).

    Walks the *whole* driver posting list in 128-posting blocks (not just
    the first ``max_candidates`` postings), skipping blocks whose
    optimistic bound cannot beat the running top-C threshold, then selects
    the top-``max_candidates`` streamed postings by optimistic score —
    so hot-term queries both move fewer bytes and keep better candidates
    than the unpruned head-of-list truncation.
    """
    from repro.kernels.text_probe.ops import window_size

    if fused:
        from repro.kernels.text_probe.ops import text_probe_pruned as _pr
    else:
        from repro.kernels.text_probe.ref import text_probe_pruned_ref as _pr

    R = spatial.doc_rects.shape[1]
    NB = text.blk_pos.shape[0]
    P = text.n_postings
    mtb = text.max_term_blocks
    n_win = window_size(mtb)
    Cs = min(budgets.max_candidates, n_win * tidx.POSTING_BLOCK)
    plane_rows_sel = (
        Cs * (1 + query.terms.shape[1] * _probe_plane_rows(text))
        if text.is_compressed
        else 0
    )
    # query-independent inputs, hoisted out of the per-query vmap: the
    # geo/pagerank remainder bounds (the block-major impact plane is
    # built with the index).
    # geo: combine_scores adds w_geo·g/max(qm, ε) with g ≤ qm·Σ_r amp_r
    # (area(r ∩ q_s) ≤ area(q_s)), so the normalized term is ≤ w_geo·Σ amps.
    plane = text.imp_plane
    with jax.named_scope("text_first.bounds"):
        amp_sum_max = jnp.max(
            jnp.sum(spatial.doc_amps.astype(jnp.float32), axis=-1), initial=0.0
        )
        const_ub = weights.w_geo * amp_sum_max + weights.w_pr * jnp.max(
            pagerank.astype(jnp.float32), initial=0.0
        )
    w_text = jnp.float32(weights.w_text)

    def one(terms, q_rects, q_amps):
        with jax.named_scope("text_first.bounds"):
            d = terms.shape[0]
            safe_terms = jnp.maximum(terms, 0)
            tlens = text.offsets[safe_terms + 1] - text.offsets[safe_terms]
            tlens = jnp.where(terms >= 0, tlens, jnp.int32(2**31 - 1))
            driver = jnp.argmin(tlens).astype(jnp.int32)
            t0 = safe_terms[driver]
            any_real = terms[0] >= 0
            # per-term max impact from the block metadata: bounds what the
            # non-driver terms can add to any candidate's text score
            tb0 = text.blk_term_off[safe_terms]
            tnb = text.blk_term_off[safe_terms + 1] - tb0
            wi = jnp.arange(n_win, dtype=jnp.int32)
            bidx = jnp.clip(tb0[:, None] + wi[None, :], 0, NB - 1)
            tmax = jnp.max(
                jnp.where(
                    wi[None, :] < tnb[:, None], text.blk_max_impact[bidx], 0.0
                ),
                axis=1,
            )
            others = (terms >= 0) & (jnp.arange(d, dtype=jnp.int32) != driver)
            rest_ub = w_text * jnp.sum(jnp.where(others, tmax, 0.0)) + const_ub
            b0 = text.blk_term_off[t0]
            nb = jnp.where(any_real, text.blk_term_off[t0 + 1] - b0, 0)
            # select floor: prune_eps × the best possible optimistic score —
            # candidates below it are dropped by the select stage, so the θ
            # buffer may be seeded with it (skipping provably unselectable
            # blocks even before C candidates have streamed)
            floor = jnp.maximum(
                jnp.float32(budgets.prune_eps) * (w_text * tmax[driver] + rest_ub),
                0.0,
            )
        with jax.named_scope("text_first.walk"):
            opt, valid, streamed, blocks_scored, blocks_active = _pr(
                plane,
                text.blk_max_impact,
                text.blk_len,
                b0,
                nb,
                w_text,
                rest_ub,
                floor,
                max_candidates=budgets.max_candidates,
                max_term_blocks=mtb,
                # impact layout: blk_max_impact is a per-term suffix-max
                # envelope (monotone non-increasing), so the traversal may
                # early-exit the driver at its first failing bound
                monotone=text.layout == "impact",
            )
        with jax.named_scope("text_first.select"):
            # select: partial top-C cut by optimistic score over the streamed
            # survivors (the pruned twin of the unpruned head-of-list cap)
            kept = valid & streamed
            val, sel = jax.lax.top_k(jnp.where(kept, opt, -1.0), Cs)
            ok_c = kept[sel] & (val > floor)
            # translate selected lattice positions → doc ids + driver impacts;
            # only the selected candidates' blocks are decoded
            w_sel = sel // tidx.POSTING_BLOCK
            lane = sel % tidx.POSTING_BLOCK
            gb = jnp.clip(b0 + w_sel, 0, NB - 1)
            apos = jnp.clip(text.blk_pos[gb] + lane, 0, max(P - 1, 0))
            if text.is_compressed:
                cand = text.doc_plane[gb, lane]
            else:
                cand = text.postings[apos]
            cand = jnp.where(ok_c, cand, jnp.int32(2**31 - 1))
            imp_d = jnp.where(ok_c, text.impacts[apos].astype(jnp.float32), 0.0)
        with jax.named_scope("text_first.probe"):
            def probe_one(i, carry):
                valid_c, score = carry
                t = terms[i]
                is_real = (t >= 0) & (i != driver)
                member, imp = tidx.probe_term(text, jnp.maximum(t, 0), cand)
                valid_c = valid_c & (member | ~is_real)
                score = score + jnp.where(is_real, imp, 0.0)
                return valid_c, score

            valid_c, tscore = jax.lax.fori_loop(0, d, probe_one, (ok_c, imp_d))
        with jax.named_scope("text_first.rank"):
            cand = jnp.where(valid_c, cand, jnp.int32(2**31 - 1))
            tscore = jnp.where(valid_c, tscore, 0.0)
            g = _geo_score_docs(spatial, cand, valid_c, q_rects, q_amps, geo_scorer)
            qm = fp.query_mass(q_rects, q_amps)
            score = ranking.combine_scores(
                weights, tscore, g, pagerank[jnp.where(valid_c, cand, 0)], qm
            )
            score = jnp.where(valid_c, score, -jnp.inf)
            ids, vals = ranking.top_k(score, cand, budgets.top_k)
            n_sel = jnp.sum(ok_c.astype(jnp.int32))  # candidates probed
            n_c = jnp.sum(valid_c.astype(jnp.int32))  # intersection survivors
            streamed_valid = jnp.sum((valid & streamed).astype(jnp.int32))
            n_terms_real = jnp.sum((terms >= 0).astype(jnp.int32))
            probes_per = jnp.maximum(n_terms_real - 1, 0)
            cand_sorted = jnp.sort(jnp.where(valid_c, cand, jnp.int32(2**31 - 1)))
            gap = cand_sorted[1:] - cand_sorted[:-1]
            new_run = (gap > 64) & (cand_sorted[1:] != jnp.int32(2**31 - 1))
            fetch_runs = jnp.sum(new_run.astype(jnp.int32)) + (n_c > 0).astype(
                jnp.int32
            )
            # stored (possibly compressed) record sizes — static per index
            pb = text.posting_bytes
            db = spatial.doc_bytes
            # the probe streams the scored driver blocks' f32 impact-plane rows
            # (skipped blocks move zero bytes); the lanes past a short block's
            # length ride along in its row copy but are not charged
            streamed_bytes = streamed_valid * jnp.float32(tidx.PLANE_BYTES)
            stats = {
                "candidates": n_c,
                "bytes_spatial": n_c * jnp.float32(R * db),
                # the streamed plane rows, plus the selected candidates'
                # random reads of the stored postings
                "bytes_postings": streamed_bytes + n_sel * jnp.float32(pb),
                "fetch_runs": fetch_runs,
                "seeks": fetch_runs + n_terms_real,
                "n_probes": n_c * probes_per,
                "text_blocks_total": blocks_active,
                "text_blocks_skipped": blocks_active - blocks_scored,
                # probes avoided by the select stage vs. probing every
                # streamed driver posting
                "probes_saved": jnp.maximum(streamed_valid - n_sel, 0)
                * probes_per,
                "bytes_seq": streamed_bytes,
                "bytes_random": n_c * jnp.float32(R * db)
                + n_c * probes_per * 32
                + n_sel * jnp.float32(pb),
                # doc_plane rows read: the select's, then one a candidate,
                # term slot and segment searched
                "plane_rows": jnp.int32(plane_rows_sel),
            }
            return ids, vals, stats

    ids, vals, stats = jax.vmap(one)(query.terms, query.rects, query.amps)
    return TopKResult(ids, vals, stats)


# ---------------------------------------------------------------------------
# GEO-FIRST (paper §IV.B)
# ---------------------------------------------------------------------------

@register_algorithm("geo_first")
def geo_first(
    text: tidx.TextIndex,
    spatial: sidx.SpatialIndex,
    pagerank: jax.Array,
    query: QueryBatch,
    budgets: QueryBudgets,
    weights: ranking.RankWeights = ranking.RankWeights(),
    geo_scorer=_default_doc_scorer,
) -> TopKResult:
    R = spatial.doc_rects.shape[1]

    def one(terms, q_rects, q_amps):
        tp_ids, ok = sidx.tile_candidate_toeprints(
            spatial, q_rects, budgets.max_tiles, budgets.max_candidates
        )
        # translate toe prints → doc ids (random access into the id column of
        # the toe-print store; the MBR table of the "R*-tree" is memory
        # resident so we charge only the id translation)
        docs = jnp.where(
            ok, spatial.tp_doc_ids[tp_ids].astype(jnp.int32), jnp.int32(2**31 - 1)
        )
        # dedupe docs (multiple toe prints per doc)
        docs_s, last = _sorted_dedupe(docs, ok)
        dvalid = last
        docs_u = jnp.where(dvalid, docs_s, 0)
        # text filter via binary probes
        match, tscore = tidx.text_score_of_docs(text, terms, docs_u)
        keep = dvalid & match
        # fetch footprints for survivors only (doc-major file)
        g = _geo_score_docs(spatial, docs_u, keep, q_rects, q_amps, geo_scorer)
        qm = fp.query_mass(q_rects, q_amps)
        score = ranking.combine_scores(
            weights, tscore, g, pagerank[jnp.where(keep, docs_u, 0)], qm
        )
        score = jnp.where(keep, score, -jnp.inf)
        ids, vals = ranking.top_k(score, docs_u, budgets.top_k)
        n_cand = jnp.sum(ok.astype(jnp.int32))
        n_uniq = jnp.sum(dvalid.astype(jnp.int32))
        n_keep = jnp.sum(keep.astype(jnp.int32))
        n_terms_real = jnp.sum((terms >= 0).astype(jnp.int32))
        # stored (possibly compressed) record sizes — static per index
        pb = text.posting_bytes
        db = spatial.doc_bytes
        idb = spatial.tp_doc_ids.dtype.itemsize
        stats = {
            "candidates": n_cand,
            "bytes_spatial": n_cand * jnp.float32(idb)  # id translation
            + n_keep * jnp.float32(R * db),  # survivor footprints
            "bytes_postings": n_uniq
            * jnp.ceil(jnp.log2(jnp.maximum(text.n_postings, 2)))
            * jnp.float32(pb),
            # every candidate toe print is fetched INDIVIDUALLY (R*-tree
            # random access), every surviving footprint likewise
            "seeks": n_cand + n_keep,
            "n_probes": n_uniq * n_terms_real,
            "bytes_seq": jnp.float32(0),
            "bytes_random": n_cand * jnp.float32(idb)
            + n_keep * jnp.float32(R * db)
            + n_uniq * n_terms_real * 32,
        }
        return ids, vals, stats

    ids, vals, stats = jax.vmap(one)(query.terms, query.rects, query.amps)
    return TopKResult(ids, vals, stats)


# ---------------------------------------------------------------------------
# K-SWEEP (paper §IV.C — the main algorithm)
# ---------------------------------------------------------------------------

@register_algorithm("k_sweep")
def k_sweep(
    text: tidx.TextIndex,
    spatial: sidx.SpatialIndex,
    pagerank: jax.Array,
    query: QueryBatch,
    budgets: QueryBudgets,
    weights: ranking.RankWeights = ranking.RankWeights(),
    tp_scorer=None,
    fused: bool = False,  # Pallas fused fetch+score (kernels/sweep_score)
) -> TopKResult:
    """K-SWEEP: (1) tile intervals → (2) ≤k sweeps → (3) bulk fetch →
    (4) docID translation + sort → (5) text filter → (6) geo scores → top-k.

    ``tp_scorer(rects [T,4], amps [T], q_rects [Q,4], q_amps [Q]) -> [T]``
    computes per-toe-print partial geo scores; defaults to the pure-jnp
    reference, swappable for the Pallas kernel (kernels/geo_score).

    ``budgets.prune`` switches stage (3+6a) to the block-max pruned
    sweep → score → select pipeline: per-block upper bounds from the
    ``SpatialIndex`` blk_* columns are tested against a running partial
    top-``max_candidates`` threshold θ and whole blocks that cannot beat it
    are skipped before scoring — only the surviving candidates reach the
    sort, the inverted-index probes, and the text filter.  ``fused=True``
    runs it as one Pallas kernel (``kernels/sweep_score``); otherwise the
    bit-matching pure-jnp oracle is used (``tp_scorer`` is ignored on the
    pruned path — the scorer is baked into the select pipeline).  The
    unpruned path is kept bit-identical as the correctness reference.

    Stats report streamed vs. scored traffic separately: ``bytes_spatial``
    counts bytes actually streamed from the store (whole sweeps, or only
    unskipped blocks when pruning), ``bytes_scored`` the toe prints that
    survive to candidate aggregation, plus ``blocks_skipped`` /
    ``blocks_total`` (metadata-block units) and ``probes_saved`` (index
    probes avoided vs. probing every fetched candidate).
    """
    if tp_scorer is None:
        tp_scorer = _default_tp_scorer

    def one(terms, q_rects, q_amps):
        # (1) intervals of all intersecting tiles
        starts, ends = sidx.gather_query_intervals(spatial, q_rects, budgets.max_tiles)
        # (2) coalesce into ≤ k sweeps, re-chunked to the fetch budget
        s_starts, s_ends = sidx.coalesce_k_sweeps(starts, ends, budgets.k_sweeps)
        s_starts, s_ends = sidx.split_sweeps_to_budget(
            s_starts, s_ends, budgets.k_sweeps, budgets.sweep_budget
        )
        n_sweeps = jnp.sum((s_starts != INVALID).astype(jnp.int32))
        total = budgets.k_sweeps * budgets.sweep_budget
        Cmax = min(budgets.max_candidates, total)
        bs = spatial.block_size
        if budgets.prune:
            # (3+6a+5a) PRUNED: block-max upper-bound test + adaptive θ
            # feedback skip whole blocks before they are scored; the fused
            # variant runs in-kernel (kernels/sweep_score), the other one
            # through the bit-matching jnp oracle.  The θ buffer is seeded
            # with the select stage's own score floor, so a skipped block
            # provably holds no candidate the selection would keep.
            if fused:
                from repro.kernels.sweep_score.ops import sweep_score_pruned as _pr

                # the kernel reads the index's resident 32-bit planes
                store = dict(planes=spatial.tp_planes)
            else:
                from repro.kernels.sweep_score.ref import (
                    sweep_score_pruned_ref as _pr,
                )

                has_scale = spatial.tp_amp_scale.shape[0] > 0
                store = dict(tp_amp_scale=spatial.tp_amp_scale if has_scale else None)
            floor = jnp.maximum(
                jnp.float32(budgets.prune_eps) * fp.query_mass(q_rects, q_amps), 0.0
            )
            part2d, ok2d, st2d, blocks_scored, blocks_active = _pr(
                spatial.tp_rects,
                spatial.tp_amps,
                spatial.blk_mbr,
                spatial.blk_max_amp,
                spatial.blk_max_mass,
                s_starts,
                s_ends,
                q_rects,
                q_amps,
                budgets.sweep_budget,
                budgets.max_candidates,
                bs,
                floor,
                **store,
            )
            part = part2d.reshape(-1)
            ok = ok2d.reshape(-1)
            kept = ok & st2d.reshape(-1)
            docs = sidx.fetch_sweep_ids(spatial, s_starts, s_ends, budgets.sweep_budget)
            # select: partial top-C cut over the pruned survivors, plus the
            # relative floor prune_eps × query_mass (a candidate below it
            # contributes < prune_eps to the normalized geo score)
            val, sel = jax.lax.top_k(jnp.where(kept, part, -1.0), Cmax)
            docs_c = docs[sel]
            ok_c = kept[sel] & (val > floor)
            streamed_tp = jnp.sum(st2d.astype(jnp.int32))
            blocks_total = blocks_active
            blocks_skipped = blocks_active - blocks_scored
        else:
            if fused:
                # (3+6a) FUSED: the Pallas kernel streams each sweep through
                # VMEM and scores it in-register (kernels/sweep_score); only
                # the i32 doc-id column is fetched separately.
                from repro.kernels.sweep_score.ops import sweep_score as _fused

                part2d, ok2d = _fused(
                    spatial.tp_rects,
                    spatial.tp_amps,
                    s_starts,
                    s_ends,
                    q_rects,
                    q_amps,
                    budgets.sweep_budget,
                    tp_amp_scale=(
                        spatial.tp_amp_scale
                        if spatial.tp_amp_scale.shape[0]
                        else None
                    ),
                )
                part = part2d.reshape(-1)
                ok = ok2d.reshape(-1)
                docs = sidx.fetch_sweep_ids(
                    spatial, s_starts, s_ends, budgets.sweep_budget
                )
            else:
                # (3) bulk contiguous fetch (k dynamic-slice streams)
                rects, amps, docs, ok = sidx.fetch_sweeps(
                    spatial, s_starts, s_ends, budgets.sweep_budget
                )
                # (6a) per-toe-print partial geo scores (the FLOP hot spot)
                part = tp_scorer(rects, jnp.where(ok, amps, 0.0), q_rects, q_amps)
            # (5a) geo-score early termination (paper §Conclusions future
            # work): keep only the strongest max_candidates toe prints
            # before the expensive sort + inverted-index probing.  Lossy,
            # and the full stream + score cost has already been paid —
            # the pruned path above avoids it up front.
            if budgets.early_termination and Cmax < total:
                val, sel = jax.lax.top_k(jnp.where(ok, part, -1.0), Cmax)
                docs_c = docs[sel]
                ok_c = ok[sel] & (val > 0)
            else:
                docs_c, ok_c = docs, ok
            streamed_tp = n_sweeps * budgets.sweep_budget
            blocks_total = n_sweeps * ((budgets.sweep_budget + bs - 1) // bs)
            blocks_skipped = jnp.int32(0)
        # (4) translate to docIDs, sort, dedupe per doc (the partial scores
        # drove selection; they are not the final geo score)
        docs_s, last = _sorted_dedupe(docs_c, ok_c)
        dvalid = last
        docs_u = jnp.where(dvalid, docs_s, 0)
        # (5) filter through the inverted index.  Under pruning the
        # counted variant reports the probes a short-circuiting evaluator
        # issues (earlier terms' misses spare later terms' probes) —
        # same match/score math, outputs bit-identical.
        if budgets.prune:
            match, tscore, text_probes = tidx.text_score_of_docs_counted(
                text, terms, docs_u, dvalid
            )
        else:
            match, tscore = tidx.text_score_of_docs(text, terms, docs_u)
            text_probes = None
        keep = dvalid & match
        # (6) final geo score from each survivor's own footprint slots —
        # the same doc-major scorer as geo_first/oracle, summed in the
        # doc's canonical slot order.  Scoring from doc_rects rows (not
        # the sweep stream's run sums) keeps per-doc scores bit-identical
        # across shard layouts: the stream order, coalescing slack, and
        # cumsum prefix all depend on the partitioning, a doc's own rect
        # row does not (the footprint-routing equivalence gate).
        g_tot = _geo_score_docs(
            spatial, docs_u, keep, q_rects, q_amps, _default_doc_scorer
        )
        qm = fp.query_mass(q_rects, q_amps)
        score = ranking.combine_scores(
            weights, tscore, g_tot, pagerank[jnp.where(keep, docs_u, 0)], qm
        )
        score = jnp.where(keep, score, -jnp.inf)
        ids, vals = ranking.top_k(score, docs_u, budgets.top_k)
        fetched = jnp.sum(ok.astype(jnp.int32))
        n_selected = jnp.sum(ok_c.astype(jnp.int32))
        n_uniq = jnp.sum(dvalid.astype(jnp.int32))
        n_terms_real = jnp.sum((terms >= 0).astype(jnp.int32))
        if budgets.prune or budgets.early_termination:
            # probes the select stage avoided vs. probing every fetched doc
            probes_saved = (_count_unique(docs, ok) - n_uniq) * n_terms_real
        else:
            probes_saved = jnp.int32(0)
        # stored (possibly compressed) record sizes — static per index;
        # the pruned sweep streams the 32-bit tp_planes instead
        tpb = spatial.tp_bytes
        stream_b = spatial.pruned_tp_bytes if budgets.prune else tpb
        pb = text.posting_bytes
        stats = {
            "candidates": fetched,
            "sweeps": n_sweeps,
            # bytes actually streamed: ≤k contiguous streams, minus any
            # block-max-skipped blocks on the pruned path
            "bytes_spatial": streamed_tp * jnp.float32(stream_b),
            "sweep_slack": n_sweeps * budgets.sweep_budget - fetched,
            # toe prints surviving to candidate aggregation (≠ streamed
            # when early termination or pruning drops candidates)
            "bytes_scored": n_selected * jnp.float32(tpb),
            "blocks_total": blocks_total,
            "blocks_skipped": blocks_skipped,
            "probes_saved": probes_saved,
            "bytes_postings": n_uniq
            * jnp.ceil(jnp.log2(jnp.maximum(text.n_postings, 2)))
            * jnp.float32(pb),
            "seeks": n_sweeps + n_terms_real,
            # honest short-circuit count when the pruned text filter ran
            "n_probes": (
                text_probes if text_probes is not None else n_uniq * n_terms_real
            ),
            "bytes_seq": streamed_tp * jnp.float32(stream_b),
            "bytes_random": n_uniq * n_terms_real * 32,
        }
        return ids, vals, stats

    ids, vals, stats = jax.vmap(one)(query.terms, query.rects, query.amps)
    return TopKResult(ids, vals, stats)


# ---------------------------------------------------------------------------
# SCAN — exhaustive exact evaluation (the planner's fallback under `exact`)
# ---------------------------------------------------------------------------

# a query term with a bitmap (``TextIndex.bitmaps``) is masked by it; a
# term whose posting list fits SCAN_MASK_BLOCKS blocks is scattered into an
# exact membership mask and exact per-document impacts.  Index builds for
# exact budgets give every term with SCAN_BITMAP_MIN_DF postings a bitmap.
SCAN_MASK_BLOCKS = 512
SCAN_BITMAP_MIN_DF = SCAN_MASK_BLOCKS * tidx.POSTING_BLOCK // 2


@register_algorithm("scan")
def scan(
    text: tidx.TextIndex,
    spatial: sidx.SpatialIndex,
    pagerank: jax.Array,
    query: QueryBatch,
    budgets: QueryBudgets,
    weights: ranking.RankWeights = ranking.RankWeights(),
) -> TopKResult:
    """Exact top-k for queries no budgeted plan covers.

    Every document's geo score is computed from the doc-major footprint
    mirror (a dense pass).  Each query term masks the documents: by its
    bitmap where the index has one, else, where its posting list fits
    ``SCAN_MASK_BLOCKS`` blocks, by scattering the list (which also gives
    its exact impacts).  Each surviving document gets the optimistic
    score ``combine_scores(Σ_t u_t, g, pr)``, with ``u_t`` the scattered
    impact or the term's max impact; documents are visited in descending
    optimistic order, ``max_candidates`` per round, and each round probes
    every query term for its candidates and merges their exact scores into
    a running top-k.  The loop stops once the next optimistic score is
    below the k-th exact score, so the answer is the oracle's.  The exact
    text score of a document never exceeds its optimistic one: both sum
    per-term values in term order from 0, termwise ``impact ≤ u_t``, and
    f32 rounding is monotone.
    """
    N = spatial.n_docs
    C = max(min(budgets.max_candidates, N), 1)
    k = budgets.top_k
    n_rounds = -(-N // C)
    R = spatial.doc_rects.shape[1]
    NB = text.blk_pos.shape[0]
    P = text.n_postings
    n_win = max(text.max_term_blocks, 1)
    mask_blocks = min(SCAN_MASK_BLOCKS, n_win)
    n_bm = text.bitmap_term_ids.shape[0]
    pr = pagerank.astype(jnp.float32)
    lane = jnp.arange(tidx.POSTING_BLOCK, dtype=jnp.int32)
    bit = jnp.arange(32, dtype=jnp.uint32)
    pb = text.posting_bytes
    db = spatial.doc_bytes
    logp = jnp.ceil(jnp.log2(jnp.maximum(jnp.float32(P), 2.0)))

    def scatter_term(t, b0, nb):
        """The term's first ``mask_blocks`` blocks as a dense (membership
        bool[N], impact f32[N]) pair."""
        i = jnp.arange(mask_blocks, dtype=jnp.int32)
        blocks = jnp.clip(b0 + i, 0, NB - 1)
        live = (i < nb)[:, None] & (lane[None, :] < text.blk_len[blocks][:, None])
        pos = jnp.clip(text.blk_pos[blocks][:, None] + lane[None, :], 0, P - 1)
        if text.is_compressed:
            docs = tidx.decode_posting_blocks(text, blocks)
        else:
            docs = text.postings[pos]
        slot = jnp.where(live, docs, N).reshape(-1)  # N: dropped
        hit = jnp.zeros((N,), bool).at[slot].set(True, mode="drop")
        imp = jnp.zeros((N,), jnp.float32).at[slot].set(
            text.impacts[pos].astype(jnp.float32).reshape(-1), mode="drop"
        )
        return hit, imp

    def bitmap_term(t):
        """(has a bitmap, membership bool[N]) from the term's bitmap row."""
        if n_bm == 0:
            return jnp.bool_(False), jnp.ones((N,), bool)
        rows = text.bitmap_term_ids == t
        words = text.bitmaps[jnp.argmax(rows)]
        bits = ((words[:, None] >> bit[None, :]) & 1).astype(bool)
        return jnp.any(rows), bits.reshape(-1)[:N]

    def one(terms, q_rects, q_amps):
        with jax.named_scope("scan.geo"):
            g = fp.geo_score(spatial.doc_rects, spatial.doc_amps, q_rects, q_amps)
            qm = fp.query_mass(q_rects, q_amps)
        with jax.named_scope("scan.mask"):
            d = terms.shape[0]
            real = terms >= 0
            safe_terms = jnp.maximum(terms, 0)
            # per-term max impact from the block metadata
            tb0 = text.blk_term_off[safe_terms]
            tnb = text.blk_term_off[safe_terms + 1] - tb0
            wi = jnp.arange(n_win, dtype=jnp.int32)
            bidx = jnp.clip(tb0[:, None] + wi[None, :], 0, NB - 1)
            tmax = jnp.max(
                jnp.where(wi[None, :] < tnb[:, None], text.blk_max_impact[bidx], 0.0),
                axis=1,
            )
            t_ub = jnp.zeros((N,), jnp.float32)
            mask = jnp.ones((N,), bool)
            scattered = jnp.int32(0)  # blocks scattered into masks
            for i in range(d):  # term order, as text_score_of_docs sums
                has_bm, bm = bitmap_term(safe_terms[i])
                hit, imp = scatter_term(safe_terms[i], tb0[i], tnb[i])
                short = ~has_bm & (tnb[i] <= mask_blocks)
                member = jnp.where(has_bm, bm, hit | ~short)
                t_ub = t_ub + jnp.where(real[i], jnp.where(short, imp, tmax[i]), 0.0)
                mask = mask & (member | ~real[i])
                scattered = scattered + jnp.where(real[i] & short, tnb[i], 0)
        with jax.named_scope("scan.order"):
            ub = ranking.combine_scores(weights, t_ub, g, pr, qm)
            ub = jnp.where(mask, ub, -jnp.inf)
            order = jnp.argsort(-ub, stable=True).astype(jnp.int32)
            ub_s = ub[order]
        with jax.named_scope("scan.probe"):
            def next_ub(r):
                return jnp.where(r * C < N, ub_s[jnp.minimum(r * C, N - 1)], -jnp.inf)

            def cond(carry):
                r, _, best, _ = carry
                nxt = next_ub(r)
                return (r < n_rounds) & (nxt > -jnp.inf) & (nxt >= best[k - 1])

            def body(carry):
                r, best_ids, best, probed = carry
                pos = r * C + jnp.arange(C, dtype=jnp.int32)
                ok = (pos < N) & (ub_s[jnp.minimum(pos, N - 1)] > -jnp.inf)
                cand = jnp.where(ok, order[jnp.minimum(pos, N - 1)], 0)
                match, tscore = tidx.text_score_of_docs(text, terms, cand)
                score = ranking.combine_scores(weights, tscore, g[cand], pr[cand], qm)
                score = jnp.where(ok & match, score, -jnp.inf)
                ids, vals = ranking.top_k(
                    jnp.concatenate([best, score]),
                    jnp.concatenate([best_ids, cand]),
                    k,
                )
                probed = probed + jnp.sum(ok.astype(jnp.int32))
                return r + 1, ids, vals, probed

            init = (
                jnp.int32(0),
                jnp.full((k,), -1, jnp.int32),
                jnp.full((k,), -jnp.inf, jnp.float32),
                jnp.int32(0),
            )
            rounds, ids, vals, probed = jax.lax.while_loop(cond, body, init)
            n_real = jnp.sum(real.astype(jnp.int32))
            probes = probed * n_real
            mask_bytes = scattered * jnp.float32(tidx.POSTING_BLOCK * pb)
            stats = {
                "candidates": probed,
                "scan_rounds": rounds,
                # the dense footprint pass reads every doc-major footprint
                "bytes_spatial": jnp.float32(N * R * db),
                # masks stream their terms' blocks; probes binary-search
                "bytes_postings": mask_bytes + probes * logp * jnp.float32(pb),
                "seeks": n_real + rounds,
                "n_probes": probes,
                "bytes_seq": jnp.float32(N * R * db) + mask_bytes,
                "bytes_random": probes * 32.0,
                # doc_plane rows read: every term slot's mask scatter,
                # then each round's probes of every term slot
                "plane_rows": (
                    d * mask_blocks + rounds * (d * C * _probe_plane_rows(text))
                    if text.is_compressed
                    else jnp.int32(0)
                ),
            }
            return ids, vals, stats

    ids, vals, stats = jax.vmap(one)(query.terms, query.rects, query.amps)
    return TopKResult(ids, vals, stats)


def _default_tp_scorer(rects, amps, q_rects, q_amps):
    """Pure-jnp per-toe-print scorer: Σ_q area(tp ∩ q)·amp_tp·amp_q.
    Casts to f32 so it accepts lossy-compressed (f16) toe-print stores."""
    from repro.core import geometry

    inter = geometry.rect_intersection_area(
        rects[:, None, :].astype(jnp.float32), q_rects[None, :, :].astype(jnp.float32)
    )
    return jnp.sum(
        inter * amps[:, None].astype(jnp.float32) * q_amps[None, :].astype(jnp.float32),
        axis=-1,
    )


# ---------------------------------------------------------------------------
# Exact oracle (dense scan) — for recall evaluation in tests/benchmarks
# ---------------------------------------------------------------------------

def oracle(
    text: tidx.TextIndex,
    spatial: sidx.SpatialIndex,
    pagerank: jax.Array,
    query: QueryBatch,
    k: int,
    weights: ranking.RankWeights = ranking.RankWeights(),
) -> TopKResult:
    """Exact top-k by scoring *every* document (no budgets).  O(N) per query.

    Term at a time: each query term's whole posting list is scattered into
    dense per-document hit counts and impact sums, so the text side costs
    the query's postings rather than one binary-search probe (and block
    decode) per document and term.  Impacts are added in term order, as
    :func:`text_index.text_score_of_docs` adds them, so text scores are
    the same bits.
    """
    N = spatial.n_docs
    all_docs = jnp.arange(N, dtype=jnp.int32)

    def one(terms, q_rects, q_amps):
        def add_term(i, carry):
            hits, tscore = carry
            t = terms[i]
            docs, imp, live = tidx.term_postings(text, jnp.maximum(t, 0))
            slot = jnp.where(live & (t >= 0), docs, N).reshape(-1)  # N: dropped
            hits = hits.at[slot].add(1, mode="drop")
            tscore = tscore.at[slot].add(imp.reshape(-1), mode="drop")
            return hits, tscore

        hits, tscore = jax.lax.fori_loop(
            0, terms.shape[0], add_term,
            (jnp.zeros((N,), jnp.int32), jnp.zeros((N,), jnp.float32)),
        )
        match = hits == jnp.sum((terms >= 0).astype(jnp.int32))
        g = fp.geo_score(spatial.doc_rects, spatial.doc_amps, q_rects, q_amps)
        qm = fp.query_mass(q_rects, q_amps)
        score = ranking.combine_scores(weights, tscore, g, pagerank, qm)
        score = jnp.where(match, score, -jnp.inf)
        return ranking.top_k(score, all_docs, k)

    ids, vals = jax.vmap(one)(query.terms, query.rects, query.amps)
    return TopKResult(ids, vals, {})
