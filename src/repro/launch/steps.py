"""Cell builders: (arch × shape × mesh) → (step_fn, input ShapeDtypeStructs).

``build_cell`` returns a ``Cell`` whose ``fn`` is ready for
``jax.jit(fn, ...).lower(*cell.args)``:

* ``lm_train``        train_step(params, opt_state, batch)   [donate 0,1]
* ``lm_prefill``      prefill(params, tokens, cache)
* ``lm_decode``       decode_step(params, cache, tokens, pos) [donate 1]
* ``gnn_*``           train_step(params, opt_state, graph)
* ``recsys_train``    train_step(params, opt_state, batch)
* ``recsys_serve``    forward(params, batch)
* ``recsys_retrieval`` candidate scoring (top-k)
* ``geo_serve``       distributed engine serve step (shard_map)

Every input carries a NamedSharding resolved from the logical axes — the
dry-run's in_shardings ARE the production sharding config.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from repro.configs.base import ArchSpec, ShapeSpec
from repro.models import egnn as egnn_lib
from repro.models import recsys as rec_lib
from repro.models import transformer as tf_lib
from repro.models.params import param_shapes
from repro.sharding.specs import named_sharding
from repro.train.loop import make_train_step
from repro.train.optimizer import OptimizerConfig


@dataclass
class Cell:
    arch: str
    shape: str
    fn: Callable
    args: tuple
    donate: tuple[int, ...] = ()
    # analytic "useful" flops for this step (MODEL_FLOPS of §Roofline), global
    model_flops: float = 0.0
    note: str = ""


def _sds(shape, dtype, mesh, logical):
    if mesh is None:
        return jax.ShapeDtypeStruct(shape, dtype)
    return jax.ShapeDtypeStruct(
        shape, dtype, sharding=named_sharding(mesh, logical, shape=shape)
    )


def _moment_shardings(pshapes, mesh):
    from repro.train.optimizer import zero1_sharding

    if mesh is None:
        return None
    return jax.tree.map(
        lambda s: zero1_sharding(mesh, s.sharding.spec, s.shape), pshapes
    )


def _opt_shapes(pshapes, mesh=None):
    """Optimizer-state ShapeDtypeStructs; moments carry ZeRO-1 shardings."""
    ms = _moment_shardings(pshapes, mesh)
    if ms is None:
        moments = pshapes
    else:
        moments = jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            pshapes, ms,
        )
    return {
        "step": jax.ShapeDtypeStruct((), jnp.int32),
        "m": moments,
        "v": moments,
    }


# ---------------------------------------------------------------------------
# LM cells
# ---------------------------------------------------------------------------

def _lm_flops(cfg, n_tokens: int, kind: str, kv_len: int = 0, batch: int = 1) -> float:
    n_active = cfg.n_active_params()
    if kind == "train":
        return 6.0 * n_active * n_tokens
    if kind == "prefill":
        return 2.0 * n_active * n_tokens
    # decode: one token per sequence + attention over the cache
    attn = 2.0 * 2.0 * batch * cfg.n_heads * cfg.d_head * kv_len
    return 2.0 * n_active * n_tokens + attn * cfg.n_layers


def build_lm_cell(
    spec: ArchSpec, shape: ShapeSpec, mesh, opt_cfg=None, overrides: dict | None = None
) -> Cell:
    cfg = spec.config
    if "attn_window" in shape.params:
        cfg = dataclasses.replace(cfg, attn_window=shape.params["attn_window"])
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    p = shape.params
    pshapes = param_shapes(cfg.param_defs(), mesh)

    if shape.kind == "lm_train":
        B, S = p["global_batch"], p["seq_len"]
        opt_cfg = opt_cfg or OptimizerConfig(zero1=True)
        step = make_train_step(
            lambda prm, b: tf_lib.loss_fn(cfg, prm, b), opt_cfg, jit=False,
            moment_shardings=_moment_shardings(pshapes, mesh),
        )
        batch = {
            "tokens": _sds((B, S), jnp.int32, mesh, ("batch", None)),
            "labels": _sds((B, S), jnp.int32, mesh, ("batch", None)),
        }
        return Cell(
            spec.name, shape.name, step,
            (pshapes, _opt_shapes(pshapes, mesh), batch), donate=(0, 1),
            model_flops=_lm_flops(cfg, B * S, "train"),
        )

    if shape.kind == "lm_prefill":
        B, S = p["global_batch"], p["seq_len"]
        cache = param_shapes(tf_lib.cache_defs(cfg, B, S), mesh)

        def fn(params, tokens, cache):
            return tf_lib.prefill(cfg, params, tokens, cache)

        tokens = _sds((B, S), jnp.int32, mesh, ("batch", None))
        return Cell(
            spec.name, shape.name, fn, (pshapes, tokens, cache), donate=(2,),
            model_flops=_lm_flops(cfg, B * S, "prefill"),
        )

    if shape.kind == "lm_decode":
        B, S = p["global_batch"], p["seq_len"]
        cache = param_shapes(tf_lib.cache_defs(cfg, B, S), mesh)

        def fn(params, cache, tokens, pos):
            return tf_lib.decode_step(cfg, params, cache, tokens, pos)

        tokens = _sds((B,), jnp.int32, mesh, ("batch",))
        pos = jax.ShapeDtypeStruct((), jnp.int32)
        return Cell(
            spec.name, shape.name, fn, (pshapes, cache, tokens, pos), donate=(1,),
            model_flops=_lm_flops(cfg, B, "decode", kv_len=S, batch=B),
        )
    raise ValueError(shape.kind)


# ---------------------------------------------------------------------------
# GNN cells
# ---------------------------------------------------------------------------

def _egnn_flops(cfg, n_edges: int, n_nodes: int, train: bool = True) -> float:
    H = cfg.d_hidden
    per_edge = 2 * ((2 * H + 1) * H + H * H) + 2 * (H * H + H)  # φ_e + φ_x
    per_node = 2 * (2 * H * H + H * H)  # φ_h
    fwd = cfg.n_layers * (per_edge * n_edges + per_node * n_nodes)
    return (3.0 if train else 1.0) * fwd


def build_gnn_cell(spec: ArchSpec, shape: ShapeSpec, mesh, opt_cfg=None) -> Cell:
    p = shape.params
    if shape.kind == "gnn_molecule":
        cfg = dataclasses.replace(spec.config, d_feat=p["d_feat"], n_classes=0)
    else:
        cfg = dataclasses.replace(
            spec.config, d_feat=p["d_feat"], n_classes=p.get("n_classes", 8)
        )
    cfg = dataclasses.replace(cfg, scan_unroll=True)
    pshapes = param_shapes(cfg.param_defs(), mesh)
    opt_cfg = opt_cfg or OptimizerConfig(zero1=True)
    step = make_train_step(
        lambda prm, b: egnn_lib.loss_fn(cfg, prm, b), opt_cfg, jit=False,
        moment_shardings=_moment_shardings(pshapes, mesh),
    )

    from repro.data.graph import pad_edges
    from repro.models.egnn import make_sharded_loss, pad_nodes

    if shape.kind == "gnn_full":
        # full-graph cells use the explicitly-sharded (shard_map) path:
        # node state sharded row-wise, AG + reduce-scatter per layer
        N, E = pad_nodes(p["n_nodes"]), pad_edges(p["n_edges"])
        if mesh is not None:
            step = make_train_step(
                make_sharded_loss(cfg, mesh), opt_cfg, jit=False,
                moment_shardings=_moment_shardings(pshapes, mesh),
            )
        batch = {
            "feats": _sds((N, cfg.d_feat), jnp.float32, mesh, ("nodes", None)),
            "coords": _sds((N, cfg.coord_dim), jnp.float32, mesh, ("nodes", None)),
            "senders": _sds((E,), jnp.int32, mesh, ("edges",)),
            "receivers": _sds((E,), jnp.int32, mesh, ("edges",)),
            "edge_mask": _sds((E,), jnp.bool_, mesh, ("edges",)),
            "labels": _sds((N,), jnp.int32, mesh, ("nodes",)),
        }
        mf = _egnn_flops(cfg, E, N)
    elif shape.kind == "gnn_minibatch":
        from repro.data.graph import SampledShape

        ss = SampledShape(p["batch_nodes"], tuple(p["fanouts"]))
        N, E = ss.max_nodes, pad_edges(ss.max_edges)
        batch = {
            "feats": _sds((N, cfg.d_feat), jnp.float32, mesh, (None, None)),
            "coords": _sds((N, cfg.coord_dim), jnp.float32, mesh, (None, None)),
            "senders": _sds((E,), jnp.int32, mesh, ("edges",)),
            "receivers": _sds((E,), jnp.int32, mesh, ("edges",)),
            "edge_mask": _sds((E,), jnp.bool_, mesh, ("edges",)),
            "labels": _sds((N,), jnp.int32, mesh, (None,)),
        }
        mf = _egnn_flops(cfg, E, N)
    elif shape.kind == "gnn_molecule":
        G, npg, epg = p["batch"], p["n_nodes"], p["n_edges"]
        N, E = G * npg, pad_edges(G * epg)
        batch = {
            "feats": _sds((N, cfg.d_feat), jnp.float32, mesh, (None, None)),
            "coords": _sds((N, 3), jnp.float32, mesh, (None, None)),
            "senders": _sds((E,), jnp.int32, mesh, ("edges",)),
            "receivers": _sds((E,), jnp.int32, mesh, ("edges",)),
            "edge_mask": _sds((E,), jnp.bool_, mesh, ("edges",)),
            "graph_ids": _sds((N,), jnp.int32, mesh, (None,)),
            "targets": _sds((G,), jnp.float32, mesh, (None,)),
        }
        mf = _egnn_flops(cfg, E, N)
    else:
        raise ValueError(shape.kind)
    return Cell(
        spec.name, shape.name, step,
        (pshapes, _opt_shapes(pshapes, mesh), batch), donate=(0, 1), model_flops=mf,
    )


# ---------------------------------------------------------------------------
# RecSys cells
# ---------------------------------------------------------------------------

def _recsys_batch_specs(cfg, B: int, mesh) -> dict:
    name = type(cfg).__name__
    if name == "DCNv2Config":
        return {
            "dense": _sds((B, cfg.n_dense), jnp.float32, mesh, ("batch", None)),
            "sparse": _sds((B, cfg.n_sparse), jnp.int32, mesh, ("batch", None)),
            "label": _sds((B,), jnp.float32, mesh, ("batch",)),
        }
    if name == "AutoIntConfig":
        return {
            "sparse": _sds((B, cfg.n_sparse), jnp.int32, mesh, ("batch", None)),
            "label": _sds((B,), jnp.float32, mesh, ("batch",)),
        }
    if name == "BSTConfig":
        return {
            "history": _sds((B, cfg.seq_len), jnp.int32, mesh, ("batch", None)),
            "target": _sds((B,), jnp.int32, mesh, ("batch",)),
            "other": _sds((B, cfg.n_other_fields), jnp.int32, mesh, ("batch", None)),
            "label": _sds((B,), jnp.float32, mesh, ("batch",)),
        }
    if name == "TwoTowerConfig":
        return {
            "user_id": _sds((B,), jnp.int32, mesh, ("batch",)),
            "user_fields": _sds(
                (B, cfg.n_user_fields), jnp.int32, mesh, ("batch", None)
            ),
            "history": _sds((B, cfg.hist_len), jnp.int32, mesh, ("batch", None)),
            "target": _sds((B,), jnp.int32, mesh, ("batch",)),
            "item_fields": _sds(
                (B, cfg.n_item_fields), jnp.int32, mesh, ("batch", None)
            ),
            "logq": _sds((B,), jnp.float32, mesh, ("batch",)),
        }
    raise ValueError(name)


def _recsys_fns(cfg):
    name = type(cfg).__name__
    if name == "DCNv2Config":
        return partial(rec_lib.dcn_v2_loss, cfg), partial(rec_lib.dcn_v2_forward, cfg)
    if name == "AutoIntConfig":
        return partial(rec_lib.autoint_loss, cfg), partial(rec_lib.autoint_forward, cfg)
    if name == "BSTConfig":
        return partial(rec_lib.bst_loss, cfg), partial(rec_lib.bst_forward, cfg)
    if name == "TwoTowerConfig":
        return partial(rec_lib.two_tower_loss, cfg), None
    raise ValueError(name)


def _recsys_flops(cfg, B: int, train: bool) -> float:
    """Dense-compute FLOPs (embedding lookups are bandwidth, not FLOPs)."""
    name = type(cfg).__name__
    if name == "DCNv2Config":
        d = cfg.d_input
        per = cfg.n_cross_layers * 2 * d * d
        dims = [d, *cfg.mlp_dims]
        per += sum(2 * a * b for a, b in zip(dims[:-1], dims[1:]))
        per += 2 * (d + cfg.mlp_dims[-1])
    elif name == "AutoIntConfig":
        F, D = cfg.n_sparse, cfg.embed_dim
        per, d_in = 0, D
        for _ in range(cfg.n_attn_layers):
            d_out = cfg.n_heads * cfg.d_attn
            per += F * (3 * 2 * d_in * d_out + 2 * d_in * d_out)
            per += 2 * F * F * d_out * 2
            d_in = d_out
        per += 2 * F * d_in
    elif name == "BSTConfig":
        D, S = cfg.embed_dim, cfg.seq_len + 1
        per = cfg.n_blocks * (
            4 * 2 * S * D * D + 2 * 2 * S * S * D + 2 * 2 * S * D * 4 * D
        )
        d_in = S * D + cfg.n_other_fields * D
        dims = [d_in, *cfg.mlp_dims, 1]
        per += sum(2 * a * b for a, b in zip(dims[:-1], dims[1:]))
    elif name == "TwoTowerConfig":
        D = cfg.feat_dim
        u_in = D * (1 + cfg.n_user_fields + 1)
        i_in = D * (1 + cfg.n_item_fields)
        u_per = _tower_flops([u_in, *cfg.tower_dims, cfg.embed_dim])
        i_per = _tower_flops([i_in, *cfg.tower_dims, cfg.embed_dim])
        if train:  # both towers + in-batch [B,B] logits
            return 3.0 * ((u_per + i_per) * B + 2 * cfg.embed_dim * B * B)
        return u_per * B  # serve = user-embedding computation
    else:
        raise ValueError(name)
    return (3.0 if train else 1.0) * per * B


def _tower_flops(dims: list[int]) -> float:
    return sum(2 * a * b for a, b in zip(dims[:-1], dims[1:]))


def _two_tower_retrieval_flops(cfg, B: int, Nc: int) -> float:
    D = cfg.feat_dim
    u_in = D * (1 + cfg.n_user_fields + 1)
    i_in = D * (1 + cfg.n_item_fields)
    return (
        _tower_flops([u_in, *cfg.tower_dims, cfg.embed_dim]) * B
        + _tower_flops([i_in, *cfg.tower_dims, cfg.embed_dim]) * Nc
        + 2.0 * cfg.embed_dim * B * Nc  # scoring dot
    )


def build_recsys_cell(spec: ArchSpec, shape: ShapeSpec, mesh, opt_cfg=None) -> Cell:
    cfg = spec.config
    p = shape.params
    pshapes = param_shapes(cfg.param_defs(), mesh)
    loss, fwd = _recsys_fns(cfg)

    if shape.kind == "recsys_train":
        B = p["batch"]
        opt_cfg = opt_cfg or OptimizerConfig(zero1=True)
        step = make_train_step(
            lambda prm, b: loss(prm, b), opt_cfg, jit=False,
            moment_shardings=_moment_shardings(pshapes, mesh),
        )
        batch = _recsys_batch_specs(cfg, B, mesh)
        return Cell(
            spec.name, shape.name, step,
            (pshapes, _opt_shapes(pshapes, mesh), batch), donate=(0, 1),
            model_flops=_recsys_flops(cfg, B, True),
        )

    if shape.kind == "recsys_serve":
        B = p["batch"]
        if fwd is None:  # two-tower: serve = user-embedding computation
            def fn(prm, batch):
                return rec_lib.two_tower_user(cfg, prm, batch)
        else:
            def fn(prm, batch):
                return fwd(prm, batch)
        batch = _recsys_batch_specs(cfg, B, mesh)
        batch.pop("label", None)
        return Cell(
            spec.name, shape.name, fn, (pshapes, batch),
            model_flops=_recsys_flops(cfg, B, False),
        )

    if shape.kind == "recsys_retrieval":
        Nc = p["n_candidates"]
        B = p["batch"]
        if type(cfg).__name__ == "TwoTowerConfig":
            def fn(prm, batch, cand_ids, cand_fields):
                return rec_lib.two_tower_score_candidates(
                    cfg, prm, batch, cand_ids, cand_fields, top_k=100
                )

            batch = _recsys_batch_specs(cfg, B, mesh)
            batch.pop("label", None)
            cand_ids = _sds((Nc,), jnp.int32, mesh, ("candidates",))
            cand_fields = _sds(
                (Nc, cfg.n_item_fields), jnp.int32, mesh, ("candidates", None)
            )
            return Cell(
                spec.name, shape.name, fn, (pshapes, batch, cand_ids, cand_fields),
                model_flops=_two_tower_retrieval_flops(cfg, B, Nc),
            )
        # CTR models: retrieval scoring = candidate-major forward batch
        batch = _recsys_batch_specs(cfg, Nc, mesh)
        batch.pop("label", None)

        def fn(prm, batch):
            scores = fwd(prm, batch)
            return jax.lax.top_k(scores, 100)

        return Cell(
            spec.name, shape.name, fn, (pshapes, batch),
            model_flops=_recsys_flops(cfg, Nc, False),
            note="candidate-major scoring (1 user context broadcast into rows)",
        )
    raise ValueError(shape.kind)


# ---------------------------------------------------------------------------
# geoweb cells (the paper's system)
# ---------------------------------------------------------------------------

I32_SAFE_MAX = 2**30  # see _check_i32_addressable below


def _check_i32_addressable(name: str, value: int, n_shards: int) -> int:
    """Guard the engine's int32 index arithmetic at production scale.

    Every posting/toe-print position in the query pipeline is int32 (CSR
    offsets, binary-search bounds, sweep starts).  At the paper's full
    scale (2^26 docs × 128 postings = 2^33 global postings) a shard's
    store only stays addressable because the mesh provides enough doc
    shards; with too few shards the offsets' top entries and the search
    positions silently wrap negative.  The bound is 2^30 — not 2^31−1 —
    so intermediate index *sums* (e.g. ``start + budget``, the bisection
    bounds) keep headroom too.  Fails loudly at cell-construction time
    with the minimum shard count instead of lowering a program that
    would return garbage.
    """
    if value > I32_SAFE_MAX:
        need = -(-value * n_shards // I32_SAFE_MAX)
        raise ValueError(
            f"geoweb cell: per-shard {name} = {value:,} exceeds the int32-"
            f"addressable bound 2^30; shard the docs over >= {need} devices "
            f"(mesh provides {n_shards}) or shrink the config"
        )
    return value


def build_geoweb_cell(spec: ArchSpec, shape: ShapeSpec, mesh) -> Cell:
    from repro.core import algorithms as alg
    from repro.core.distributed import COVERAGE_GRID, make_serve_fn, ShardedGeoIndex

    cfg = spec.config
    if mesh is None:
        raise ValueError("geoweb cells need a mesh")
    doc_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    q_axis = "model"
    S = int(np.prod([mesh.shape[a] for a in doc_axes]))
    N = cfg.n_docs // S  # docs per shard
    Tt = _check_i32_addressable(
        "toe prints", N * cfg.max_rects, S
    )  # toe prints per shard
    Pp = _check_i32_addressable("postings", N * cfg.avg_postings_per_doc, S)
    G2 = cfg.grid * cfg.grid
    R = cfg.doc_major_rects
    M = cfg.n_terms

    def sh(shape_, dtype, logical):
        return _sds(shape_, dtype, mesh, logical)

    from repro.core.spatial_index import SCALE_BLOCK, normalize_compress
    from repro.core.text_index import POSTING_BLOCK

    mode = normalize_compress(getattr(cfg, "compress", False))
    ft = jnp.float16 if mode != "none" else jnp.float32
    at = jnp.int8 if mode == "int8" else ft  # amp store dtype
    it = jnp.int16 if (mode != "none" and N <= 2**15 - 1) else jnp.int32
    # compressed posting store widths: one block per POSTING_BLOCK postings,
    # delta width bounded by the per-shard doc-id range
    NBp = max(-(-Pp // POSTING_BLOCK), 1) if mode != "none" else 0
    # logical 128-posting framing exists in BOTH layouts (block-max text
    # pruning metadata rides on it)
    NBt = max(-(-Pp // POSTING_BLOCK), 1)
    d_bits = max(int(N - 1).bit_length(), 1) if N > 1 else 1
    Wp = NBp * (POSTING_BLOCK * d_bits // 32)
    Pp_store = 0 if mode != "none" else Pp  # raw doc-id column
    SBn = max(-(-Tt // SCALE_BLOCK), 1) if mode == "int8" else 0
    # block-max metadata columns (always f32; see core/spatial_index.py)
    block_size = getattr(cfg, "block_size", 128)
    NB = max((Tt + block_size - 1) // block_size, 1)
    lead = ("docs",)  # leading shard dim over doc axes
    idx = ShardedGeoIndex(
        postings=sh((S, Pp_store), jnp.int32, lead + (None,)),
        impacts=sh((S, Pp), ft, lead + (None,)),
        offsets=sh((S, M + 1), jnp.int32, lead + (None,)),
        post_packed=sh((S, Wp), jnp.uint32, lead + (None,)),
        blk_first=sh((S, NBp), jnp.int32, lead + (None,)),
        blk_bits=sh((S, NBp), jnp.int32, lead + (None,)),
        blk_word_off=sh((S, NBp), jnp.int32, lead + (None,)),
        blk_n_exc=sh((S, NBp), jnp.int32, lead + (None,)),
        blk_len=sh((S, NBt), jnp.int32, lead + (None,)),
        blk_pos=sh((S, NBt), jnp.int32, lead + (None,)),
        blk_max_impact=sh((S, NBt), jnp.float32, lead + (None,)),
        imp_plane=sh((S, NBt, POSTING_BLOCK), jnp.float32, lead + (None, None)),
        doc_plane=sh((S, NBp, POSTING_BLOCK), jnp.int32, lead + (None, None)),
        blk_term_off=sh((S, M + 1), jnp.int32, lead + (None,)),
        # docID layout: the impact-segment CSR is degenerate (see
        # core/text_index.py build_text_index_np)
        seg_term_off=sh((S, M + 1), jnp.int32, lead + (None,)),
        seg_pos=sh((S, 1), jnp.int32, lead + (None,)),
        seg_len=sh((S, 1), jnp.int32, lead + (None,)),
        tp_rects=sh((S, Tt, 4), ft, lead + (None, None)),
        tp_amps=sh((S, Tt), at, lead + (None,)),
        tp_doc_ids=sh((S, Tt), it, lead + (None,)),
        tp_amp_scale=sh((S, SBn), jnp.float32, lead + (None,)),
        tp_planes=sh(
            (S, 3 if mode != "none" else 5, NB * (block_size // 128), 128),
            jnp.int32, lead + (None, None, None),
        ),
        tile_starts=sh((S, G2, cfg.m_intervals), jnp.int32, lead + (None, None)),
        tile_ends=sh((S, G2, cfg.m_intervals), jnp.int32, lead + (None, None)),
        doc_rects=sh((S, N, R, 4), ft, lead + (None, None, None)),
        doc_amps=sh((S, N, R), ft, lead + (None, None)),
        doc_mbr=sh((S, N, 4), ft, lead + (None, None)),
        doc_mass=sh((S, N), ft, lead + (None,)),
        blk_mbr=sh((S, NB, 4), jnp.float32, lead + (None, None)),
        blk_max_amp=sh((S, NB), jnp.float32, lead + (None,)),
        blk_max_mass=sh((S, NB), jnp.float32, lead + (None,)),
        pagerank=sh((S, N), jnp.float32, lead + (None,)),
        doc_offset=sh((S, N), jnp.int32, lead + (None,)),
        coverage_sat=sh(
            (S, COVERAGE_GRID + 1, COVERAGE_GRID + 1),
            jnp.float32,
            lead + (None, None),
        ),
        grid=cfg.grid,
        n_terms=M,
        block_size=block_size,
        coverage_grid=COVERAGE_GRID,
        # synthetic hot-term bound: a term may touch every shard doc
        max_term_blocks=max(-(-N // POSTING_BLOCK), 1),
    )
    B, d, Qr = cfg.query_batch, cfg.d_terms, cfg.q_rects
    query = alg.QueryBatch(
        terms=sh((B, d), jnp.int32, ("queries", None)),
        rects=sh((B, Qr, 4), jnp.float32, ("queries", None, None)),
        amps=sh((B, Qr), jnp.float32, ("queries", None)),
    )
    serve = make_serve_fn(
        mesh, cfg.budgets, cfg.weights, doc_axes=doc_axes, query_axis=q_axis,
        algorithm=shape.params["algorithm"], grid=cfg.grid, n_terms=M,
        max_term_blocks=idx.max_term_blocks,
    )
    # geo-score flops: ~14 flops per (toeprint, query-rect) pair per query
    kb = cfg.budgets
    mf = float(B) * kb.k_sweeps * kb.sweep_budget * Qr * 14
    return Cell(spec.name, shape.name, serve, (idx, query), model_flops=mf)


def build_cell(
    spec: ArchSpec,
    shape: ShapeSpec,
    mesh,
    opt_cfg=None,
    lm_overrides: dict | None = None,
) -> Cell:
    if spec.family == "lm":
        return build_lm_cell(spec, shape, mesh, opt_cfg, lm_overrides)
    if spec.family == "gnn":
        return build_gnn_cell(spec, shape, mesh, opt_cfg)
    if spec.family == "recsys":
        return build_recsys_cell(spec, shape, mesh, opt_cfg)
    if spec.family == "geoweb":
        return build_geoweb_cell(spec, shape, mesh)
    raise ValueError(spec.family)
