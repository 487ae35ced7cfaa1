"""E(n)-Equivariant Graph Neural Network (EGNN, arXiv:2102.09844).

Message passing over an explicit edge index with ``jax.ops.segment_sum`` —
the JAX-native scatter substrate (no SpMM needed for EGNN's scalar-distance
messages).  Kernel regime per the taxonomy: cheap equivariant (no spherical
harmonics).

Layer l:
    m_ij      = φ_e(h_i, h_j, ||x_i − x_j||², e_ij)
    x_i^{l+1} = x_i + C · Σ_j (x_i − x_j) · φ_x(m_ij)          (coord update)
    h_i^{l+1} = φ_h(h_i, Σ_j m_ij)                              (feature update)

Distribution (ogb_products scale: 62M edges): edges are sharded over every
mesh axis; nodes are replicated.  The segment-sum over a sharded edge dim
lowers to per-shard partial sums + an all-reduce — the canonical
graph-parallel pattern.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from repro.models.params import ParamDef, init_params, param_count
from repro.sharding.specs import shard


@dataclass(frozen=True)
class EGNNConfig:
    name: str = "egnn"
    n_layers: int = 4
    d_hidden: int = 64
    d_feat: int = 16  # input node-feature dim
    coord_dim: int = 3
    n_classes: int = 8  # node classification head (0 → graph regression)
    coord_agg: str = "mean"
    scan_unroll: bool = False
    compute_dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    def param_defs(self) -> dict:
        H, Fin, Lyr = self.d_hidden, self.d_feat, self.n_layers
        pd = self.param_dtype
        # φ_e: (h_i, h_j, dist²) → m ; φ_x: m → scalar ; φ_h: (h_i, Σm) → h
        layer = {
            "edge_w1": ParamDef((Lyr, 2 * H + 1, H), ("layers", None, None), pd),
            "edge_b1": ParamDef((Lyr, H), ("layers", None), pd, "zeros"),
            "edge_w2": ParamDef((Lyr, H, H), ("layers", None, None), pd),
            "edge_b2": ParamDef((Lyr, H), ("layers", None), pd, "zeros"),
            "coord_w1": ParamDef((Lyr, H, H), ("layers", None, None), pd),
            "coord_b1": ParamDef((Lyr, H), ("layers", None), pd, "zeros"),
            "coord_w2": ParamDef((Lyr, H, 1), ("layers", None, None), pd, "normal", 0.001),
            "node_w1": ParamDef((Lyr, 2 * H, H), ("layers", None, None), pd),
            "node_b1": ParamDef((Lyr, H), ("layers", None), pd, "zeros"),
            "node_w2": ParamDef((Lyr, H, H), ("layers", None, None), pd),
            "node_b2": ParamDef((Lyr, H), ("layers", None), pd, "zeros"),
        }
        defs = {
            "encode": ParamDef((Fin, H), (None, None), pd),
            "layers": layer,
        }
        if self.n_classes > 0:
            defs["head"] = ParamDef((H, self.n_classes), (None, None), pd)
        else:
            defs["head"] = ParamDef((H, 1), (None, None), pd)
        return defs

    def init(self, key: jax.Array) -> dict:
        return init_params(self.param_defs(), key)

    def n_params(self) -> int:
        return param_count(self.param_defs())


def _mlp2(x, w1, b1, w2, b2, act=jax.nn.silu):
    w1, b1, w2, b2 = (t.astype(x.dtype) for t in (w1, b1, w2, b2))
    return act(x @ w1 + b1) @ w2 + b2


def egnn_layer(cfg: EGNNConfig, lp: dict, h, x, senders, receivers, edge_mask):
    """One EGNN layer.  h [N,H], x [N,C], edges i32[E], edge_mask bool[E]."""
    N = h.shape[0]
    hi = h[receivers]  # [E, H]
    hj = h[senders]
    xi = x[receivers]  # [E, C]
    xj = x[senders]
    diff = xi - xj
    dist2 = jnp.sum(diff * diff, axis=-1, keepdims=True)  # [E,1]
    m_in = jnp.concatenate([hi, hj, dist2], axis=-1)
    m_in = shard(m_in, "edges", None)
    m = _mlp2(m_in, lp["edge_w1"], lp["edge_b1"], lp["edge_w2"], lp["edge_b2"])
    m = jax.nn.silu(m) * edge_mask[:, None]
    m = shard(m, "edges", None)

    # coordinate update (E(n) equivariant)
    cw = jax.nn.silu(
        m @ lp["coord_w1"].astype(m.dtype) + lp["coord_b1"].astype(m.dtype)
    ) @ lp["coord_w2"].astype(m.dtype)  # [E,1]
    upd = diff * cw * edge_mask[:, None]
    num = jax.ops.segment_sum(upd, receivers, num_segments=N)
    if cfg.coord_agg == "mean":
        deg = jax.ops.segment_sum(
            edge_mask.astype(jnp.float32), receivers, num_segments=N
        )
        num = num / jnp.maximum(deg, 1.0).astype(num.dtype)[:, None]
    x_new = x + num.astype(x.dtype)

    # feature update
    agg = jax.ops.segment_sum(m, receivers, num_segments=N)  # [N,H]
    h_new = h + _mlp2(
        jnp.concatenate([h, agg], axis=-1),
        lp["node_w1"], lp["node_b1"], lp["node_w2"], lp["node_b2"],
    )
    return h_new, x_new


def forward(cfg: EGNNConfig, params: dict, batch: dict):
    """batch: feats f32[N,Fin], coords f32[N,C], senders/receivers i32[E],
    edge_mask bool[E].  Returns (node_out [N, n_classes] or graph scalar)."""
    h = batch["feats"].astype(cfg.compute_dtype) @ params["encode"].astype(
        cfg.compute_dtype
    )
    x = batch["coords"].astype(cfg.compute_dtype)
    senders, receivers = batch["senders"], batch["receivers"]
    edge_mask = batch["edge_mask"].astype(cfg.compute_dtype)

    def body(carry, lp):
        h, x = carry
        h, x = egnn_layer(cfg, lp, h, x, senders, receivers, edge_mask)
        return (h, x), None

    (h, x), _ = jax.lax.scan(body, (h, x), params["layers"], unroll=cfg.scan_unroll)
    return (h @ params["head"].astype(h.dtype)).astype(jnp.float32), x


# ---------------------------------------------------------------------------
# Explicitly-sharded full-graph training (shard_map)
# ---------------------------------------------------------------------------
#
# Auto-SPMD on the replicated-node formulation materializes f32 full-node
# gathers in backward (observed 10+ GB/device on ogb_products, plus
# "involuntary full rematerialization" partitioner warnings).  This path
# shards the NODE state row-wise over every mesh axis and makes the
# communication pattern explicit per layer:
#     all_gather(h, x)            — senders may live on any shard
#     local messages + local segment_sum into a full-N partial buffer
#     psum_scatter(partials)      — reduce-scatter back to node shards
# i.e. AG + RS per tensor per layer instead of AR + backward re-gathers.

def make_sharded_loss(cfg: EGNNConfig, mesh):
    """Returns loss(params, batch) running under shard_map on ``mesh``.

    batch node arrays must be padded to a multiple of the total device count
    (``pad_nodes``), edge arrays likewise (senders/receivers use GLOBAL ids).
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    axes = tuple(a for a in ("pod", "data", "model") if a in mesh.axis_names)

    def body(params, batch):
        feats, coords = batch["feats"], batch["coords"]  # [N/P, ...] local
        senders, receivers = batch["senders"], batch["receivers"]  # global ids
        edge_mask = batch["edge_mask"].astype(cfg.compute_dtype)
        N_loc = feats.shape[0]
        P_tot = 1
        for a in axes:
            # jax.lax.axis_size only exists in newer jax; psum(1) is equivalent
            P_tot *= jax.lax.psum(1, a)
        N = N_loc * P_tot

        h = feats.astype(cfg.compute_dtype) @ params["encode"].astype(cfg.compute_dtype)
        x = coords.astype(cfg.compute_dtype)

        def layer(carry, lp):
            h, x = carry
            h_full = jax.lax.all_gather(h, axes, tiled=True)  # [N, H]
            x_full = jax.lax.all_gather(x, axes, tiled=True)
            hi, hj = h_full[receivers], h_full[senders]
            xi, xj = x_full[receivers], x_full[senders]
            diff = xi - xj
            dist2 = jnp.sum(diff * diff, axis=-1, keepdims=True)
            m = _mlp2(
                jnp.concatenate([hi, hj, dist2], axis=-1),
                lp["edge_w1"], lp["edge_b1"], lp["edge_w2"], lp["edge_b2"],
            )
            m = jax.nn.silu(m) * edge_mask[:, None]
            cw = jax.nn.silu(
                m @ lp["coord_w1"].astype(m.dtype) + lp["coord_b1"].astype(m.dtype)
            ) @ lp["coord_w2"].astype(m.dtype)
            upd = diff * cw * edge_mask[:, None]
            # local partial sums over the FULL node range, then reduce-scatter
            upd_p = jax.ops.segment_sum(upd, receivers, num_segments=N)
            agg_p = jax.ops.segment_sum(m, receivers, num_segments=N)
            # degree stays f32: hub degrees (>256) are not exact in bf16
            deg_p = jax.ops.segment_sum(
                edge_mask.astype(jnp.float32), receivers, num_segments=N
            )
            upd_l = jax.lax.psum_scatter(upd_p, axes, scatter_dimension=0, tiled=True)
            agg_l = jax.lax.psum_scatter(agg_p, axes, scatter_dimension=0, tiled=True)
            deg_l = jax.lax.psum_scatter(deg_p, axes, scatter_dimension=0, tiled=True)
            if cfg.coord_agg == "mean":
                upd_l = upd_l / jnp.maximum(deg_l, 1.0)[:, None]
            x = x + upd_l.astype(x.dtype)
            h = h + _mlp2(
                jnp.concatenate([h, agg_l.astype(h.dtype)], axis=-1),
                lp["node_w1"], lp["node_b1"], lp["node_w2"], lp["node_b2"],
            )
            return (h, x), None

        (h, x), _ = jax.lax.scan(
            jax.checkpoint(layer), (h, x), params["layers"], unroll=cfg.scan_unroll
        )
        out = (h @ params["head"].astype(h.dtype)).astype(jnp.float32)
        labels = batch["labels"]
        mask = labels >= 0
        lse = jax.nn.logsumexp(out, axis=-1)
        ll = jnp.sum(
            jnp.where(
                jax.lax.broadcasted_iota(jnp.int32, out.shape, 1)
                == jnp.maximum(labels, 0)[:, None],
                out, 0.0,
            ),
            axis=-1,
        )
        nll_sum = jax.lax.psum(((lse - ll) * mask).sum(), axes)
        n = jax.lax.psum(mask.sum(), axes)
        acc = jax.lax.psum(((out.argmax(-1) == labels) & mask).sum(), axes)
        loss = nll_sum / jnp.maximum(n, 1)
        return loss, {"nll": loss, "acc": acc / jnp.maximum(n, 1)}

    node = P(axes)
    edge = P(axes)
    in_specs = (
        P(),  # params replicated
        {
            "feats": node, "coords": node, "labels": node,
            "senders": edge, "receivers": edge, "edge_mask": edge,
        },
    )
    return shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=(P(), P()), check_vma=False
    )


def pad_nodes(n: int, multiple: int = 512) -> int:
    return (n + multiple - 1) // multiple * multiple


def loss_fn(cfg: EGNNConfig, params: dict, batch: dict):
    """Node classification (labels i32[N], −1 ignored) or graph regression
    (graph_ids i32[N] + targets f32[G])."""
    out, _ = forward(cfg, params, batch)
    if cfg.n_classes > 0:
        labels = batch["labels"]
        mask = labels >= 0
        lse = jax.nn.logsumexp(out, axis=-1)
        ll = jnp.take_along_axis(out, jnp.maximum(labels, 0)[:, None], axis=1)[:, 0]
        n = jnp.maximum(mask.sum(), 1)
        loss = ((lse - ll) * mask).sum() / n
        acc = ((out.argmax(-1) == labels) & mask).sum() / n
        return loss, {"nll": loss, "acc": acc}
    # graph regression: mean-pool nodes per graph
    G = batch["targets"].shape[0]
    pooled = jax.ops.segment_sum(out[:, 0], batch["graph_ids"], num_segments=G)
    counts = jax.ops.segment_sum(
        jnp.ones_like(out[:, 0]), batch["graph_ids"], num_segments=G
    )
    pred = pooled / jnp.maximum(counts, 1.0)
    loss = jnp.mean((pred - batch["targets"]) ** 2)
    return loss, {"mse": loss}
