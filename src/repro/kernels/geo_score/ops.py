"""jit'd public wrappers for the geo_score Pallas kernel.

Handles layout adaptation (packed [T,4] rects → planar [rows,128] components),
padding, and backend selection (interpret mode off-TPU).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.geo_score.kernel import BLOCK_ROWS, LANES, Q_MAX, geo_score_planar


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=("interpret",))
def geo_score_toeprints(
    rects: jax.Array,  # f32[T, 4]
    amps: jax.Array,  # f32[T]
    q_rects: jax.Array,  # f32[Q, 4], Q <= Q_MAX
    q_amps: jax.Array,  # f32[Q]
    interpret: bool | None = None,
) -> jax.Array:
    """Per-toe-print geo scores, f32[T]. Drop-in for the k_sweep tp_scorer."""
    if interpret is None:
        interpret = _default_interpret()
    T = rects.shape[0]
    Q = q_rects.shape[0]
    assert Q <= Q_MAX, f"at most {Q_MAX} query rects per pass, got {Q}"

    # pad query to Q_MAX with zero-amp empty rects
    qr = jnp.zeros((Q_MAX, 4), jnp.float32).at[:Q].set(q_rects.astype(jnp.float32))
    qa = jnp.zeros((1, Q_MAX), jnp.float32).at[0, :Q].set(q_amps.astype(jnp.float32))

    # planarize: [T,4] -> four [rows,128] planes (pad T up to tile multiple)
    tile = BLOCK_ROWS * LANES
    Tp = (T + tile - 1) // tile * tile
    pad = Tp - T

    def plane(v, fill):
        v = jnp.pad(v.astype(jnp.float32), (0, pad), constant_values=fill)
        return v.reshape(Tp // LANES, LANES)

    out = geo_score_planar(
        qr, qa,
        plane(rects[:, 0], 1.0),  # empty-rect padding (x1 < x0 => area 0)
        plane(rects[:, 1], 1.0),
        plane(rects[:, 2], 0.0),
        plane(rects[:, 3], 0.0),
        plane(amps, 0.0),
        interpret=interpret,
    )
    return out.reshape(Tp)[:T]


@functools.partial(jax.jit, static_argnames=("interpret",))
def geo_score_docs(
    doc_rects: jax.Array,  # f32[C, R, 4]
    doc_amps: jax.Array,  # f32[C, R]
    q_rects: jax.Array,  # f32[Q, 4]
    q_amps: jax.Array,  # f32[Q]
    interpret: bool | None = None,
) -> jax.Array:
    """Per-document geo scores f32[C]: kernel over the flattened rect set."""
    C, R, _ = doc_rects.shape
    flat = geo_score_toeprints(
        doc_rects.reshape(C * R, 4),
        doc_amps.reshape(C * R),
        q_rects,
        q_amps,
        interpret=interpret,
    )
    return flat.reshape(C, R).sum(axis=1)
