"""Pallas TPU kernel: per-toe-print geographic scores.

The FLOP hot spot of the paper's pipeline (precise geo scoring, §IV):
for a tile of toe prints and a small set of query rectangles compute

    out[t] = amp[t] * Σ_j area(rect[t] ∩ qrect[j]) * qamp[j]

Layout decisions (TPU-native, DESIGN.md §2):

* Toe-print rect components arrive as four planar f32 arrays shaped
  ``[rows, 128]`` (ops.py transposes/pads) — lane dimension = toe prints, so
  every min/max/mul is a full-width VPU op.  The packed ``[T, 4]`` layout
  would put the 4 coordinates in lanes and waste 124/128 of the vector unit.
* The query footprint (≤ Q_MAX rects) is tiny: it sits unblocked in SMEM and
  the kernel unrolls a static Python loop over its rows — each iteration is
  a scalar-broadcast VPU multiply-accumulate over the [BLOCK_ROWS, 128] tile.
* Block shape (BLOCK_ROWS × 128) f32 = 8 sublanes × 128 lanes per input
  plane — the native VREG tile; 5 input planes + 1 output plane per block =
  24 KiB of VMEM per grid step at the default BLOCK_ROWS=8, leaving VMEM for
  double buffering at any practical grid size.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
BLOCK_ROWS = 8  # sublane-aligned f32 tile
Q_MAX = 8  # max query rects supported by a single kernel pass


def _geo_score_kernel(qr_ref, qa_ref, x0_ref, y0_ref, x1_ref, y1_ref, amp_ref, out_ref):
    x0 = x0_ref[...]
    y0 = y0_ref[...]
    x1 = x1_ref[...]
    y1 = y1_ref[...]
    acc = jnp.zeros_like(x0)
    for j in range(Q_MAX):  # static unroll over query rects
        qx0 = qr_ref[j, 0]
        qy0 = qr_ref[j, 1]
        qx1 = qr_ref[j, 2]
        qy1 = qr_ref[j, 3]
        w = jnp.maximum(jnp.minimum(x1, qx1) - jnp.maximum(x0, qx0), 0.0)
        h = jnp.maximum(jnp.minimum(y1, qy1) - jnp.maximum(y0, qy0), 0.0)
        acc = acc + (w * h) * qa_ref[0, j]
    out_ref[...] = acc * amp_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def geo_score_planar(
    q_rects: jax.Array,  # f32[Q_MAX, 4]
    q_amps: jax.Array,  # f32[1, Q_MAX] (2-D: a 1-D block fails under vmap)
    x0: jax.Array,  # f32[rows, 128]
    y0: jax.Array,
    x1: jax.Array,
    y1: jax.Array,
    amp: jax.Array,
    interpret: bool,
) -> jax.Array:
    """Raw pallas_call on pre-planarized inputs. Prefer ops.geo_score_toeprints."""
    rows = x0.shape[0]
    assert rows % BLOCK_ROWS == 0, rows
    assert q_rects.shape == (Q_MAX, 4) and q_amps.shape == (1, Q_MAX)
    grid = (rows // BLOCK_ROWS,)
    plane = pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0))
    return pl.pallas_call(
        _geo_score_kernel,
        grid=grid,
        in_specs=[
            # query rects / amps are read as scalars: whole, in SMEM
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            plane, plane, plane, plane, plane,
        ],
        out_specs=plane,
        out_shape=jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
        interpret=interpret,
    )(q_rects, q_amps, x0, y0, x1, y1, amp)
