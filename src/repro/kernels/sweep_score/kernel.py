"""Pallas TPU kernels: fused k-sweep fetch + geo scoring (+ block-max prune).

The K-SWEEP hot path does two HBM passes in the reference implementation:
(1) ``dynamic_slice`` the toe-print store for each sweep, (2) score the
fetched toe prints against the query footprint.  ``sweep_score_planar``
FUSES them: the grid walks ``(sweep, block-within-sweep)`` and the input
BlockSpec index_map is driven by the **scalar-prefetched sweep starts** —
each grid step DMAs the next VMEM tile of the Morton-ordered store directly
from the sweep's dynamic offset and scores it in-register.  The fetched toe
prints never round-trip through HBM.

``sweep_score_pruned_planar`` extends the fused pipeline into
sweep → score → *select*: each VMEM tile is divided into its metadata
blocks (``core/spatial_index.py``; 128–1024 toe prints, i.e. whole lane
rows), and every block's precomputed upper bound (block MBR ∩ query ×
max amp) is tested against a running threshold θ — blocks that cannot
beat θ are masked out of scoring and flagged skipped, WAND-style adaptive
feedback.  θ is maintained in a persistent VMEM scratch buffer
approximating the partial top-``max_candidates`` heap: the buffer holds
``C`` slots, every tile folds its surviving masked scores elementwise-max
into a cyclically-assigned slice, and θ = min(buffer).  Each slot is then
the max of a disjoint subset of the candidate scores seen so far, so min
over the ``C`` slots never exceeds the true C-th largest candidate score —
pruning against it is *safe*: a skipped block cannot contain a top-C
candidate.  The buffer is *seeded* with the select stage's score floor
(``prune_eps`` × query mass), so blocks below the floor are skipped even
before C candidates have streamed — provably without changing the final
selection.  Per-block ``scored`` flags are emitted so the caller can
count skipped blocks and charge only the bytes actually streamed.

Layout mirrors kernels/geo_score: planar coordinate arrays with the lane
dimension along toe prints ([rows, 128] tiles), query rects unrolled from
SMEM scalars.  Sweep starts are tile-aligned by ops.py (rounded down to the
kernel's tile); masking against the true [start, end) range happens in
ops.py for the unpruned kernel, and in-kernel (positions derived from the
prefetched starts) for the pruned one, whose θ updates must see only
genuine candidates.

TPU tiling constraints (found by compiling for a v5e):

* the unpruned kernel streams the stored dtypes (f32/f16 coords,
  f32/f16/int8 amps) through BlockSpecs of ``PLAIN_ROWS = 32`` rows — the
  minimum sublane tile of an 8-bit plane (16-bit needs 16, 32-bit 8), so
  one block shape loads every stored dtype;
* the v5e cannot load f16 vectors: 16-bit float planes arrive as their
  int16 bit patterns and are widened in-register (``f16_bits_to_f32``);
* the pruned kernel copies single 128-toe-print rows by hand, and a row
  slice of a 16- or 8-bit HBM array is not tile-aligned, so it reads a
  32-bit copy of the store built once with the index
  (``SpatialIndex.tp_planes``, see ``ops.pruned_store_planes``): f32
  coordinates as 4 planes, or f16 coordinates packed two per 32-bit word
  as 2, plus the amp decoded to f32 (× the int8 row scale) — 20 or 12
  bytes per toe print, decoded exactly in-register;
* the per-block ``scored`` flags are one SMEM output block spanning the
  whole array (a ``(1, bpt)`` block violates the (8, 128) block rule).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
BLOCK_ROWS = 8
TILE = BLOCK_ROWS * LANES  # toe prints per pruned grid step
PLAIN_ROWS = 32  # unpruned block rows: the 8-bit sublane tile
PLAIN_TILE = PLAIN_ROWS * LANES  # toe prints per unpruned grid step
Q_MAX = 8

# query rects / amps are read as scalars: whole arrays in SMEM
_Q_SPECS = [
    pl.BlockSpec(memory_space=pltpu.SMEM),
    pl.BlockSpec(memory_space=pltpu.SMEM),
]


def f16_bits_to_f32(h: jax.Array) -> jax.Array:
    """Decode f16 bit patterns (any integer dtype) to f32, exactly.

    The v5e cannot load f16 vectors, so 16-bit float planes reach the
    kernel as their int16 bit patterns and are widened here with integer
    ops: bit-identical to ``astype(float32)`` for every f16 value
    (normals, subnormals, signed zeros, inf and NaN).
    """
    h = h.astype(jnp.int32) & 0xFFFF
    sign = (h >> 15) << 31
    ex = (h >> 10) & 0x1F
    man = h & 0x3FF
    normal = jax.lax.bitcast_convert_type(
        sign | ((ex + 112) << 23) | (man << 13), jnp.float32
    )
    special = jax.lax.bitcast_convert_type(
        sign | 0x7F800000 | (man << 13), jnp.float32
    )
    sub = man.astype(jnp.float32) * jnp.float32(2.0**-24)
    sub = jnp.where(sign != 0, -sub, sub)
    return jnp.where(ex == 0, sub, jnp.where(ex == 31, special, normal))


def _decode(v: jax.Array) -> jax.Array:
    # int16 planes carry f16 bit patterns (see f16_bits_to_f32); every
    # other stored dtype (f32, int8) converts directly
    return f16_bits_to_f32(v) if v.dtype == jnp.int16 else v.astype(jnp.float32)


def _kernel(
    starts_ref, qr_ref, qa_ref, x0_ref, y0_ref, x1_ref, y1_ref, amp_ref, sc_ref,
    out_ref,
):
    # starts_ref is scalar-prefetch (used only by the index maps).  The
    # planes arrive in their STORED dtype (f32 or f16 bits as int16 coords,
    # f32/f16 bits/int8 amps) and are decoded in-register to f32, then
    # the amps × the per-row scale (all-ones for non-int8 stores — ×1.0 is
    # bitwise exact).
    x0 = _decode(x0_ref[...])
    y0 = _decode(y0_ref[...])
    x1 = _decode(x1_ref[...])
    y1 = _decode(y1_ref[...])
    amp = _decode(amp_ref[...]) * sc_ref[...]
    acc = jnp.zeros_like(x0)
    for j in range(Q_MAX):  # static unroll over query rects
        qx0 = qr_ref[j, 0]
        qy0 = qr_ref[j, 1]
        qx1 = qr_ref[j, 2]
        qy1 = qr_ref[j, 3]
        w = jnp.maximum(jnp.minimum(x1, qx1) - jnp.maximum(x0, qx0), 0.0)
        h = jnp.maximum(jnp.minimum(y1, qy1) - jnp.maximum(y0, qy0), 0.0)
        acc = acc + (w * h) * qa_ref[j]
    out_ref[...] = acc * amp


@functools.partial(jax.jit, static_argnames=("n_sweeps", "budget", "interpret"))
def sweep_score_planar(
    block_starts: jax.Array,  # i32[k] sweep starts in PLAIN_TILE units
    q_rects: jax.Array,  # f32[Q_MAX, 4]
    q_amps: jax.Array,  # f32[Q_MAX]
    x0: jax.Array,  # [rows, 128] — the ENTIRE toe-print store, planar, in
    y0: jax.Array,  # its stored dtype (f16 planes as int16 bit patterns)
    x1: jax.Array,
    y1: jax.Array,
    amp: jax.Array,
    scale: jax.Array,  # f32[rows, 1] per-row amp scale (ones unless int8)
    n_sweeps: int,
    budget: int,  # toe prints fetched per sweep; multiple of PLAIN_TILE
    interpret: bool,
) -> jax.Array:
    """Returns per-sweep scores f32[k, budget // LANES, 128].

    grid = (k, budget/PLAIN_TILE); block (i, j) reads store rows
    ``(block_starts[i] + j) * PLAIN_ROWS`` — a streaming DMA from the sweep
    offset, fused with scoring.
    """
    assert budget % PLAIN_TILE == 0
    n_blocks = budget // PLAIN_TILE

    def in_map(i, j, starts):
        # starts[i] is in PLAIN_TILE units
        return (starts[i] + j, 0)

    plane = pl.BlockSpec((PLAIN_ROWS, LANES), in_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_sweeps, n_blocks),
        in_specs=[
            *_Q_SPECS,
            plane, plane, plane, plane, plane,
            pl.BlockSpec((PLAIN_ROWS, 1), in_map),
        ],
        out_specs=pl.BlockSpec(
            (1, PLAIN_ROWS, LANES), lambda i, j, s: (i, j, 0)
        ),
    )
    out = pl.pallas_call(
        lambda s_ref, qr, qa, a, b, c, d, e, sc, o: _kernel(
            s_ref, qr, qa, a, b, c, d, e, sc, o.at[0]
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            (n_sweeps, budget // LANES, LANES), jnp.float32
        ),
        interpret=interpret,
    )(block_starts, q_rects, q_amps, x0, y0, x1, y1, amp, scale)
    return out


def _unpack_planes(planes_s, n_planes: int):
    """(x0, y0, x1, y1, amp) f32 tiles from the copied 32-bit planes: 5
    planes hold f32 bit patterns; 3 hold the f16 bit pairs ``x0 | y0 << 16``
    and ``x1 | y1 << 16`` and the f32 amp (``ops.pruned_store_planes``)."""
    def f32(p):
        return jax.lax.bitcast_convert_type(planes_s[p], jnp.float32)

    if n_planes == 5:
        return tuple(f32(p) for p in range(5))
    lo, hi = planes_s[0], planes_s[1]
    return (
        f16_bits_to_f32(lo),
        f16_bits_to_f32(lo >> 16),
        f16_bits_to_f32(hi),
        f16_bits_to_f32(hi >> 16),
        f32(2),
    )


def _pruned_kernel(
    starts_ref,  # scalar prefetch: i32[k] sweep starts in TILE units
    bounds_ref,  # SMEM i32[k, 2]: exact [start, end) element offsets
    floor_ref,  # SMEM f32[1]: select-stage score floor (prune_eps × mass)
    ub_ref,  # SMEM f32[k, n_tiles*bpt]: per-metadata-block upper bounds
    qr_ref,  # SMEM f32[Q_MAX, 4]
    qa_ref,  # SMEM f32[Q_MAX]
    planes_hbm,  # ANY-space i32[P, rows, LANES] store planes (copied per block)
    out_ref,  # VMEM f32[BLOCK_ROWS, LANES] tile of the score output
    scored_ref,  # SMEM i32[k, n_tiles*bpt] per-metadata-block scored flags
    buf_ref,  # VMEM scratch f32[cb*BLOCK_ROWS, LANES]: partial top-C heap
    planes_s,  # VMEM scratch i32[P, BLOCK_ROWS, LANES]: the manually-DMA'd
    # tile (only scored blocks' rows are copied in)
    copy_sem,  # DMA semaphore for the per-block copies
    *,
    n_tiles: int,
    cb: int,
    bpt: int,  # metadata blocks per tile
    n_planes: int,
):
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when((i == 0) & (j == 0))
    def _init():
        # seed every slot with the selection floor: θ never drops below it,
        # so blocks whose bound cannot clear the floor are skipped — their
        # candidates would be dropped by the select stage regardless
        buf_ref[...] = jnp.full_like(buf_ref, floor_ref[0])

    theta = jnp.min(buf_ref[...])
    rows_per_block = (BLOCK_ROWS + bpt - 1) // bpt  # bpt divides BLOCK_ROWS
    rows = jax.lax.broadcasted_iota(jnp.int32, (BLOCK_ROWS, LANES), 0)
    row0 = (starts_ref[i] + j) * BLOCK_ROWS  # planar row of this tile
    # per-row scored mask assembled from the bpt per-block decisions
    mask = jnp.zeros((BLOCK_ROWS, LANES), dtype=bool)
    any_scored = False
    for b in range(bpt):  # static unroll over the tile's metadata blocks
        sb = ub_ref[i, j * bpt + b] > theta
        scored_ref[i, j * bpt + b] = sb.astype(jnp.int32)
        mask = mask | (sb & (rows // rows_per_block == b))
        any_scored = sb | any_scored

        # a θ-skipped block issues NO copy: zero bytes move for it.  Its
        # scratch rows keep stale data from earlier tiles, which is safe —
        # every consumer below selects through ``mask`` (jnp.where), so
        # garbage (even NaN) in never-copied rows cannot propagate.
        @pl.when(sb)
        def _fetch(b=b):
            src_row = row0 + b * rows_per_block
            dst_row = b * rows_per_block
            for p in range(n_planes):
                cp = pltpu.make_async_copy(
                    planes_hbm.at[p, pl.ds(src_row, rows_per_block), :],
                    planes_s.at[p, pl.ds(dst_row, rows_per_block), :],
                    copy_sem,
                )
                cp.start()
                cp.wait()

    @pl.when(any_scored)
    def _score():
        x0, y0, x1, y1, amp = _unpack_planes(planes_s, n_planes)
        acc = jnp.zeros_like(x0)
        for q in range(Q_MAX):  # static unroll over query rects
            qx0 = qr_ref[q, 0]
            qy0 = qr_ref[q, 1]
            qx1 = qr_ref[q, 2]
            qy1 = qr_ref[q, 3]
            w = jnp.maximum(jnp.minimum(x1, qx1) - jnp.maximum(x0, qx0), 0.0)
            h = jnp.maximum(jnp.minimum(y1, qy1) - jnp.maximum(y0, qy0), 0.0)
            acc = acc + (w * h) * qa_ref[q]
        sc = jnp.where(mask, acc * amp, 0.0)
        out_ref[...] = sc
        # absolute toe-print positions of this tile, for the validity mask —
        # only genuine [start, end) candidates may feed the θ buffer
        cols = jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
        pos = (starts_ref[i] + j) * TILE + rows * LANES + cols
        okm = (pos >= bounds_ref[i, 0]) & (pos < bounds_ref[i, 1])
        masked = jnp.where(okm, sc, 0.0)
        # cyclic top-C approximation: fold this tile into its buffer slice
        r0 = ((i * n_tiles + j) % cb) * BLOCK_ROWS
        sl = buf_ref[pl.ds(r0, BLOCK_ROWS), :]
        buf_ref[pl.ds(r0, BLOCK_ROWS), :] = jnp.maximum(sl, masked)

    @pl.when(jnp.logical_not(any_scored))
    def _skip():
        out_ref[...] = jnp.zeros_like(out_ref)


@functools.partial(
    jax.jit,
    static_argnames=("n_sweeps", "budget", "max_candidates", "bpt", "interpret"),
)
def sweep_score_pruned_planar(
    block_starts: jax.Array,  # i32[k] sweep starts in TILE units
    bounds: jax.Array,  # i32[k, 2] exact [start, end) element offsets
    floor: jax.Array,  # f32[1] select-stage score floor
    block_ub: jax.Array,  # f32[k, (budget // TILE) * bpt] per-block bounds
    q_rects: jax.Array,  # f32[Q_MAX, 4]
    q_amps: jax.Array,  # f32[Q_MAX]
    planes: jax.Array,  # i32[P, rows, 128] — the ENTIRE toe-print store in
    # 32-bit planes (16/8-bit rows cannot be copied singly; P = 5 or 3)
    n_sweeps: int,
    budget: int,  # toe prints fetched per sweep; multiple of TILE
    max_candidates: int,  # C of the partial top-C threshold buffer
    bpt: int,  # metadata blocks per TILE (1, 2, 4 or 8)
    interpret: bool,
) -> tuple[jax.Array, jax.Array]:
    """Pruned fused sweep: (scores f32[k, budget//LANES, 128],
    scored i32[k, (budget//TILE)*bpt] per-metadata-block flags).

    Grid = (k, budget/TILE), walked sequentially, so the θ scratch carries
    across all tiles of all sweeps of one query; under ``vmap`` the batch
    axis becomes the outermost grid dimension and the (0, 0) re-init gives
    every query a fresh threshold.

    Unlike the unpruned kernel, the store planes are NOT auto-DMA'd by a
    BlockSpec: they stay in ``ANY`` memory space and the kernel issues a
    manual ``make_async_copy`` per *metadata block* that survives the θ
    test, so a skipped block truly moves zero bytes (the PR 4 caveat —
    previously the whole tile streamed and skipped blocks were only
    masked after the fetch).
    """
    assert budget % TILE == 0
    assert BLOCK_ROWS % bpt == 0
    n_planes = planes.shape[0]
    assert planes.dtype == jnp.int32 and n_planes in (3, 5)
    n_tiles = budget // TILE
    # C rounded up to whole tiles: a larger buffer only lowers θ (safer)
    cb = max(1, -(-max_candidates // TILE))

    # store planes: full arrays, manually copied block-wise in-kernel
    plane = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_sweeps, n_tiles),
        in_specs=[
            pl.BlockSpec(
                (n_sweeps, 2), lambda i, j, s: (0, 0), memory_space=pltpu.SMEM
            ),
            pl.BlockSpec((1,), lambda i, j, s: (0,), memory_space=pltpu.SMEM),
            pl.BlockSpec(
                (n_sweeps, n_tiles * bpt),
                lambda i, j, s: (0, 0),
                memory_space=pltpu.SMEM,
            ),
            *_Q_SPECS,
            plane,
        ],
        out_specs=[
            pl.BlockSpec((1, BLOCK_ROWS, LANES), lambda i, j, s: (i, j, 0)),
            # one resident block over the whole flag array: every grid
            # step writes its own bpt entries
            pl.BlockSpec(
                (n_sweeps, n_tiles * bpt),
                lambda i, j, s: (0, 0),
                memory_space=pltpu.SMEM,
            ),
        ],
        scratch_shapes=[
            pltpu.VMEM((cb * BLOCK_ROWS, LANES), jnp.float32),
            pltpu.VMEM((n_planes, BLOCK_ROWS, LANES), jnp.int32),
            pltpu.SemaphoreType.DMA,
        ],
    )
    kernel = functools.partial(
        _pruned_kernel, n_tiles=n_tiles, cb=cb, bpt=bpt, n_planes=n_planes
    )
    scores, scored = pl.pallas_call(
        lambda s_ref, bd, fl, ub, qr, qa, pln, o, *rest: kernel(
            s_ref, bd, fl, ub, qr, qa, pln, o.at[0], *rest
        ),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((n_sweeps, budget // LANES, LANES), jnp.float32),
            jax.ShapeDtypeStruct((n_sweeps, n_tiles * bpt), jnp.int32),
        ],
        interpret=interpret,
    )(
        block_starts, bounds, floor, block_ub, q_rects, q_amps, planes,
    )
    return scores, scored
