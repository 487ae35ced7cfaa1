"""Compile the serving path's Pallas kernels for a TPU v5e, without a chip.

Interpret mode on the CPU hides the TPU compiler's constraints (block
tiling, SMEM size, unsupported primitives, vector types the chip cannot
load).  These tests lower each kernel at geoweb's widths (one chip's shard
of ``configs/geoweb.py``) against a *described* v5e and compile it with
``interpret=False``, as the real serving step calls it: under ``vmap``
over a query batch.  Nothing runs; a passing compile is not a chip run.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every xdist worker imports this
file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.geoweb import CONFIG

B = 8  # queries per served batch
N = CONFIG.n_docs // 8  # one chip's shard: the fewest shards int32 allows
T = N * CONFIG.max_rects  # toe prints
NB_TP = -(-T // 128)  # 128-toe-print metadata blocks
NB_POST = N * CONFIG.avg_postings_per_doc // 128 + CONFIG.n_terms  # + ragged tails
MAX_TERM_BLOCKS = N // 128  # a term in every document
KB = CONFIG.budgets


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs go to /tmp
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to rehearse
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    print(fn.__name__, compiled.memory_analysis())
    return compiled.as_text()


def _query(sds):
    return (
        sds((B, KB.k_sweeps), jnp.int32),
        sds((B, KB.k_sweeps), jnp.int32),
        sds((B, CONFIG.q_rects, 4), jnp.float32),
        sds((B, CONFIG.q_rects), jnp.float32),
    )


def _store(sds, mode):
    ft = jnp.float16 if mode != "none" else jnp.float32
    at = jnp.int8 if mode == "int8" else ft
    scale = sds((NB_TP,), jnp.float32) if mode == "int8" else None
    return sds((T, 4), ft), sds((T,), at), scale


@pytest.mark.parametrize("mode", ["none", "f16", "int8"])
def test_sweep_score_compiles(one_chip, mode):
    from repro.kernels.sweep_score.ops import sweep_score

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    rects, amps, scale = _store(sds, mode)

    def sweep_plain(rects, amps, s, e, qr, qa, scale):
        return jax.vmap(
            lambda s, e, qr, qa: sweep_score(
                rects, amps, s, e, qr, qa, KB.sweep_budget,
                interpret=False, tp_amp_scale=scale,
            )
        )(s, e, qr, qa)

    hlo = _compile(sweep_plain, rects, amps, *_query(sds), scale)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("mode", ["none", "f16", "int8"])
def test_sweep_score_pruned_compiles(one_chip, mode):
    from repro.kernels.sweep_score.ops import sweep_score_pruned

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    rects, amps, _ = _store(sds, mode)
    meta = (
        sds((NB_TP, 4), jnp.float32),
        sds((NB_TP,), jnp.float32),
        sds((NB_TP,), jnp.float32),
    )
    # the index's resident 32-bit planes (SpatialIndex.tp_planes)
    planes = sds((5 if mode == "none" else 3, NB_TP, 128), jnp.int32)

    def sweep_pruned(rects, amps, mbr, mamp, mmass, s, e, qr, qa, planes):
        return jax.vmap(
            lambda s, e, qr, qa: sweep_score_pruned(
                rects, amps, mbr, mamp, mmass, s, e, qr, qa,
                KB.sweep_budget, KB.max_candidates, 128, 0.0,
                interpret=False, planes=planes,
            )
        )(s, e, qr, qa)

    hlo = _compile(sweep_pruned, rects, amps, *meta, *_query(sds), planes)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("monotone", [False, True], ids=["docid", "impact"])
@pytest.mark.parametrize("impact_dtype", ["float32", "float16"])
def test_text_probe_pruned_compiles(one_chip, impact_dtype, monotone):
    import numpy as np

    from repro.core.text_index import impact_plane_np
    from repro.kernels.text_probe.ops import text_probe_pruned

    # the plane the index builds for impacts stored in ``impact_dtype``
    plane_dtype = impact_plane_np(
        np.zeros((1,), impact_dtype), np.zeros((1,), np.int32),
        np.ones((1,), np.int32),
    ).dtype

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def text_pruned(plane, blk_len, blk_max, b0, nb, w, rest, floor):
        return jax.vmap(
            lambda b0, nb, rest, floor: text_probe_pruned(
                plane, blk_max, blk_len, b0, nb, w, rest, floor,
                max_candidates=KB.max_candidates,
                max_term_blocks=MAX_TERM_BLOCKS,
                interpret=False, monotone=monotone,
            )
        )(b0, nb, rest, floor)

    hlo = _compile(
        text_pruned,
        sds((NB_POST, 128), plane_dtype),
        sds((NB_POST,), jnp.int32),
        sds((NB_POST,), jnp.float32),
        sds((B,), jnp.int32),
        sds((B,), jnp.int32),
        sds((), jnp.float32),
        sds((B,), jnp.float32),
        sds((B,), jnp.float32),
    )
    assert "tpu_custom_call" in hlo


def test_geo_score_compiles(one_chip):
    from repro.kernels.geo_score.ops import geo_score_docs

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    R = CONFIG.doc_major_rects

    def geo_docs(rects, amps, qr, qa):
        return jax.vmap(
            lambda qr, qa: geo_score_docs(rects, amps, qr, qa, interpret=False)
        )(qr, qa)

    hlo = _compile(
        geo_docs,
        sds((KB.max_candidates, R, 4), jnp.float32),
        sds((KB.max_candidates, R), jnp.float32),
        sds((B, CONFIG.q_rects, 4), jnp.float32),
        sds((B, CONFIG.q_rects), jnp.float32),
    )
    assert "tpu_custom_call" in hlo


def test_tile_intervals_compiles(one_chip):
    from repro.kernels.tile_intervals.kernel import tile_gaps_planar

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    G = CONFIG.grid
    patches = (G // 8) * (G // 128)
    C = 1 << 28  # candidate (patch, toe print) pairs: ~2^28 at this shard

    def tile_gaps(chunks, counts, ids, packed):
        return tile_gaps_planar(
            chunks, counts, ids, packed, G // 8, G // 128,
            max(CONFIG.m_intervals - 1, 1), interpret=False,
        )

    hlo = _compile(
        tile_gaps,
        sds((patches,), jnp.int32),
        sds((patches,), jnp.int32),
        sds((C,), jnp.int32),
        sds((C,), jnp.int32),
    )
    assert "tpu_custom_call" in hlo
