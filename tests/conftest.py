import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.text_index import PFOR_SLOT_BITS, POSTING_BLOCK


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


def random_rects(rng, n):
    """n random well-formed rects in the unit square, (n, 4) f32."""
    lo = rng.uniform(0, 0.9, (n, 2))
    hi = lo + rng.uniform(0.01, 0.1, (n, 2))
    return np.concatenate([lo, np.minimum(hi, 1.0)], axis=1).astype(np.float32)


def _bit_extract_decode(index, blocks):
    """Decode packed blocks from ``post_packed`` alone — i32[..., 128].

    The reference for ``TextIndex.doc_plane``: shift/mask extraction of
    each block's 128 base-width deltas, a replay of the block's PForDelta
    patch list (each patch word restores one delta's high bits), then a
    prefix sum from ``blk_first``.  Slots past ``blk_len`` are garbage:
    blocks are stored tail-trimmed, so those reads fall into the exception
    words or the next block.
    """
    bits = index.blk_bits[blocks]  # [...]
    w0 = index.blk_word_off[blocks]
    j = jnp.arange(POSTING_BLOCK, dtype=jnp.int32)
    bitpos = j * bits[..., None]  # [..., 128]
    word = w0[..., None] + (bitpos >> 5)
    off = (bitpos & 31).astype(jnp.uint32)
    W = max(index.post_packed.shape[0], 1)
    lo_w = index.post_packed[jnp.clip(word, 0, W - 1)]
    hi_w = index.post_packed[jnp.clip(word + 1, 0, W - 1)]
    # two-word extraction; the hi shift amount stays < 32 via the mask and
    # the off == 0 case (where 32 - off would be 32) selects 0 anyway
    hi_part = jnp.where(
        off > 0, hi_w << ((jnp.uint32(32) - off) & jnp.uint32(31)), jnp.uint32(0)
    )
    mask = (jnp.uint32(1) << bits[..., None].astype(jnp.uint32)) - 1  # bits ≤ 31
    delta = (((lo_w >> off) | hi_part) & mask).astype(jnp.int32)
    delta = jnp.where(j == 0, 0, delta)
    # exception words live right after the block's tail-trimmed base words
    n_exc = index.blk_n_exc[blocks]  # [...]
    base_words = jnp.maximum((index.blk_len[blocks] * bits + 31) >> 5, 1)
    ew0 = w0 + base_words

    def _patch(e, d):
        pw = index.post_packed[jnp.clip(ew0 + e, 0, W - 1)]  # [...]
        slot = (pw & jnp.uint32((1 << PFOR_SLOT_BITS) - 1)).astype(jnp.int32)
        high = (pw >> jnp.uint32(PFOR_SLOT_BITS)).astype(jnp.int32)
        add = jnp.where(e < n_exc, high << bits, 0)  # [...]
        return d + jnp.where(j == slot[..., None], add[..., None], 0)

    delta = jax.lax.fori_loop(0, jnp.max(n_exc), _patch, delta)
    return index.blk_first[blocks][..., None] + jnp.cumsum(delta, axis=-1)


@pytest.fixture(scope="session")
def bit_extract_decode():
    """The bit-extracting decoder of the packed posting store (the query
    path reads ``doc_plane`` instead; tests pin the two together)."""
    return _bit_extract_decode
