"""A pool of searches asked under Zipf ranks (the mix ``zipf``).

Parameters of the mix file:

* ``pool_size`` / ``pool_seed`` — distinct searches in a pool drawn once
  from the fixed ``pool_seed``;
* ``zipf_a`` — the pool is asked with Zipf ranks truncated to the pool:
  P(rank k) proportional to k^-zipf_a for k = 1..pool_size;
* ``hot_frac`` / ``n_hot_cities`` — share of searches about the largest
  cities;
* ``d_terms`` / ``q_rects`` / ``scales`` — 1..d_terms terms drawn from one
  document, 1..q_rects rects, and the rect extents in city radii.

The pool is ``repro.corpus.make_zipf_trace``'s pool for the seed
``pool_seed``, draw for draw.  Each chunk's multiset of searches is drawn
from ``pool_seed`` too; the run's seed only orders the searches inside
each chunk.  So every seed does the same work, chunk by chunk.
"""
from __future__ import annotations

import numpy as np

from benchmarks.chip.corpus import Query, footprint
from benchmarks.chip.traffic import shuffle_chunks, zipf_pmf


def _doc_terms(rng, corpus, d_terms: int) -> np.ndarray:
    nt = int(rng.integers(1, d_terms + 1))
    doc = corpus.doc_terms[rng.integers(0, len(corpus.doc_terms))]
    return np.unique(rng.choice(doc, size=min(nt, len(doc)), replace=False))


def generate(corpus, mix: dict, seed) -> list[Query]:
    """The stream of ``mix["queries"]`` queries over ``corpus``: the same
    searches in every chunk for every ``seed``, in the order ``seed``
    draws inside the chunk."""
    rng = np.random.default_rng(mix["pool_seed"])
    n_cities = len(corpus.cities)
    hot = np.argsort(-corpus.cities[:, 2])[: mix["n_hot_cities"]]

    def one() -> Query:
        if rng.random() < mix["hot_frac"]:
            city = int(hot[rng.integers(0, len(hot))])
        else:
            city = int(rng.integers(0, n_cities))
        terms = _doc_terms(rng, corpus, mix["d_terms"])
        rects, amps = footprint(rng, corpus.cities[city], mix["q_rects"], mix["scales"])
        return Query(terms.astype(np.int32), rects, amps)

    pool_size = mix["pool_size"]
    pool = [one() for _ in range(pool_size)]
    ranks = rng.choice(pool_size, size=mix["queries"], p=zipf_pmf(mix["zipf_a"], pool_size))
    shuffle_chunks(ranks, mix["chunk"], seed)
    return [pool[r] for r in ranks]
