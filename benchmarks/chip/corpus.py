"""The benchmark's own corpus and query generators.

Copied from ``repro.corpus.synth`` (``make_corpus``, ``zipf_terms``,
``_one_query``) so that a change to the program cannot move the yardstick.
The arithmetic and the order of the random draws are those of the
originals: the same seed gives the same documents.  ``traffic.py`` draws
``make_zipf_trace``'s pool of searches with these; how often each is asked
is its own.  Queries
are plain objects with the four fields the server reads (``terms``,
``rects``, ``amps``, ``arrival_s``); nothing here imports the program.
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass

import numpy as np

HOST_THREADS = 8  # worker threads of the term sampler (numpy drops the GIL)
EMPTY_RECT = (1.0, 1.0, 0.0, 0.0)  # x1 < x0: an empty slot


@dataclass
class Corpus:
    doc_terms: np.ndarray  # i32[N, doc_len] term ids (repeats = frequencies)
    doc_rects: np.ndarray  # f32[N, R, 4]
    doc_amps: np.ndarray  # f32[N, R]
    pagerank: np.ndarray  # f32[N]
    n_terms: int
    cities: np.ndarray  # f32[C, 3]: x, y, radius


@dataclass
class Query:
    terms: np.ndarray  # i32[d], sorted, distinct
    rects: np.ndarray  # f32[r, 4]
    amps: np.ndarray  # f32[r]
    arrival_s: float = 0.0


def _zipf_tail(a: float, n: int) -> float:
    """``sum_{j >= n} j^-a`` (a > 1): 10^5 terms summed, the rest by
    Euler-Maclaurin."""
    K = 100_000
    j = np.arange(n, n + K, dtype=np.float64)
    m = float(n + K)
    em = (
        m ** (1 - a) / (a - 1)
        + 0.5 * m**-a
        + a * m ** (-a - 1) / 12
        - a * (a + 1) * (a + 2) * m ** (-a - 3) / 720
    )
    return float(np.sum(j**-a)) + em


def _zipf_pmf(zipf_a: float, n_terms: int) -> np.ndarray:
    """Probabilities of ``min(zipf(zipf_a) - 1, n_terms - 1)``."""
    head = np.arange(1, n_terms, dtype=np.float64) ** -zipf_a
    tail = _zipf_tail(zipf_a, n_terms)
    return np.append(head, tail) / (head.sum() + tail)


def _alias_table(p: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Walker/Vose alias table for the pmf ``p``."""
    n = len(p)
    q = p * n
    prob = np.ones((n,), np.float64)
    alias = np.arange(n, dtype=np.int32)
    small = list(np.flatnonzero(q < 1.0))
    large = list(np.flatnonzero(q >= 1.0))
    q = q.tolist()
    while small and large:
        s, g = small.pop(), large[-1]
        prob[s], alias[s] = q[s], g
        q[g] -= 1.0 - q[s]
        if q[g] < 1.0:
            small.append(large.pop())
    return prob, alias


def doc_len_for_postings(postings: float, n_terms: int, zipf_a: float) -> int:
    """Fewest Zipf draws per document whose expected number of distinct
    terms reaches ``postings`` (postings per document)."""
    p = _zipf_pmf(zipf_a, n_terms)

    def distinct(n):
        return float(np.sum(-np.expm1(n * np.log1p(-p))))

    lo, hi = 1, max(int(postings), 1)
    while distinct(hi) < postings:
        lo, hi = hi, hi * 2
    while lo < hi:
        mid = (lo + hi) // 2
        lo, hi = (mid + 1, hi) if distinct(mid) < postings else (lo, mid)
    return lo


def zipf_terms(rng, shape, zipf_a: float, n_terms: int) -> np.ndarray:
    """i32 term ids distributed as ``min(zipf(zipf_a) - 1, n_terms - 1)``,
    by an alias table, in chunks of rows each from its own spawned
    generator (the result depends on the seed, not on the threads)."""
    prob, alias = _alias_table(_zipf_pmf(zipf_a, n_terms))
    n_rows, n_cols = shape
    out = np.empty(shape, np.int32)
    rows = 1 << 14
    starts = range(0, n_rows, rows)
    gens = rng.spawn(len(starts))

    def fill(i):
        r0 = starts[i]
        r1 = min(r0 + rows, n_rows)
        g = gens[i]
        cand = g.integers(0, n_terms, (r1 - r0, n_cols), dtype=np.int32)
        keep = g.random((r1 - r0, n_cols)) < prob[cand]
        out[r0:r1] = np.where(keep, cand, alias[cand])

    with ThreadPoolExecutor(HOST_THREADS) as pool:
        list(pool.map(fill, range(len(starts))))
    return out


def make_corpus(config: dict, seed) -> Corpus:
    """Corpus of ``config["n_docs"]`` documents from ``seed``.

    Each document holds ``doc_len`` Zipf term draws and 1..R places, each
    an address-style (small, high amplitude) or town-style (larger, low
    amplitude) rect around a population-weighted city.
    """
    n_docs, n_terms = config["n_docs"], config["n_terms"]
    n_cities, max_rects = config["n_cities"], config["doc_major_rects"]
    zipf_a = config["term_zipf_a"]
    doc_len = doc_len_for_postings(config["avg_postings_per_doc"], n_terms, zipf_a)
    rng = np.random.default_rng(seed)
    cx = rng.uniform(0.05, 0.95, n_cities)
    cy = rng.uniform(0.05, 0.95, n_cities)
    pop = rng.zipf(1.5, n_cities).astype(np.float64)
    pop = pop / pop.max()
    radius = 0.01 + 0.06 * np.sqrt(pop)
    cities = np.stack([cx, cy, radius], axis=1).astype(np.float32)
    city_p = pop / pop.sum()

    doc_terms = zipf_terms(rng, (n_docs, doc_len), zipf_a, n_terms)

    shape = (n_docs, max_rects)
    n_places = rng.integers(1, max_rects + 1, n_docs)
    cdf = np.cumsum(city_p)
    city = np.minimum(
        np.searchsorted(cdf, rng.random(shape) * cdf[-1], "right"), n_cities - 1
    )
    x, y, r = (cities[city, i].astype(np.float64) for i in range(3))
    address = rng.random(shape) < 0.5
    w = r * np.where(
        address, rng.uniform(0.05, 0.2, shape), rng.uniform(0.5, 1.5, shape)
    )
    amp = np.where(address, rng.uniform(0.7, 1.0, shape), rng.uniform(0.2, 0.6, shape))
    px = np.clip(x + rng.normal(0, 1, shape) * (r / 2), 0.001, 0.999)
    py = np.clip(y + rng.normal(0, 1, shape) * (r / 2), 0.001, 0.999)
    x0, x1 = np.clip(px - w, 0, 1), np.clip(px + w, 0, 1)
    y0, y1 = np.clip(py - w, 0, 1), np.clip(py + w, 0, 1)
    ok = (np.arange(max_rects)[None, :] < n_places[:, None]) & (x1 > x0) & (y1 > y0)
    rects = np.where(
        ok[..., None], np.stack([x0, y0, x1, y1], axis=-1), np.array(EMPTY_RECT)
    ).astype(np.float32)
    amps = np.where(ok, amp, 0.0).astype(np.float32)

    pagerank = rng.pareto(2.0, n_docs).astype(np.float32)
    pagerank = pagerank / max(pagerank.max(), 1e-9)
    return Corpus(doc_terms, rects, amps, pagerank, n_terms, cities)


def relabel_terms(corpus: Corpus, seed) -> Corpus:
    """The corpus with its term ids renamed by a permutation drawn from
    ``seed``.  Every posting list, document frequency and score keeps its
    size; only the names and the order of the terms change."""
    perm = np.random.default_rng(seed).permutation(corpus.n_terms).astype(np.int32)
    src = corpus.doc_terms
    out = np.empty_like(src)
    rows = 1 << 16

    def fill(r0):
        out[r0 : r0 + rows] = perm[src[r0 : r0 + rows]]

    with ThreadPoolExecutor(HOST_THREADS) as pool:
        list(pool.map(fill, range(0, len(src), rows)))
    return Corpus(out, corpus.doc_rects, corpus.doc_amps, corpus.pagerank,
                  corpus.n_terms, corpus.cities)


def footprint(rng, city_xyr, q_rects: int, scales) -> tuple[np.ndarray, np.ndarray]:
    """1..q_rects rects about a city at the given extents (in city radii);
    a draw that clips to nothing is dropped, and an all-empty draw becomes
    the whole-city rect."""
    x, y, r = city_xyr
    scales = np.asarray(scales)
    rects, amps = [], []
    for _ in range(int(rng.integers(1, q_rects + 1))):
        w = r * scales[rng.integers(0, len(scales))] * rng.uniform(0.5, 1.0)
        px = np.clip(x + rng.normal(0, r / 4), 0.001, 0.999)
        py = np.clip(y + rng.normal(0, r / 4), 0.001, 0.999)
        x0, x1 = np.clip(px - w, 0, 1), np.clip(px + w, 0, 1)
        y0, y1 = np.clip(py - w, 0, 1), np.clip(py + w, 0, 1)
        if x1 <= x0 or y1 <= y0:
            continue
        rects.append((x0, y0, x1, y1))
        amps.append(1.0)
    if not rects:
        rects, amps = [(x - r, y - r, x + r, y + r)], [1.0]
    return np.asarray(rects, dtype=np.float32), np.asarray(amps, dtype=np.float32)
