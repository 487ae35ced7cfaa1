"""Synthetic geo web corpus + query traces.

Models the workload of the paper's evaluation (a national-domain crawl with
extracted footprints, plus a realistic geographic query trace):

* **Places**: ``n_cities`` city centers in the unit square with power-law
  populations; each city has a radius ~ sqrt(population).
* **Documents**: term ids drawn from a Zipf distribution over ``n_terms``;
  each document is "about" 1–3 places — its footprint is 1..R rectangles
  around those places (complete-address-style small rects with high
  amplitude, town-name-style larger rects with low amplitude — paper fig. 1
  split footprints).  A fraction of documents is non-geographic (empty
  footprint never happens here: the paper's engine only indexes docs with
  footprints; non-geo docs get a country-wide low-amplitude rect).
* **Queries**: ``d`` terms from the same Zipf head + a footprint around a
  random city with town/city/region extent.
* **Traces** (``make_zipf_trace``): a *stream* of variable-width queries
  with Zipf-skewed repetition over a finite pool of distinct searches and
  geographic hot-spot locality — the workload shape the serving layer's
  cache and batcher are designed for.
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, replace

import numpy as np

from repro.core.algorithms import QueryBatch
from repro.core.geometry import HOST_THREADS
import jax.numpy as jnp


@dataclass
class SynthCorpus:
    doc_terms: np.ndarray  # i32[N, doc_len] term ids (repeats = frequencies)
    doc_rects: np.ndarray  # [N, R, 4]
    doc_amps: np.ndarray  # [N, R]
    pagerank: np.ndarray  # [N]
    n_terms: int
    cities: np.ndarray  # [C, 3]: x, y, radius


def _zipf_tail(a: float, n: int) -> float:
    """``sum_{j >= n} j^-a`` (a > 1): 10^5 terms summed, the rest by
    Euler–Maclaurin (error far below float64 resolution)."""
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


def _alias_table(p: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Walker/Vose alias table for the pmf ``p`` (O(n), exact)."""
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


def _zipf_pmf(zipf_a: float, n_terms: int) -> np.ndarray:
    """Probabilities of ``min(zipf(zipf_a) - 1, n_terms - 1)``: the last
    term takes the whole clipped tail."""
    head = np.arange(1, n_terms, dtype=np.float64) ** -zipf_a
    tail = _zipf_tail(zipf_a, n_terms)
    return np.append(head, tail) / (head.sum() + tail)


def doc_len_for_postings(postings: float, n_terms: int, zipf_a: float = 1.3) -> int:
    """Fewest Zipf draws per document whose expected number of *distinct*
    terms (= postings per document) reaches ``postings``.

    A configuration states postings per document; :func:`make_corpus`
    draws terms with repetition, so it needs more draws than postings.
    """
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


def zipf_terms(
    rng: np.random.Generator, shape: tuple[int, int], zipf_a: float, n_terms: int
) -> np.ndarray:
    """i32 term ids distributed as ``min(zipf(zipf_a) - 1, n_terms - 1)``.

    Sampled by an alias table over the exact probabilities (the last term
    takes the whole clipped tail) rather than by ``rng.zipf``'s rejection
    loop, in fixed chunks of rows, each from its own spawned generator, on
    a thread pool — the result depends on the seed, not on the threads.
    """
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


def make_corpus(
    n_docs: int = 2000,
    n_terms: int = 500,
    n_cities: int = 32,
    max_rects: int = 4,
    doc_len: int = 32,
    zipf_a: float = 1.3,
    seed: int = 0,
) -> SynthCorpus:
    """Synthetic corpus (see the module docstring), vectorised over docs.

    ``doc_terms`` is one ``[n_docs, doc_len]`` matrix of Zipf term ids.
    Each doc has 1..``max_rects`` places, each an address-style (small,
    high amplitude) or town-style (larger, low amplitude) rect around a
    population-weighted city; a draw that clips to nothing leaves its
    slot empty.
    """
    rng = np.random.default_rng(seed)
    # cities: power-law sizes
    cx = rng.uniform(0.05, 0.95, n_cities)
    cy = rng.uniform(0.05, 0.95, n_cities)
    pop = rng.zipf(1.5, n_cities).astype(np.float64)
    pop = pop / pop.max()
    radius = 0.01 + 0.06 * np.sqrt(pop)
    cities = np.stack([cx, cy, radius], axis=1).astype(np.float32)
    city_p = pop / pop.sum()

    doc_terms = zipf_terms(rng, (n_docs, doc_len), zipf_a, n_terms)

    # footprints: one candidate place per slot, the first n_places kept
    shape = (n_docs, max_rects)
    n_places = rng.integers(1, max_rects + 1, n_docs)
    cdf = np.cumsum(city_p)
    city = np.minimum(np.searchsorted(cdf, rng.random(shape) * cdf[-1], "right"),
                      n_cities - 1)
    x, y, r = (cities[city, i].astype(np.float64) for i in range(3))
    address = rng.random(shape) < 0.5
    w = r * np.where(address, rng.uniform(0.05, 0.2, shape), rng.uniform(0.5, 1.5, shape))
    amp = np.where(address, rng.uniform(0.7, 1.0, shape), rng.uniform(0.2, 0.6, shape))
    px = np.clip(x + rng.normal(0, 1, shape) * (r / 2), 0.001, 0.999)
    py = np.clip(y + rng.normal(0, 1, shape) * (r / 2), 0.001, 0.999)
    x0, x1 = np.clip(px - w, 0, 1), np.clip(px + w, 0, 1)
    y0, y1 = np.clip(py - w, 0, 1), np.clip(py + w, 0, 1)
    ok = (np.arange(max_rects)[None, :] < n_places[:, None]) & (x1 > x0) & (y1 > y0)
    rects = np.where(
        ok[..., None],
        np.stack([x0, y0, x1, y1], axis=-1),
        np.array([1.0, 1.0, 0.0, 0.0]),  # empty-rect padding (x1 < x0)
    ).astype(np.float32)
    amps = np.where(ok, amp, 0.0).astype(np.float32)

    pagerank = rng.pareto(2.0, n_docs).astype(np.float32)
    pagerank = pagerank / max(pagerank.max(), 1e-9)
    return SynthCorpus(doc_terms, rects, amps, pagerank, n_terms, cities)


def make_query_trace(
    corpus: SynthCorpus,
    n_queries: int = 64,
    d_terms: int = 4,
    q_rects: int = 2,
    zipf_a: float = 1.3,
    seed: int = 1,
    from_docs: bool = True,
) -> QueryBatch:
    """Query trace: terms + footprints around cities.

    ``from_docs=True`` samples query terms from a random document (queries
    correlate with content, every conjunction has ≥ 1 match — realistic
    trace); otherwise draws independent Zipf terms.  Extents mix town
    (~0.3·r), city (~1·r) and region (~3·r) scales, matching the paper's
    town/city/region query classes.
    """
    rng = np.random.default_rng(seed)
    n_cities = len(corpus.cities)
    terms = np.full((n_queries, d_terms), -1, dtype=np.int32)
    rects = np.zeros((n_queries, q_rects, 4), dtype=np.float32)
    rects[:, :, 0] = 1.0
    rects[:, :, 1] = 1.0
    amps = np.zeros((n_queries, q_rects), dtype=np.float32)
    scales = np.array([0.3, 1.0, 3.0])
    for i in range(n_queries):
        nt = rng.integers(1, d_terms + 1)
        if from_docs:
            doc = corpus.doc_terms[rng.integers(0, len(corpus.doc_terms))]
            t = np.unique(rng.choice(doc, size=min(nt, len(doc)), replace=False))
        else:
            t = np.unique(np.minimum(rng.zipf(zipf_a, nt) - 1, corpus.n_terms - 1))
        terms[i, : len(t)] = t
        c = rng.integers(0, n_cities)
        x, y, r = corpus.cities[c]
        nr = rng.integers(1, q_rects + 1)
        for j in range(nr):
            w = r * scales[rng.integers(0, 3)] * rng.uniform(0.5, 1.0)
            px = np.clip(x + rng.normal(0, r / 4), 0.001, 0.999)
            py = np.clip(y + rng.normal(0, r / 4), 0.001, 0.999)
            x0, x1 = np.clip(px - w, 0, 1), np.clip(px + w, 0, 1)
            y0, y1 = np.clip(py - w, 0, 1), np.clip(py + w, 0, 1)
            if x1 <= x0 or y1 <= y0:
                continue
            rects[i, j] = (x0, y0, x1, y1)
            amps[i, j] = 1.0
    return QueryBatch(
        terms=jnp.asarray(terms), rects=jnp.asarray(rects), amps=jnp.asarray(amps)
    )


@dataclass
class TraceQuery:
    """One un-padded query in a serving trace (variable widths).

    ``arrival_s`` stamps when the query enters the system (seconds from
    trace start).  Closed-loop replay ignores it; open-loop replay
    (:meth:`repro.serving.server.GeoServer.run_trace` with
    ``arrival != "closed"``) releases queries at these times regardless of
    server progress, which is what makes tail latency under load visible.
    """

    terms: np.ndarray  # i32[d], no padding
    rects: np.ndarray  # f32[r, 4]
    amps: np.ndarray  # f32[r]
    arrival_s: float = 0.0


def _one_query(
    rng, corpus: SynthCorpus, city: int, d_terms: int, q_rects: int,
    scales: tuple = (0.3, 1.0, 3.0),
):
    """Sample one variable-width query about ``city`` (terms from a doc)."""
    nt = int(rng.integers(1, d_terms + 1))
    doc = corpus.doc_terms[rng.integers(0, len(corpus.doc_terms))]
    terms = np.unique(rng.choice(doc, size=min(nt, len(doc)), replace=False))
    x, y, r = corpus.cities[city]
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
    if not rects:  # degenerate draw: whole-city rect
        rects, amps = [(x - r, y - r, x + r, y + r)], [1.0]
    return TraceQuery(
        terms=terms.astype(np.int32),
        rects=np.asarray(rects, dtype=np.float32),
        amps=np.asarray(amps, dtype=np.float32),
    )


def make_zipf_trace(
    corpus: SynthCorpus,
    n_queries: int = 2048,
    pool_size: int = 256,
    zipf_a: float = 1.1,
    hot_frac: float = 0.8,
    n_hot_cities: int = 4,
    d_terms: int = 4,
    q_rects: int = 2,
    seed: int = 1,
    scales: tuple = (0.3, 1.0, 3.0),
) -> list[TraceQuery]:
    """Skewed serving trace: Zipf repetition + geographic hot spots.

    A pool of ``pool_size`` distinct queries is built first; ``hot_frac``
    of them are about one of the ``n_hot_cities`` largest cities (the
    paper's observation that geographic query load concentrates on big
    population centers).  The trace then samples the pool with Zipf(``a``)
    rank skew, so head queries repeat heavily — the regime where a result
    cache pays for itself — while the tail keeps the batcher honest.

    ``scales`` sets the footprint-extent mix in city radii; the default
    matches the paper's town (0.3·r) / city (1·r) / region (3·r) query
    classes, and ``scales=(1.0,)`` pins a city-sized workload (the
    footprint-routing benches).
    """
    rng = np.random.default_rng(seed)
    hot = np.argsort(-corpus.cities[:, 2])[:n_hot_cities]
    pool = []
    for _ in range(pool_size):
        if rng.random() < hot_frac:
            city = int(hot[rng.integers(0, len(hot))])
        else:
            city = int(rng.integers(0, len(corpus.cities)))
        pool.append(_one_query(rng, corpus, city, d_terms, q_rects, scales))
    # Zipf over pool ranks (rejection-free: clip the unbounded tail)
    ranks = np.minimum(rng.zipf(zipf_a, n_queries) - 1, pool_size - 1)
    return [pool[r] for r in ranks]


# ---------------------------------------------------------------------------
# arrival processes
# ---------------------------------------------------------------------------

ARRIVAL_KINDS = ("closed", "poisson", "bursty", "diurnal")


def make_arrivals(
    kind: str,
    n: int,
    rate_qps: float = 200.0,
    seed: int = 0,
    burst_factor: float = 4.0,
    on_frac: float = 0.1,
    diurnal_period_s: float = 60.0,
    diurnal_depth: float = 0.8,
) -> np.ndarray:
    """Arrival-time stamps (seconds, non-decreasing, f64[n]) for a stream.

    * ``closed``  — all zeros; the replay loop ignores them (next query is
      released when the previous one finishes — PR 1 behavior).
    * ``poisson`` — open-loop Poisson process at ``rate_qps``: i.i.d.
      exponential inter-arrivals, the memoryless baseline load model.
    * ``bursty``  — two-state MMPP (on/off Markov-modulated Poisson): an ON
      state firing at ``burst_factor × rate_qps`` for ~``on_frac`` of the
      time, and an OFF state at the complementary rate so the *mean* rate
      stays ``rate_qps``.  Dwell times in each state are exponential with
      mean ``diurnal_period_s / 10`` (bursts are short relative to the
      diurnal swing).  This is the flash-crowd regime where deadline-based
      flushing earns its keep.
    * ``diurnal`` — inhomogeneous Poisson with a sinusoidal rate profile
      ``rate_qps · (1 + diurnal_depth · sin(2πt / diurnal_period_s))``,
      generated by thinning; models the day/night swing of a geoportal.

    ``burst_factor · on_frac`` must be < 1 so the OFF rate stays positive.
    """
    if kind not in ARRIVAL_KINDS:
        raise ValueError(f"unknown arrival kind {kind!r}; want one of {ARRIVAL_KINDS}")
    if kind == "closed":
        return np.zeros(n, dtype=np.float64)
    if rate_qps <= 0:
        raise ValueError("rate_qps must be > 0 for open-loop arrivals")
    rng = np.random.default_rng(seed)
    if kind == "poisson":
        return np.cumsum(rng.exponential(1.0 / rate_qps, n))
    if kind == "bursty":
        if not 0.0 < on_frac < 1.0:
            raise ValueError("on_frac must be in (0, 1)")
        if burst_factor * on_frac >= 1.0:
            raise ValueError("burst_factor * on_frac must be < 1 (mean-rate budget)")
        rate_on = burst_factor * rate_qps
        rate_off = (1.0 - burst_factor * on_frac) * rate_qps / (1.0 - on_frac)
        mean_dwell = diurnal_period_s / 10.0
        out = np.empty(n, dtype=np.float64)
        t, i, on = 0.0, 0, False
        state_end = t + rng.exponential(mean_dwell * (1.0 - on_frac))
        while i < n:
            rate = rate_on if on else rate_off
            nxt = t + rng.exponential(1.0 / rate)
            if nxt >= state_end:
                # no arrival before the state switch; restart the clock in
                # the new state (exponential dwell ⇒ memoryless, so this is
                # an exact simulation, not an approximation)
                t, on = state_end, not on
                state_end = t + rng.exponential(
                    mean_dwell * (on_frac if on else 1.0 - on_frac)
                )
                continue
            t = nxt
            out[i] = t
            i += 1
        return out
    # diurnal: thinning against the peak rate
    rate_max = rate_qps * (1.0 + diurnal_depth)
    out = np.empty(n, dtype=np.float64)
    t, i = 0.0, 0
    while i < n:
        t += rng.exponential(1.0 / rate_max)
        rate_t = rate_qps * (
            1.0 + diurnal_depth * np.sin(2.0 * np.pi * t / diurnal_period_s)
        )
        if rng.random() * rate_max < rate_t:
            out[i] = t
            i += 1
    return out


def stamp_arrivals(
    trace: list[TraceQuery],
    kind: str = "poisson",
    rate_qps: float = 200.0,
    seed: int = 0,
    **kw,
) -> list[TraceQuery]:
    """Return a copy of ``trace`` with ``arrival_s`` stamped by ``kind``."""
    times = make_arrivals(kind, len(trace), rate_qps=rate_qps, seed=seed, **kw)
    return [replace(q, arrival_s=float(t)) for q, t in zip(trace, times)]


def term_document_frequencies(corpus: SynthCorpus) -> np.ndarray:
    """Per-term document frequency (docs containing the term), f64[n_terms]."""
    from repro.core.text_index import document_frequencies_np

    return document_frequencies_np(corpus.doc_terms, corpus.n_terms)


def make_mixture_trace(
    corpus: SynthCorpus,
    n_queries: int = 2048,
    rare_frac: float = 0.5,
    rare_df_max: int = 4,
    hot_quantile: float = 0.92,
    seed: int = 1,
) -> list[TraceQuery]:
    """Bimodal term-selectivity × footprint-area workload (planner stressor).

    Two query populations, mixed ``rare_frac`` / ``1 - rare_frac``:

    * **rare + huge** — one very rare term (df ≤ ``rare_df_max``) over a
      country-sized footprint.  The inverted index pins the answer set to a
      handful of docs while the spatial structures see almost the whole
      toe-print store: TEXT-FIRST territory, and catastrophic for GEO-FIRST
      / K-SWEEP (they stream/enumerate nearly everything).
    * **hot + tiny** — 2–3 of the collection's hottest terms (df above the
      ``hot_quantile``) over a city-block footprint centered on a real
      document's footprint (so the conjunction has a co-located match).
      Anchor documents are drawn from the *sparse* tail of the geographic
      density distribution — where the tile grid's intervals are tight —
      so the spatial index pins the candidates to a few toe prints while
      every posting list is huge: GEO-FIRST territory, and wasteful for
      TEXT-FIRST (its driver list is long regardless of the footprint).

    No single fixed algorithm is close to per-query selection on this
    workload — the cost-based planner's acceptance trace
    (``benchmarks/run.py::planner_mixture_*``).
    """
    rng = np.random.default_rng(seed)
    df = term_document_frequencies(corpus)
    rare_terms = np.nonzero((df >= 1) & (df <= rare_df_max))[0]
    if len(rare_terms) == 0:  # tiny corpora: fall back to the rarest decile
        order = np.argsort(df + np.where(df < 1, np.inf, 0.0))
        rare_terms = order[: max(corpus.n_terms // 10, 1)]
    hot_cut = np.quantile(df[df > 0], hot_quantile)
    hot_set = set(np.nonzero(df >= max(hot_cut, 2))[0].tolist())
    # geographic crowding per cell: how many footprint rects INTERSECT each
    # cell of a coarse grid (2D difference trick + cumsum = integral image).
    # Hot+tiny queries anchor on doc rects in the emptiest cells — exactly
    # where the tile grid's intervals are tight and a spatial-first plan
    # touches a handful of toe prints.
    G = 64
    N, R, _ = corpus.doc_rects.shape
    rects_flat = corpus.doc_rects.reshape(-1, 4)
    valid_flat = rects_flat[:, 2] > rects_flat[:, 0]
    vx0 = np.clip((rects_flat[:, 0] * G).astype(np.int64), 0, G - 1)
    vy0 = np.clip((rects_flat[:, 1] * G).astype(np.int64), 0, G - 1)
    vx1 = np.clip((rects_flat[:, 2] * G).astype(np.int64), 0, G - 1)
    vy1 = np.clip((rects_flat[:, 3] * G).astype(np.int64), 0, G - 1)
    diff = np.zeros((G + 1, G + 1))
    w = valid_flat.astype(np.float64)
    np.add.at(diff, (vy0, vx0), w)
    np.add.at(diff, (vy1 + 1, vx0), -w)
    np.add.at(diff, (vy0, vx1 + 1), -w)
    np.add.at(diff, (vy1 + 1, vx1 + 1), w)
    crowd = diff.cumsum(axis=0).cumsum(axis=1)[:G, :G]  # [iy, ix]
    # per doc: its least-crowded valid rect (anchor) and that crowding
    cx = ((rects_flat[:, 0] + rects_flat[:, 2]) * 0.5 * G).astype(np.int64)
    cy = ((rects_flat[:, 1] + rects_flat[:, 3]) * 0.5 * G).astype(np.int64)
    rect_crowd = np.where(
        valid_flat,
        crowd[np.clip(cy, 0, G - 1), np.clip(cx, 0, G - 1)],
        np.inf,
    ).reshape(N, R)
    anchor_rect = rect_crowd.argmin(axis=1)
    anchor_crowd = rect_crowd.min(axis=1)
    finite = np.isfinite(anchor_crowd)
    cut = np.quantile(anchor_crowd[finite], 0.15) if finite.any() else np.inf
    quiet_docs = np.nonzero(finite & (anchor_crowd <= cut))[0]
    if len(quiet_docs) == 0:
        quiet_docs = np.nonzero(finite)[0]
    out = []
    for _ in range(n_queries):
        if rng.random() < rare_frac:
            # rare + huge: one rare term, near-domain-wide footprint
            t = np.array([rare_terms[rng.integers(0, len(rare_terms))]], np.int32)
            w = rng.uniform(0.25, 0.45)
            qx, qy = rng.uniform(0.35, 0.65, 2)
            rect = (
                max(qx - w, 0.0), max(qy - w, 0.0),
                min(qx + w, 1.0), min(qy + w, 1.0),
            )
        else:
            # hot + tiny: the doc's hottest terms, city-block footprint at
            # the doc's least-crowded footprint rect (guaranteed overlap,
            # tight tile intervals)
            while True:
                d_i = int(quiet_docs[rng.integers(0, len(quiet_docs))])
                cand = np.unique(corpus.doc_terms[d_i])
                hot = cand[np.isin(cand, list(hot_set))] if hot_set else cand
                if len(hot) == 0:  # fall back to the doc's highest-df terms
                    hot = cand[np.argsort(-df[cand])][:3]
                if len(hot):
                    break
            nt = int(rng.integers(2, 4))
            t = np.sort(rng.choice(hot, size=min(nt, len(hot)), replace=False))
            r0 = corpus.doc_rects[d_i, anchor_rect[d_i]]
            qx = float((r0[0] + r0[2]) * 0.5)
            qy = float((r0[1] + r0[3]) * 0.5)
            w = rng.uniform(0.002, 0.006)
            rect = (
                max(qx - w, 0.0), max(qy - w, 0.0),
                min(qx + w, 1.0), min(qy + w, 1.0),
            )
        out.append(
            TraceQuery(
                terms=t.astype(np.int32),
                rects=np.asarray([rect], dtype=np.float32),
                amps=np.ones((1,), dtype=np.float32),
            )
        )
    return out


def make_uniform_trace(
    corpus: SynthCorpus,
    n_queries: int = 2048,
    d_terms: int = 4,
    q_rects: int = 2,
    seed: int = 1,
) -> list[TraceQuery]:
    """Adversarial trace for the cache: every query distinct, no locality."""
    rng = np.random.default_rng(seed)
    return [
        _one_query(
            rng, corpus, int(rng.integers(0, len(corpus.cities))), d_terms, q_rects
        )
        for _ in range(n_queries)
    ]


def pad_trace_batch(
    trace: list[TraceQuery],
    max_terms: int = 8,
    max_rects: int = 4,
) -> QueryBatch:
    """Pad a serving trace into one fixed-shape :class:`QueryBatch`.

    The core-algorithm analogue of the serving batcher's padding — lets
    benchmarks and tests drive ``GeoSearchEngine.query`` directly with the
    same zipf/uniform traces the serving layer replays."""
    B = len(trace)
    terms = np.full((B, max_terms), -1, dtype=np.int32)
    rects = np.tile(
        np.array([1.0, 1.0, 0.0, 0.0], np.float32), (B, max_rects, 1)
    )
    amps = np.zeros((B, max_rects), dtype=np.float32)
    for i, q in enumerate(trace):
        t = q.terms[:max_terms]
        terms[i, : len(t)] = t
        r = q.rects[:max_rects]
        rects[i, : len(r)] = r
        amps[i, : len(r)] = q.amps[: len(r)]
    return QueryBatch(
        terms=jnp.asarray(terms), rects=jnp.asarray(rects), amps=jnp.asarray(amps)
    )
