"""Plain reference of a geo search: every document scored, exact top-k.

It states the deployment's ranking from its definition, with nothing of
the program imported and nothing the program built (no index, no impact
column, no tables):

* text score: for each query term, in query order, the document's impact
  ``idf * (1 + ln tf) / sqrt(doc_len)`` with ``idf = ln(1 + N / df)``,
  worked out in float64, rounded to float32 once and, under the f16
  compression the configuration states, stored as float16; a document
  must hold every query term;
* geo score: the amplitude-weighted intersection area of the document's
  rects (stored as float16 under f16 compression) with the query's, over
  the query footprint's own mass; a document must overlap the footprint;
* ``F = w_text * text + w_geo * geo / mass + w_pr * pagerank``, top-k by
  ``F``, ties to the lower document id.

``dtype`` is the arithmetic's precision: float32 as the configuration
states it, or bfloat16 for the control that the comparison must fail.
Documents are processed in blocks of rows so that the pass fits beside
nothing else on the chip once the program's state is freed.  Over several
devices the blocks are spread in runs of consecutive blocks, each scored
on the device that holds it, and the runs' top-k are merged on the host
(ties to the lower id): every document's score is computed alone, so the
answers are the one-device reference's bits.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

BLOCK_DOCS = 1 << 18  # documents per block of the dense passes


def _blocks(x: jax.Array, rows: int) -> jax.Array:
    return x.reshape((x.shape[0] // rows, rows) + x.shape[1:])


@functools.partial(jax.jit, static_argnames=("rows",))
def term_counts(doc_terms, terms, rows):
    """tf of every (document, term) pair: i32[N, T] (term -1 counts 0)."""

    def block(d):
        return jnp.sum(d[:, :, None] == terms[None, None, :], axis=1, dtype=jnp.int32)

    out = jax.lax.map(block, _blocks(doc_terms, rows))
    return out.reshape(doc_terms.shape[0], terms.shape[0])


@functools.partial(jax.jit, static_argnames=("rows",))
def doc_freqs(doc_terms, terms, rows):
    """Documents holding each term: i32[T]."""
    return jnp.sum(term_counts(doc_terms, terms, rows) > 0, axis=0, dtype=jnp.int32)


def impact_tables(df, n_docs: int, doc_len: int, compress: str) -> np.ndarray:
    """Impact of each term at each tf in 0..doc_len: f32[T, doc_len + 1]."""
    idf = np.log(1.0 + n_docs / np.maximum(np.asarray(df, np.float64), 1.0))
    tf = np.arange(1, doc_len + 1, dtype=np.float64)
    imp = idf[:, None] * (1.0 + np.log(tf))[None, :] / np.sqrt(float(doc_len))
    imp = imp.astype(np.float32)
    if compress == "f16":
        imp = imp.astype(np.float16).astype(np.float32)
    elif compress != "none":
        raise ValueError(f"no reference for compression {compress!r}")
    return np.concatenate([np.zeros((len(imp), 1), np.float32), imp], axis=1)


@functools.partial(jax.jit, static_argnames=("k", "rows", "dtype", "weights"))
def scores(doc_terms, doc_rects, doc_amps, pagerank, terms, q_rects, q_amps,
           tables, served, k, rows, dtype, weights):
    """Reference top-k of a batch of queries, and the reference score of
    each served document.

    terms i32[B, D] (-1 pads), q_rects f32[B, Q, 4], q_amps f32[B, Q],
    tables f32[B, D, L + 1], served i32[B, k] (ids of these documents, an
    id outside them scores -inf).  Returns (ids i32[B, k], scores f32[B, k],
    at_served f32[B, k]); -inf marks no document.
    """
    w_text, w_geo, w_pr = weights
    B, D = terms.shape
    real = terms >= 0
    qr = q_rects.astype(dtype)
    qa = q_amps.astype(dtype)
    qw = jnp.maximum(qr[..., 2] - qr[..., 0], 0) * jnp.maximum(qr[..., 3] - qr[..., 1], 0)
    mass = jnp.maximum(jnp.sum(qw * qa, axis=-1), 1e-12)  # [B]
    tab = tables.astype(dtype)

    def block(args):
        d_terms, d_rects, d_amps, pr = args
        tf = jnp.sum(
            d_terms[:, :, None] == terms.reshape(-1)[None, None, :],
            axis=1, dtype=jnp.int32,
        ).reshape(-1, B, D)
        imp = jnp.take_along_axis(
            tab[None], tf[..., None], axis=-1
        )[..., 0]  # [rows, B, D]
        text = jnp.zeros(imp.shape[:2], dtype)
        for j in range(D):  # query order, as the definition adds them
            text = text + jnp.where(real[None, :, j], imp[:, :, j], 0)
        match = jnp.all((tf > 0) | ~real[None], axis=-1)
        dr = d_rects.astype(dtype)[:, None, :, None, :]  # [rows, 1, R, 1, 4]
        x0 = jnp.maximum(dr[..., 0], qr[None, :, None, :, 0])
        y0 = jnp.maximum(dr[..., 1], qr[None, :, None, :, 1])
        x1 = jnp.minimum(dr[..., 2], qr[None, :, None, :, 2])
        y1 = jnp.minimum(dr[..., 3], qr[None, :, None, :, 3])
        inter = jnp.maximum(x1 - x0, 0) * jnp.maximum(y1 - y0, 0)  # [rows,B,R,Q]
        amp = d_amps.astype(dtype)[:, None, :, None] * qa[None, :, None, :]
        geo = jnp.sum(inter * amp, axis=(-1, -2))  # [rows, B]
        f = (
            jnp.asarray(w_text, dtype) * text
            + jnp.asarray(w_geo, dtype) * geo / mass[None, :]
            + jnp.asarray(w_pr, dtype) * pr.astype(dtype)[:, None]
        )
        return jnp.where(match & (geo > 0), f, -jnp.inf).astype(jnp.float32)

    f = jax.lax.map(
        block,
        (
            _blocks(doc_terms, rows), _blocks(doc_rects, rows),
            _blocks(doc_amps, rows), _blocks(pagerank, rows),
        ),
    ).reshape(-1, B).T  # [B, N]
    top, ids = jax.lax.top_k(f, k)
    ids = jnp.where(jnp.isfinite(top), ids, -1)
    at = jnp.take_along_axis(f, jnp.clip(served, 0, f.shape[1] - 1), axis=1)
    at = jnp.where((served >= 0) & (served < f.shape[1]), at, -jnp.inf)
    return ids, top, at


@dataclass
class _Part:
    """A run of consecutive blocks, on one device."""

    first: int  # id of its first document
    doc_terms: jax.Array
    doc_rects: jax.Array
    doc_amps: jax.Array
    pagerank: jax.Array


class Reference:
    """The corpus on the devices, and the reference answers over it."""

    def __init__(self, corpus, config: dict, dtype=jnp.float32, devices=None,
                 block_docs: int = BLOCK_DOCS):
        devices = list(devices or [None])  # None: JAX's default device
        self.n_docs, self.doc_len = corpus.doc_terms.shape
        self.rows = min(block_docs, self.n_docs)
        if self.n_docs % self.rows:
            raise ValueError("n_docs must be a multiple of the reference block")
        stored = np.float16 if config["compress"] == "f16" else np.float32
        # the rects and amps the configuration stores, decoded to f32
        rects = corpus.doc_rects.astype(stored).astype(np.float32)
        amps = corpus.doc_amps.astype(stored).astype(np.float32)
        n_blocks = self.n_docs // self.rows
        runs = np.array_split(np.arange(n_blocks), min(len(devices), n_blocks))
        self.parts = []
        for dev, run in zip(devices, runs):
            a, b = run[0] * self.rows, (run[-1] + 1) * self.rows
            put = functools.partial(jax.device_put, device=dev)
            self.parts.append(_Part(
                int(a), put(corpus.doc_terms[a:b]), put(rects[a:b]),
                put(amps[a:b]), put(corpus.pagerank[a:b]),
            ))
        self.compress = config["compress"]
        w = config["weights"]
        self.weights = (float(w["w_text"]), float(w["w_geo"]), float(w["w_pr"]))
        self.k = config["top_k"]
        self.dtype = dtype

    def answer(self, terms, rects, amps, served) -> tuple[np.ndarray, ...]:
        """Reference (ids, scores, score of each served id) for a padded
        batch: terms i32[B, D], rects f32[B, Q, 4], amps f32[B, Q],
        served i32[B, k]."""
        terms = np.asarray(terms, np.int32)
        served = np.asarray(served, np.int32)
        counts = [doc_freqs(p.doc_terms, terms.reshape(-1), self.rows)
                  for p in self.parts]
        df = sum(np.asarray(c, np.int64) for c in counts)
        tables = impact_tables(df, self.n_docs, self.doc_len, self.compress)
        tables = tables.reshape(terms.shape + (self.doc_len + 1,))
        rects = np.asarray(rects, np.float32)
        amps = np.asarray(amps, np.float32)
        outs = [
            scores(
                p.doc_terms, p.doc_rects, p.doc_amps, p.pagerank, terms, rects,
                amps, tables, np.where(served >= 0, served - p.first, -1),
                k=self.k, rows=self.rows, dtype=self.dtype, weights=self.weights,
            )
            for p in self.parts
        ]  # every device's call dispatched before any result is read
        outs = [[np.asarray(x) for x in o] for o in outs]
        ids = np.concatenate([np.where(i >= 0, i + p.first, -1)
                              for (i, _, _), p in zip(outs, self.parts)], axis=1)
        top = np.concatenate([t for _, t, _ in outs], axis=1)
        at = np.max([a for _, _, a in outs], axis=0)
        # merge the runs' top-k: best score first, ties to the lower id
        order = np.lexsort((ids, -top), axis=-1)[:, : self.k]
        top = np.take_along_axis(top, order, axis=1)
        ids = np.take_along_axis(ids, order, axis=1)
        return np.where(np.isfinite(top), ids, -1).astype(np.int32), top, at


def pad_queries(queries, d_terms: int, q_rects: int):
    """(terms, rects, amps) of a list of queries, padded as the server
    pads a batch: term -1, empty rect, amp 0."""
    B = len(queries)
    terms = np.full((B, d_terms), -1, np.int32)
    rects = np.tile(np.array([1.0, 1.0, 0.0, 0.0], np.float32), (B, q_rects, 1))
    amps = np.zeros((B, q_rects), np.float32)
    for i, q in enumerate(queries):
        terms[i, : len(q.terms)] = q.terms
        rects[i, : len(q.rects)] = q.rects
        amps[i, : len(q.amps)] = q.amps
    return terms, rects, amps
