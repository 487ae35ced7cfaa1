"""The comparison that decides ``correct``: served answers against the
plain reference (``reference.py``), query by query.

Numbers compared, each against the limit the configuration's file gives
under ``check``:

* ``unanswered`` — queries of the window that got no answer;
* ``rank_gap`` — the widest relative gap between the served score and the
  reference's at the same rank (inf where the two disagree on how many
  documents qualify);
* ``doc_gap`` — the widest relative gap between the served score of a
  document and the reference's score of that same document (inf where the
  served document does not qualify at all: a term missing, no overlap);
* ``dup_ids`` — served lists that name a document twice.

Together these hold every served list to the reference's top-k up to ties:
each served document scores what the reference says it scores, and the
scores fall rank by rank where the reference's do.
"""
from __future__ import annotations

import numpy as np

from benchmarks.chip.reference import pad_queries

NAMES = ("unanswered", "rank_gap", "doc_gap", "dup_ids")


def _rel(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    return np.abs(a - b) / np.maximum(np.abs(b), 1e-30)


def gaps(ids, got, want, at) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Per-answer (rank_gap, doc_gap, dup) of served (ids, scores) ``got``
    against the reference's top-k scores ``want`` and its score ``at`` of
    each served id; all ``[B, k]``."""
    fin_g, fin_w, fin_a = np.isfinite(got), np.isfinite(want), np.isfinite(at)
    rank = np.where(fin_g & fin_w, _rel(got, np.where(fin_w, want, 1.0)), 0.0)
    rank = np.where(fin_g != fin_w, np.inf, rank).max(axis=1)
    doc = np.where(fin_g & fin_a, _rel(got, np.where(fin_a, at, 1.0)), 0.0)
    doc = np.where(fin_g & ~fin_a, np.inf, doc).max(axis=1)
    live = np.where(fin_g, ids, -1)
    srt = np.sort(live, axis=1)
    dup = ((srt[:, 1:] == srt[:, :-1]) & (srt[:, 1:] >= 0)).any(axis=1)
    return rank, doc, dup


def compare(queries, answers, ref, d_terms: int, q_rects: int,
            batch: int = 8) -> dict:
    """Compare every answer of the window.

    ``queries[i]`` was answered with ``answers[i]`` (an object with
    ``ids`` and ``scores``, or None).  Each distinct (query, served list)
    pair is asked of the reference once, ``batch`` pairs to a call.
    Returns per-answer arrays and their maxima under ``numbers``.
    """
    n = len(queries)
    rank = np.zeros(n)
    doc = np.zeros(n)
    dup = np.zeros(n, bool)
    missing = np.array([a is None for a in answers], bool)
    jobs: dict[tuple, list[int]] = {}
    for i, (q, a) in enumerate(zip(queries, answers)):
        if a is not None:
            jobs.setdefault((id(q), np.asarray(a.ids).tobytes()), []).append(i)
    todo = list(jobs.values())
    for s in range(0, len(todo), batch):
        part = [rows[0] for rows in todo[s : s + batch]]
        pad = part + part[:1] * (batch - len(part))
        terms, rects, amps = pad_queries([queries[i] for i in pad], d_terms, q_rects)
        served = np.stack([np.asarray(answers[i].ids, np.int32) for i in pad])
        _, want, at = ref.answer(terms, rects, amps, served)
        for j, rows in enumerate(todo[s : s + batch]):
            ids = np.stack([answers[i].ids for i in rows])
            got = np.stack([answers[i].scores for i in rows]).astype(np.float32)
            m = len(rows)
            r, d, u = gaps(ids, got, want[j : j + 1].repeat(m, 0),
                           at[j : j + 1].repeat(m, 0))
            rank[rows], doc[rows], dup[rows] = r, d, u
    return {
        "missing": missing, "rank": rank, "doc": doc, "dup": dup,
        "numbers": {
            "unanswered": int(missing.sum()),
            "rank_gap": float(rank.max(initial=0.0)),
            "doc_gap": float(doc.max(initial=0.0)),
            "dup_ids": int(dup.sum()),
        },
    }
