"""Tests of the benchmark's reader of the program's host stages
(``metrics/server_span_ms_per_query.py``), on the CPU."""
from __future__ import annotations

import json
import os
import sys
import time
from dataclasses import dataclass, field

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.chip import devtrace, harness  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@dataclass
class _Rep:
    n_queries: int
    stage_s: dict = field(default_factory=dict)


def _run(reports) -> harness.Run:
    run = harness.Run(config={"cache": None, "coalesce": False})
    run.reports = reports
    run.queries = sum(r.n_queries for r in reports)
    return run


def test_stage_time_per_query_by_hand():
    read = harness.reader("server_span_ms_per_query")
    run = _run([
        _Rep(4, {"geo.plan": 0.001, "geo.batch": 9.0, "geo.dispatch": 0.002,
                 "geo.result": 5.0, "geo.stats": 0.003, "geo.deliver": 0.004}),
        _Rep(4, {"geo.plan": 0.010, "geo.deliver": 0.020}),
    ])
    # plan, dispatch, stats and deliver only: 0.04 s over 8 queries
    assert read(run) == pytest.approx(5.0)


def test_stage_time_is_nothing_without_stages():
    """A program that times no stages (no tracer, or one that predates them)
    gives nothing, as does a window that answered nothing."""
    read = harness.reader("server_span_ms_per_query")

    @dataclass
    class _OldRep:  # a report without the field
        n_queries: int

    assert read(_run([_OldRep(4), _OldRep(4)])) is None
    assert read(_run([_Rep(4), _Rep(4)])) is None
    assert read(_run([])) is None
    # the recorded v5e trace's window (no stages) gives nothing
    with open(os.path.join(DATA, "v5e_trace.json")) as f:
        run = _run([_OldRep(8)])
        run.device = devtrace.DeviceTrace.from_json(json.load(f)["trace"])
    assert read(run) is None


def test_traced_run_reports_the_stage_time():
    cell = harness.load_cell(ROOT, "geoweb.zipf")
    cell.config = json.loads(json.dumps(cell.config))
    cell.config.update(n_docs=4096, n_terms=1024, avg_postings_per_doc=16, grid=64)
    cell.config["budgets"].update(
        max_candidates=256, max_tiles=64, k_sweeps=4, sweep_budget=256
    )
    cell.mix = dict(cell.mix, queries=1024)
    line = harness.run_cell(cell, 2**31 + 3, 0.2, True, time.perf_counter(), ROOT,
                            require_chip=False)
    assert line["correct"] is True
    got = line["metrics"]["server_span_ms_per_query"]
    assert got["unit"] == "ms" and got["value"] > 0
