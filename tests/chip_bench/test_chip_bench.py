"""Tests of the chip benchmark's harness (``benchmarks/chip``), on the CPU.

They cover what the chip's runs cannot show cheaply: that cells, mixes and
metrics are found by name, the metric arithmetic, the reduction of a
recorded device trace, the keys of the result line, that a run without a
TPU prints no result, that the benchmark's generators are the program's,
and that the check passes a sound run and fails its control and every
fault the cells can have.  A tiny deployment (4096 documents) stands in
for the chip's shard.
"""
from __future__ import annotations

import hashlib
import inspect
import json
import os
import re
import shutil
import subprocess
import sys
import time
from collections import Counter
from dataclasses import dataclass, field

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.chip import check, corpus as bcorpus, devtrace, harness, traffic  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
BENCH = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def tiny(cell: str, queries: int = 2048) -> harness.Cell:
    """The cell at a size the CPU holds: 4096 documents, every width and
    budget ratio scaled down as the tests of the program do."""
    c = harness.load_cell(ROOT, cell)
    c.config = json.loads(json.dumps(c.config))
    c.config.update(n_docs=4096, n_terms=1024, avg_postings_per_doc=16, grid=64)
    c.config["budgets"].update(
        max_candidates=256, max_tiles=64, k_sweeps=4, sweep_budget=256
    )
    c.mix = dict(c.mix, queries=queries)
    return c


def run_tiny(cell: str, seed: int = 5, trace: bool = False, wrap=None,
             seconds: float = 0.2) -> dict:
    c = tiny(cell)
    return harness.run_cell(
        c, seed, seconds, trace, time.perf_counter(), ROOT,
        require_chip=False, wrap_executor=wrap,
    )


def _forced_devices(script: str, n: int = 4, args=()) -> dict:
    """Run ``script`` on ``n`` forced host devices; its last line is JSON."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={n}",
               PYTHONPATH=os.pathsep.join([os.path.join(ROOT, "src"), ROOT]))
    p = subprocess.run([sys.executable, "-c", script, *args], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


# ----------------------------------------------------------------------
# BENCHMARK.json and discovery by name
# ----------------------------------------------------------------------
def test_benchmark_json_keys_and_names():
    assert list(BENCH) == [
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    ]
    names = [e["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for e in BENCH[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    assert all(UNIT.match(m["unit"]) for m in BENCH["end_to_end"] + BENCH["per_layer"])
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    cells = {w["name"] for w in BENCH["workloads"]}
    assert "setup_s" in e2e
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and set(m.get("workloads", cells)) <= cells
    for p in BENCH["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_cell_files_found_by_name(cell):
    c = harness.load_cell(ROOT, cell)
    w = next(w for w in BENCH["workloads"] if w["name"] == cell)
    assert c.config["name"] == w["config"] and c.chips == w["chips"]
    assert {m["name"] for m in c.end_to_end} >= {"setup_s"}
    assert len(c.end_to_end) >= 2 and c.per_layer


@pytest.mark.parametrize(
    "metric", [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
)
def test_every_metric_has_a_reader(metric):
    assert callable(harness.reader(metric))


def test_unknown_cell_and_device_are_errors():
    with pytest.raises(KeyError):
        harness.load_cell(ROOT, "no.such.cell")
    with pytest.raises(KeyError):
        harness.peaks_of("cpu")
    assert harness.peaks_of("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


# ----------------------------------------------------------------------
# metric arithmetic
# ----------------------------------------------------------------------
@dataclass
class _Ev:
    start_t: float
    done_t: float


@dataclass
class _Rep:
    n_queries: int
    latencies_s: list
    batch_events: list
    cache_hits: int = 0
    coalesced: int = 0
    plan_queries: dict = field(default_factory=dict)
    plan_stats: dict = field(default_factory=dict)


def _run(cache=True) -> harness.Run:
    run = harness.Run(config={"cache": {"policy": "lru"} if cache else None,
                              "coalesce": False})
    run.setup_s = 12.5
    run.window_s = 10.0
    run.reports = [
        _Rep(4, [0.1, 0.2, 0.3, 0.4], [_Ev(0.0, 1.0), _Ev(2.0, 4.0)], 1, 1,
             {"scan": 2, "text_first+prune+fused": 1}, {"scan": {"scan_rounds": 6.0}}),
        _Rep(4, [1.0, 2.0, 3.0, 4.0], [_Ev(5.0, 6.0)], 2, 0, {"scan": 1},
             {"scan": {"scan_rounds": 3.0}}),
    ]
    run.queries = 8
    run.latencies_s = [x for r in run.reports for x in r.latencies_s]
    run.batch_spans = [
        ("scan", 0.0, 1.0, 2), ("text_first+prune+fused", 2.0, 4.0, 1),
        ("scan", 5.0, 6.5, 1),
    ]
    return run


def test_metric_arithmetic_on_fixed_latencies_and_batches():
    run = _run()
    r = {m: harness.reader(m)(run) for m in (
        "qps", "latency_p50_ms", "latency_p95_ms", "setup_s",
        "server_ms_per_query", "cache_hit_share", "scan_share",
        "batch_ms.scan", "batch_ms.textfirst", "scan_rounds_per_query",
    )}
    assert r["qps"] == pytest.approx(0.8)
    assert r["latency_p50_ms"] == pytest.approx(700.0)
    assert r["latency_p95_ms"] == pytest.approx(3650.0)
    assert r["setup_s"] == 12.5
    # 10 s of window, 4 s inside batches, 8 queries
    assert r["server_ms_per_query"] == pytest.approx(750.0)
    assert r["cache_hit_share"] == pytest.approx(50.0)
    assert r["scan_share"] == pytest.approx(75.0)
    assert r["batch_ms.scan"] == pytest.approx(1250.0)
    assert r["batch_ms.textfirst"] == pytest.approx(2000.0)
    assert r["scan_rounds_per_query"] == pytest.approx(3.0)


def test_readers_return_nothing_where_nothing_is_read():
    run = _run(cache=False)
    run.batch_spans = []
    for m in ("cache_hit_share", "batch_ms.scan", "batch_ms.textfirst",
              "scan_rounds_per_query", "device_idle_share"):
        assert harness.reader(m)(run) is None


# ----------------------------------------------------------------------
# the device trace
# ----------------------------------------------------------------------
def test_trace_reduction_by_hand():
    ms = 1_000_000
    kernel = '%custom-call.7 = f32[8]{0} custom-call(), custom_call_target="tpu_custom_call"'
    tr = devtrace.DeviceTrace(
        ops=[
            (0, "%while.1 = (s32[]) while(...)", 0, 3 * ms),
            (0, "%fusion.2 = f32[8]{0} fusion(...)", 1 * ms, 2 * ms),
            (0, kernel, 5 * ms, 6 * ms),
            (0, "%fusion.1 = f32[8]{0} fusion(...)", 9 * ms, 12 * ms),
        ],
        marks=[("window", 0, 10 * ms), ("exec:scan", 4 * ms, 8 * ms)],
    )
    assert tr.window_s == pytest.approx(0.010)
    assert tr.busy_s == pytest.approx(0.005)  # 0-3, 5-6, 9-10 inside the window
    assert devtrace.kernel_seconds(tr, "tpu_custom_call") == (pytest.approx(0.001), 1)
    gaps = devtrace.idle_gaps(tr)
    assert [(n, b - a) for n, a, b in gaps] == [
        ("exec:scan", 2 * ms), ("exec:scan", 3 * ms)
    ]
    assert devtrace.self_times(tr) == {
        "while.1": 2 * ms, "fusion.2": 1 * ms,
        "custom-call.7 (tpu_custom_call)": 1 * ms, "fusion.1": 1 * ms,
    }
    bd = devtrace.breakdown(tr)
    assert bd["device_ops"][0] == ["while.1", pytest.approx(0.002)]
    assert bd["idle_gaps"] == [["exec:scan (2 gaps)", pytest.approx(0.005)]]
    run = harness.Run(config={})
    run.device = tr
    assert harness.reader("device_idle_share")(run) == pytest.approx(50.0)


def test_trace_reduction_on_a_recorded_chip_trace():
    """Two batches of a window traced on one TPU v5 lite (geoweb.zipf)."""
    with open(os.path.join(DATA, "v5e_trace.json")) as f:
        rec = json.load(f)
    tr = devtrace.DeviceTrace.from_json(rec["trace"])
    want = rec["expected"]
    assert tr.window_s == pytest.approx(want["window_s"])
    assert tr.busy_s == pytest.approx(want["busy_s"])
    # the union of the op intervals, counted again on a 1 us grid
    t0, t1 = tr.window
    grid = np.zeros((t1 - t0) // 1000 + 1, bool)
    for _, _, a, b in tr.ops:
        grid[max(a - t0, 0) // 1000 : max(min(b, t1) - t0, 0) // 1000] = True
    assert grid.sum() * 1e-6 == pytest.approx(tr.busy_s, rel=1e-3)
    # own times add up to the busy time; idle gaps to the rest
    assert sum(devtrace.self_times(tr).values()) * 1e-9 == pytest.approx(tr.busy_s)
    bd = devtrace.breakdown(tr)
    assert bd == json.loads(json.dumps(want["breakdown"]))
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    idle = sum(ns for _, a, b in devtrace.idle_gaps(tr) for ns in [b - a]) * 1e-9
    assert idle == pytest.approx(tr.window_s - tr.busy_s)
    assert devtrace.kernel_seconds(tr, "tpu_custom_call")[0] == pytest.approx(
        want["kernel_s"]
    )
    # program runs named by the plans of the window's batches, in order
    first = min(tr.modules, key=lambda m: m[2])[1]
    labels = ["scan" if m[1] == first else "text_first+prune+fused"
              for m in sorted(tr.modules, key=lambda m: m[2])]
    tr.name_modules(labels)
    names = {n.split("/")[0] for n, _ in devtrace.breakdown(tr)["device_ops"]}
    assert names == {"scan", "text_first+prune+fused"}
    # the kernel's time is that of its ops inside the TEXT-FIRST runs
    assert devtrace.kernel_seconds(tr, "tpu_custom_call", "text_first")[0] == (
        pytest.approx(want["kernel_s"])
    )
    assert devtrace.kernel_seconds(tr, "tpu_custom_call", "scan") == (0.0, 0)


def test_text_probe_time_counts_only_text_first_runs():
    """A Pallas kernel in a scan program is not ``text_probe``'s, and runs
    that were not paired with batches give nothing."""
    ms = 1_000_000
    kernel = '%c = f32[8]{0} custom-call(), custom_call_target="tpu_custom_call"'
    tr = devtrace.DeviceTrace(
        ops=[(0, kernel, 1 * ms, 2 * ms), (0, kernel, 5 * ms, 8 * ms)],
        marks=[("window", 0, 10 * ms)],
        modules=[(0, "jit_run(1)", 0, 4 * ms), (0, "jit_run(2)", 4 * ms, 9 * ms)],
    )
    run = harness.Run(config={})
    run.device = tr
    run.batch_spans = [("text_first+prune+fused", 0.0, 0.004, 3),
                       ("scan", 0.004, 0.009, 8)]
    read = harness.reader("device_ms.text_probe")
    assert read(run) is None  # program runs not named by plan
    tr.name_modules([b[0] for b in run.batch_spans])
    assert read(run) == pytest.approx(1.0)


def test_xplane_marks_read_back(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x * 2).sum())
    x = jnp.ones((64,))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("window"):
        with jax.profiler.TraceAnnotation("exec:scan"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    tr = devtrace.read(str(tmp_path))
    names = {m[0] for m in tr.marks}
    assert {"window", "exec:scan"} <= names
    assert tr.window_s > 0


# ----------------------------------------------------------------------
# the generators are the program's
# ----------------------------------------------------------------------
def test_generators_equal_the_programs():
    from repro.corpus import make_corpus, make_zipf_trace

    cfg = dict(n_docs=3000, n_terms=700, avg_postings_per_doc=16,
               term_zipf_a=1.3, n_cities=32, doc_major_rects=4)
    doc_len = bcorpus.doc_len_for_postings(16, 700, 1.3)
    ours = bcorpus.make_corpus(cfg, 11)
    theirs = make_corpus(n_docs=3000, n_terms=700, max_rects=4, doc_len=doc_len,
                         seed=11)
    for k in ("doc_terms", "doc_rects", "doc_amps", "pagerank", "cities"):
        np.testing.assert_array_equal(getattr(ours, k), getattr(theirs, k))
    mix = harness.load_json(os.path.join(ROOT, "benchmarks/chip/traffic/zipf.json"))
    mix = dict(mix, queries=20000, pool_seed=3)
    # the pool is make_zipf_trace's for the same seed (how often each search
    # is asked differs: the benchmark truncates the Zipf ranks to the pool)
    def searches(trace):
        return {(tuple(q.terms), tuple(q.rects.ravel())) for q in trace}

    ours_pool = searches(traffic.generate(ours, mix, 8))
    theirs_pool = searches(make_zipf_trace(theirs, n_queries=20000, seed=3))
    assert len(ours_pool ^ theirs_pool) <= 4 and len(ours_pool) > 200


def test_zipf_ranks_are_truncated_to_the_pool():
    """P(rank k) is k^-a renormalised over the pool: the head search takes
    its share and the pool's last search no clipped tail."""
    cfg = dict(n_docs=4096, n_terms=1024, avg_postings_per_doc=16,
               term_zipf_a=1.3, n_cities=32, doc_major_rects=4)
    corpus = bcorpus.make_corpus(cfg, 2)
    mix = harness.load_json(os.path.join(ROOT, "benchmarks/chip/traffic/zipf.json"))
    mix = dict(mix, queries=64 * 400)
    stream = traffic.generate(corpus, mix, 4)
    counts = np.array(sorted(Counter(id(q) for q in stream).values(), reverse=True))
    p = traffic.zipf_pmf(mix["zipf_a"], mix["pool_size"])
    assert p.sum() == pytest.approx(1.0) and p[0] == pytest.approx(0.2065, abs=1e-3)
    n = len(stream)
    assert counts[0] / n == pytest.approx(p[0], abs=0.01)
    assert counts[:10].sum() / n == pytest.approx(p[:10].sum(), abs=0.02)
    # no search takes the clipped tail's half of the stream
    assert counts[0] / n < 0.25


def test_seeds_rename_terms_and_reorder_the_same_searches():
    c = tiny("geoweb.zipf", queries=20000)
    (a, qa), (b, qb) = harness.inputs(c, 1), harness.inputs(c, 2**31 + 5)
    assert not np.array_equal(a.doc_terms, b.doc_terms)
    np.testing.assert_array_equal(a.doc_rects, b.doc_rects)
    # the same posting-list lengths under other names
    assert sorted(np.bincount(a.doc_terms.ravel())) == sorted(
        np.bincount(b.doc_terms.ravel())
    )
    def search(q):
        return tuple(q.rects.ravel()), len(q.terms)

    # every chunk holds the same searches, in another order
    size = c.mix["chunk"]
    for s in range(0, len(qa), size):
        assert Counter(map(search, qa[s : s + size])) == Counter(
            map(search, qb[s : s + size])
        )
    assert [search(q) for q in qa[:size]] != [search(q) for q in qb[:size]]
    assert len(set(map(search, qa))) > 200


def _digest(stream) -> str:
    h = hashlib.sha256()
    for q in stream:
        for a in (q.terms, q.rects, q.amps):
            h.update(a.tobytes())
            h.update(f"{a.dtype}{a.shape}".encode())
    return h.hexdigest()


@pytest.mark.parametrize("seed, want", [
    (1, "8e93f58cc91fbfb39bcacdc57cb809c1345757c4651fd298731c710e6122322d"),
    (2**31 + 5, "5558e14638ac74aff4c234073a65f7bb8eb913dd9be6d3f5e77f9a2170dafa14"),
])
def test_pool_generator_gives_the_zipf_stream_it_always_gave(seed, want):
    """Terms, rects and amps of every query, as the stream read before the
    mix named its generator (digests of that stream at this size)."""
    c = tiny("geoweb.zipf")
    assert c.mix["generator"] == "pool"
    assert _digest(harness.inputs(c, seed)[1]) == want


# ----------------------------------------------------------------------
# whole runs on the CPU: the line, the check, the control, the faults
# ----------------------------------------------------------------------
@pytest.fixture(scope="module")
def traced_line():
    return run_tiny("geoweb-cached.zipf", trace=True)


def test_last_line_keys_of_a_traced_run(traced_line):
    line = traced_line
    assert list(line) == [
        "correct", "attempted", "failed", "metrics", "device", "breakdown", "check"
    ]
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["device"]) >= {
        "platform", "kind", "count", "memory_peak_bytes", "busy_s", "window_s"
    }
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    assert list(line["check"]) == list(check.NAMES)
    assert all(set(c) == {"value", "limit"} for c in line["check"].values())
    assert {"cache_hit_share", "scan_share", "server_ms_per_query",
            "latency_p95_ms"} <= set(line["metrics"])
    json.dumps(line)


def test_sound_run_is_correct_with_every_answer_compared():
    line = run_tiny("geoweb.zipf", seed=2**31 + 77)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 64
    assert set(line["metrics"]) == {"qps", "latency_p50_ms", "setup_s"}
    nums = {k: c["value"] for k, c in line["check"].items()}
    assert nums == {"unanswered": 0, "rank_gap": 0.0, "doc_gap": 0.0, "dup_ids": 0}


class _Fault:
    """The executor with a fault planted where answers are produced."""

    def __init__(self, ex, fault: str):
        self._ex, self._fault = ex, fault

    def __getattr__(self, name):
        return getattr(self._ex, name)

    def run(self, batch, plan):
        import jax.numpy as jnp

        res = self._ex.run(batch, plan=plan)
        ids, scores = res.ids, res.scores
        B = ids.shape[0]
        if self._fault == "altered":  # one answer's best document swapped
            ids = ids.at[0, 0].set(jnp.where(ids[0, 0] >= 0, (ids[0, 0] + 1) % 4096, -1))
        elif self._fault == "half_batch":  # the second half of the rows dropped
            ids = ids.at[B // 2 :].set(-1)
            scores = scores.at[B // 2 :].set(-jnp.inf)
        return type(res)(ids, scores, res.stats)


@pytest.mark.parametrize("fault", ["altered", "half_batch"])
def test_planted_fault_makes_the_run_incorrect(fault):
    line = run_tiny("geoweb.zipf", seed=9, wrap=lambda ex: _Fault(ex, fault))
    assert line["correct"] is False and line["failed"] > 0


class _Dups(_Fault):
    """Every batch's first answer names document 0 twice, at its top."""

    def run(self, batch, plan):
        self._fault.append(plan)  # here: the list of batches run
        res = self._ex.run(batch, plan=plan)
        return type(res)(res.ids.at[0, :2].set(0), res.scores.at[0, :2].set(1e9),
                         res.stats)


def test_duplicate_answers_make_the_run_incorrect():
    """One chunk of 64 answers: every batch's first names a document twice."""
    batches = []
    line = run_tiny("geoweb.zipf", seed=2**31 + 4,
                    wrap=lambda ex: _Dups(ex, batches), seconds=0.0)
    assert line["attempted"] == 64
    # one in each of the window's batches (64 queries, batches of at most
    # 8 of one plan); ``batches`` holds the warm-up's too
    assert 8 <= line["check"]["dup_ids"]["value"] < len(batches)
    assert line["correct"] is False


@pytest.mark.parametrize("cell", ["geoweb.zipf", "geoweb-cached.zipf"])
def test_control_in_lower_precision_is_not_correct(cell):
    from benchmarks.chip import control

    c = tiny(cell)
    nums = control.readings(c, 21, 192)
    limits = c.config["check"]["limits"]
    assert any(nums[k] > limits[k] for k in nums), nums


# ----------------------------------------------------------------------
# executor layouts and the reference over several devices
# ----------------------------------------------------------------------
LAYOUT_KEYS = {"single": {"kind"}, "mesh": {"kind", "routing", "partitioner"}}


def _config(name: str) -> dict:
    entry = next(c for c in BENCH["configs"] if c["name"] == name)
    return harness.load_json(os.path.join(ROOT, entry["file"]))


@pytest.mark.parametrize("name", [c["name"] for c in BENCH["configs"]])
def test_configuration_names_its_executor_layout(name):
    """Every configuration's block is one the harness can build: a layout
    it places on chips, with keys the program's ``make_executor`` takes."""
    from repro.core.distributed import resolve_partitioner
    from repro.serving.factory import make_executor

    layout = _config(name)["executor"]
    assert layout["kind"] in LAYOUT_KEYS
    assert set(layout) <= LAYOUT_KEYS[layout["kind"]]
    assert set(layout) - {"kind"} <= set(inspect.signature(make_executor).parameters)
    if "partitioner" in layout:
        resolve_partitioner(layout["partitioner"])


@pytest.mark.parametrize("name", ["geoweb", "geoweb-cached"])
def test_one_chip_deployments_keep_the_single_layout(name):
    assert _config(name)["executor"] == {"kind": "single"}


def test_single_layout_places_the_index_on_the_first_device():
    import jax

    c = tiny("geoweb.zipf")
    corpus, _ = harness.inputs(c, 1)
    dev = jax.devices()[0]
    ex = harness.build_executor(c.config, corpus, [dev])
    leaves = harness.index_leaves(ex)
    assert leaves and all(x.devices() == {dev} for x in leaves)


MESH_RUN = """
import json, sys, time
import jax
from benchmarks.chip import harness

root = sys.argv[1]
c = harness.load_cell(root, "geoweb-mesh4.zipf")
c.config.update(n_docs=4096, n_terms=1024, avg_postings_per_doc=16, grid=64)
c.mix = dict(c.mix, queries=1024)
corpus, _ = harness.inputs(c, 1)
ex = harness.build_executor(c.config, corpus, jax.devices()[: c.chips])
on = set().union(*(x.devices() for x in harness.index_leaves(ex)))
del ex
line = harness.run_cell(c, 2**31 + 13, 0.3, False, time.perf_counter(), root,
                        require_chip=False)
line["index_devices"] = len(on)
line["chips"] = c.chips
print(json.dumps(line))
"""


def _mesh_deployment(root) -> None:
    """A four-chip deployment added as files only: a configuration file
    naming a mesh layout and its entries in ``BENCHMARK.json``."""
    bench = json.loads(json.dumps(BENCH))
    config = _config("geoweb")
    # the mesh's plans have no exhaustive scan: budgets that cover every
    # query at the CPU size (every shard's postings within max_candidates)
    config.update(algorithm="text_first", fused=False,
                  executor={"kind": "mesh", "partitioner": "morton",
                            "routing": "footprint"})
    config["budgets"].update(max_candidates=4096, max_tiles=4096, k_sweeps=8,
                             sweep_budget=4096, prune=False, exact=False)
    path = "benchmarks/chip/configs/geoweb-mesh4.json"
    os.makedirs(os.path.join(root, os.path.dirname(path)))
    with open(os.path.join(root, path), "w") as f:
        json.dump(config, f)
    bench["configs"].append(dict(bench["configs"][0], name="geoweb-mesh4", file=path))
    bench["workloads"].append(dict(bench["workloads"][0], name="geoweb-mesh4.zipf",
                                   config="geoweb-mesh4", chips=4))
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)


def test_mesh_layout_runs_a_cell_on_four_forced_devices(tmp_path):
    _mesh_deployment(tmp_path)
    line = _forced_devices(MESH_RUN, args=[str(tmp_path)])
    assert line["chips"] == 4 and line["index_devices"] == 4
    assert line["device"]["count"] == 4
    assert len(line["device"]["memory_peak_bytes_each"]) == 4
    assert line["correct"] is True and line["failed"] == 0, line["check"]
    assert line["attempted"] >= 64


REFERENCE_SPREAD = """
import json
import jax
import numpy as np
from benchmarks.chip import harness
from benchmarks.chip.reference import Reference, pad_queries

c = harness.load_cell(".", "geoweb.zipf")
c.config.update(n_docs=4096, n_terms=1024, avg_postings_per_doc=16, grid=64)
c.mix = dict(c.mix, queries=256)
corpus, stream = harness.inputs(c, 2**31 + 21)
one = Reference(corpus, c.config)
many = Reference(corpus, c.config, devices=jax.devices(), block_docs=512)
same, found = True, 0
for s in range(0, 64, 8):
    t, r, a = pad_queries(stream[s : s + 8], 4, 2)
    ids0, top0, _ = one.answer(t, r, a, np.full((8, 10), -1, np.int32))
    served = ids0[:, ::-1]  # documents from several runs of blocks, -1 pads
    x = one.answer(t, r, a, served)
    y = many.answer(t, r, a, served)
    same &= all(np.array_equal(u, v) for u, v in zip(x, y))
    found += int((ids0 >= 0).sum())
print(json.dumps({"same": bool(same), "parts": len(many.parts), "found": found,
                  "devices": len({p.doc_terms.devices().pop() for p in many.parts})}))
"""


def test_reference_over_several_devices_gives_the_one_device_bits():
    r = _forced_devices(REFERENCE_SPREAD)
    assert r["parts"] == 4 and r["devices"] == 4
    assert r["found"] > 100 and r["same"] is True


# ----------------------------------------------------------------------
# no chip, no program: no result
# ----------------------------------------------------------------------
def _run_cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", "geoweb.zipf",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


def test_data_seed_serves_another_corpus(monkeypatch, capsys):
    """``--data-seed`` replaces the configuration's ``data_seed``; without
    it a run serves the configuration's."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "chip_run", os.path.join(ROOT, "benchmarks/chip/run.py"))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    seen = []

    def fake_run_cell(cell, *a, **k):
        seen.append(cell.config["data_seed"])
        return {"check": {}}

    monkeypatch.setattr(harness, "chips", lambda n: None)
    monkeypatch.setattr(harness, "enable_compile_cache", lambda root: None)
    monkeypatch.setattr(harness, "run_cell", fake_run_cell)
    args = ["--workload", "geoweb.zipf", "--seed", "1", "--seconds", "1"]
    assert run.main(args) == 0 and run.main(args + ["--data-seed", "3"]) == 0
    assert seen == [0, 3]
    capsys.readouterr()


def test_run_without_a_tpu_prints_no_result():
    p = _run_cli(ROOT)
    assert p.returncode == 3, p.stderr[-2000:]
    assert p.stdout.strip() == ""


def test_run_with_only_the_benchmark_files_prints_no_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_cli(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
