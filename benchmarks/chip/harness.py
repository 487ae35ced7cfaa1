"""One run of one cell: set-up, the measured window, the check, one line.

A cell is a ``workloads`` entry of ``BENCHMARK.json``: it names a
configuration (``configs/<file>.json``, the deployment and its executor
layout) and a traffic mix (``traffic/<name>.json``, whose generator is
``generators/<generator>.py``).  Each metric is computed by its own
reader, ``metrics/<name>.py``.  Everything is found by name, so a new
cell, mix, generator, layout or metric is a new file and an entry in
``BENCHMARK.json``.

The window drives the program's ``GeoServer.run_trace`` in closed-loop
mode on the wall clock, one chunk of the stream after another, until
``seconds`` have passed; the chunk in flight is finished.  Once the window
has closed, every answer it served is compared with the plain reference
(``reference.py``, ``check.py``).
"""
from __future__ import annotations

import gc
import importlib.util
import json
import os
import sys
import tempfile
import time
from collections import Counter
from dataclasses import dataclass, field

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    chips: int
    config: dict  # the configuration's file
    mix: dict  # the traffic mix's file
    end_to_end: list  # BENCHMARK.json metric entries this cell reports
    per_layer: list


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root: str, name: str) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json``, with its files."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(os.path.join(root, configs[w["config"]]["file"]))
    mix = load_json(os.path.join(HERE, "traffic", f"{w['traffic']}.json"))
    return Cell(
        name=name,
        chips=w["chips"],
        config=config,
        mix=mix,
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, name)],
    )


def reader(name: str):
    """The ``read(run)`` function of ``metrics/<name>.py``."""
    path = os.path.join(HERE, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"chip_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks_of(kind: str) -> dict:
    """The chip's published peaks; a device not in the table is an error."""
    table = load_json(os.path.join(HERE, "peaks.json"))
    if kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table["devices"][kind]


def chips(n: int):
    """The accelerator devices, at least ``n``; raises :class:`NoChip`."""
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as e:  # no backend could start
        raise NoChip(str(e)) from e
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU, only {devs[0].platform}")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} chips, JAX found {len(devs)}")
    return devs


def enable_compile_cache(root: str) -> str:
    """JAX's persistent cache: ``JAX_COMPILATION_CACHE_DIR`` where it is
    set, else the fixed ``.jax_cache/`` of the checkout.  Every program is
    kept, so a second run of a cell compiles nothing."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        root, ".jax_cache"
    )
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts compile requests (persistent-cache hits and misses)."""

    def __init__(self):
        import jax

        self.requests = 0
        self.hits = 0

        def on_event(event, **_):
            if event == "/jax/compilation_cache/compile_requests_use_cache":
                self.requests += 1
            elif event == "/jax/compilation_cache/cache_hits":
                self.hits += 1

        jax.monitoring.register_event_listener(on_event)


# ----------------------------------------------------------------------
# the system under test
# ----------------------------------------------------------------------
def build_executor(config: dict, corpus, devices):
    """The configuration's executor, with its index on ``devices`` (the
    cell's chips), built by the program's ``make_executor``.

    The configuration's ``executor`` block gives the layout: ``kind``
    (``single`` or ``mesh``), and for a mesh ``routing`` and
    ``partitioner`` (by name).  A ``single`` index sits
    on the first chip; a ``mesh`` spans the cell's chips, one doc shard a
    chip, each placed by the program's own sharding specs.
    """
    import jax

    from repro.core.algorithms import QueryBudgets
    from repro.core.distributed import resolve_partitioner
    from repro.core.ranking import RankWeights
    from repro.serving.factory import make_executor

    layout = dict(config["executor"])
    kind = layout.pop("kind")
    if kind not in ("single", "mesh"):
        raise ValueError(f"the benchmark places no {kind!r} executor on chips")
    if "partitioner" in layout:
        layout["partitioner"] = resolve_partitioner(layout["partitioner"])
    if kind == "mesh":
        from repro.launch.mesh import make_host_mesh

        if len(jax.devices()) != len(devices):
            raise ValueError(f"a mesh cell of {len(devices)} chips needs a host "
                             f"of as many, JAX found {len(jax.devices())}")
        layout["mesh"] = make_host_mesh((len(devices), 1), ("data", "model"))
    try:
        host = jax.devices("cpu")[0]
    except RuntimeError:  # no CPU backend: arrays land on the chip directly
        host = devices[0]
    with jax.default_device(host):
        ex = make_executor(
            kind, corpus, algorithm=config["algorithm"],
            budgets=QueryBudgets(**config["budgets"]),
            weights=RankWeights(**config["weights"]), grid=config["grid"],
            m_intervals=config["m_intervals"], fused=config["fused"],
            compress=config["compress"], **layout,
        )
    if kind == "single":
        ex.engine.index = jax.block_until_ready(
            jax.device_put(ex.engine.index, devices[0]))
    # a mesh's build placed each shard by its spec, on the mesh's devices
    for x in index_leaves(ex):
        if not x.devices() <= set(devices):
            raise RuntimeError(f"index array on {x.devices()}, not on the cell's chips")
    return ex


def index_leaves(ex) -> list:
    """The arrays of the executor's index, whatever executor holds it."""
    import jax

    tree = ex.engine.index if hasattr(ex, "engine") else ex._index  # single, mesh
    return [x for x in jax.tree.leaves(tree) if isinstance(x, jax.Array)]


def build_server(config: dict, corpus, devices, telemetry=None):
    """The configured deployment: executor (index on ``devices``), result
    cache, batcher and ``GeoServer``, built with the program's own
    builders."""
    from repro.serving import DeadlineBatcher, GeoServer, make_cache

    ex = build_executor(config, corpus, devices)
    b = config["batcher"]
    batcher = DeadlineBatcher(
        max_batch=b["batch"], max_terms=b["terms"], max_rects=b["rects"],
        term_buckets=[b["terms"]], rect_buckets=[b["rects"]],
        batch_sizes=[b["batch"]], max_wait_s=b["max_wait_s"],
    )
    c = config["cache"]
    cache = make_cache(c["policy"], c["capacity"]) if c else None
    if telemetry is not None:
        ex = Annotated(ex)
    server = GeoServer(
        ex, cache=cache, batcher=batcher, coalesce=config["coalesce"],
        telemetry=telemetry,
    )
    return server, ex


class Annotated:
    """The executor, with each batch's call marked on the profiler's clock
    (``exec:<plan>``), so that the device trace's idle gaps can be told
    apart by what the host was doing."""

    def __init__(self, ex):
        self._ex = ex

    def __getattr__(self, name):
        return getattr(self._ex, name)

    def run(self, batch, plan):
        import jax

        label = plan.label if plan is not None else self._ex.algorithm
        with jax.profiler.TraceAnnotation(f"exec:{label}"):
            return self._ex.run(batch, plan=plan)


def warm_plans(ex, stream, batch: dict) -> list:
    """Compile (or load from the cache) each plan the stream takes, at the
    one batch shape the batcher emits, with an inert batch."""
    import jax
    import jax.numpy as jnp

    from repro.core.algorithms import QueryBatch

    t0 = time.perf_counter()
    distinct = {id(q): q for q in stream}
    plan_of = {k: ex.plan_query(q.terms, q.rects, q.amps) for k, q in distinct.items()}
    # a fixed algorithm plans nothing (None): the executor's one program
    label = {k: p.label if p is not None else ex.algorithm for k, p in plan_of.items()}
    plans = {label[k]: p for k, p in plan_of.items()}
    first = Counter(label[id(q)] for q in stream[:512])
    print(f"setup planned {time.perf_counter() - t0:.2f}s first512={dict(first)}",
          file=sys.stderr, flush=True)
    B, D, R = batch["batch"], batch["terms"], batch["rects"]
    rects = np.zeros((B, R, 4), np.float32)
    rects[:, :, :2] = 1.0  # empty rects: the inert batch matches nothing
    inert = QueryBatch(
        terms=jnp.full((B, D), -1, jnp.int32), rects=jnp.asarray(rects),
        amps=jnp.zeros((B, R), jnp.float32),
    )
    for label, p in plans.items():
        t0 = time.perf_counter()
        jax.block_until_ready(ex.run(inert, plan=p).scores)
        print(f"setup warm {label} {time.perf_counter() - t0:.2f}s",
              file=sys.stderr, flush=True)
    return sorted(plans)


# ----------------------------------------------------------------------
# what a run records, for the metric readers
# ----------------------------------------------------------------------
@dataclass
class Run:
    """What the metric readers read (``metrics/<name>.py``)."""

    config: dict
    setup_s: float = 0.0
    window_s: float = 0.0  # first admission to the last answer
    queries: int = 0  # answered in the window
    latencies_s: list = field(default_factory=list)
    reports: list = field(default_factory=list)  # one ServeReport a chunk
    batch_spans: list = field(default_factory=list)  # (label, t0, t1, rows)
    query_spans: list = field(default_factory=list)  # (kind, latency_s)
    device: object = None  # devtrace.DeviceTrace of a traced run

    def batch_service_s(self) -> float:
        return sum(e.done_t - e.start_t for r in self.reports for e in r.batch_events)

    def total(self, attr: str) -> float:
        return sum(getattr(r, attr) for r in self.reports)

    def plan_queries(self) -> dict:
        out: dict = {}
        for r in self.reports:
            for k, v in r.plan_queries.items():
                out[k] = out.get(k, 0) + v
        return out

    def plan_stat(self, label: str, key: str) -> float:
        return sum(r.plan_stats.get(label, {}).get(key, 0.0) for r in self.reports)


def window(server, chunks, start: int, seconds: float, run: Run):
    """Serve chunk after chunk from ``start`` until ``seconds`` have
    passed; returns the queries served and their answers, in order."""
    queries, answers = [], []
    t0 = time.perf_counter()
    i = start
    while True:
        if i == len(chunks):
            raise RuntimeError("the traffic mix's stream ran out inside the window")
        rep = server.run_trace(
            chunks[i], warmup=False, arrival="closed", collect_results=True
        )
        run.reports.append(rep)
        queries += chunks[i]
        answers += rep.results
        i += 1
        if time.perf_counter() - t0 >= seconds:
            break
    run.window_s = time.perf_counter() - t0
    run.queries = sum(r.n_queries for r in run.reports)
    run.latencies_s = [x for r in run.reports for x in r.latencies_s]
    return queries, answers


def inputs(cell: Cell, seed: int):
    """The run's corpus and query stream.

    The deployment's documents come from its fixed ``data_seed``; ``seed``
    renames their terms and orders the searches inside each chunk.  So
    every seed serves the same posting lists, index shapes and chunks of
    searches, under other names and in another order, and every seed's
    programs are the first seed's.
    """
    from benchmarks.chip import corpus, traffic

    base = corpus.make_corpus(cell.config, cell.config["data_seed"])
    docs = corpus.relabel_terms(base, np.random.SeedSequence([seed, 0]))
    del base
    return docs, traffic.generate(docs, cell.mix, np.random.SeedSequence([seed, 1]))


def check_limits(numbers: dict, limits: dict) -> dict:
    return {k: {"value": numbers[k], "limit": limits[k]} for k in numbers}


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, t_start: float,
             root: str, require_chip: bool = True, wrap_executor=None) -> dict:
    """One run; returns the result line (a dict, ``check`` its last key).

    ``wrap_executor`` (tests only) puts a wrapper round the executor before
    the server is built, to plant a fault under the timed path.
    """
    import jax

    from benchmarks.chip import check, devtrace, traffic
    from benchmarks.chip.reference import Reference

    log = lambda *a: print(*a, file=sys.stderr, flush=True)  # noqa: E731
    if require_chip:
        devs = chips(cell.chips)
        peaks_of(devs[0].device_kind)
    else:
        devs = jax.devices()
    dev, used = devs[0], devs[: cell.chips]
    counter = CompileCounter()
    log(f"setup start {time.perf_counter() - t_start:.2f}s")
    config, mix = cell.config, cell.mix
    corpus, stream = inputs(cell, seed)
    chunks = traffic.chunks(stream, mix["chunk"])
    log(f"setup corpus+stream {time.perf_counter() - t_start:.2f}s")

    telemetry = None
    if trace:
        from repro.obs import SpanRecorder, Telemetry

        telemetry = Telemetry(metrics=None, tracer=SpanRecorder(), audit=None,
                              events=None)
    server, ex = build_server(config, corpus, used, telemetry)
    index = index_leaves(ex._ex if isinstance(ex, Annotated) else ex)
    if wrap_executor is not None:
        server.executor = ex = wrap_executor(ex)
    log(f"setup build+place {time.perf_counter() - t_start:.2f}s")
    plans = warm_plans(ex, stream, config["batcher"])
    warm = config["warm_chunks"]
    for i in range(warm):
        server.run_trace(chunks[i], warmup=False, arrival="closed")
    run = Run(config=config)
    run.setup_s = time.perf_counter() - t_start
    log(f"setup done {run.setup_s:.2f}s plans={plans} compile_requests="
        f"{counter.requests} cache_hits={counter.hits}")

    n_req = counter.requests
    n_spans = len(telemetry.tracer.batches) if trace else 0
    n_qspans = len(telemetry.tracer.queries) if trace else 0
    with tempfile.TemporaryDirectory() as tdir:
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(tdir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation("window"):
                queries, answers = window(server, chunks, warm, seconds, run)
        finally:
            if trace:
                jax.profiler.stop_trace()
        if trace:
            run.device = devtrace.read(tdir)
            run.batch_spans = [
                (b.label, b.start_t, b.done_t, b.n_real)
                for b in telemetry.tracer.batches[n_spans:]
            ]
            run.device.name_modules([b[0] for b in run.batch_spans])
            run.query_spans = [
                (q.kind, q.latency) for q in telemetry.tracer.queries[n_qspans:]
            ]
    window_compiles = counter.requests - n_req
    log(f"window {run.window_s:.2f}s queries={run.queries} chunks={len(run.reports)}"
        f" compile_requests={window_compiles}")
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in used]
    device = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(devs),
        "memory_peak_bytes": max(peaks),
    }
    if len(used) > 1:
        device["memory_peak_bytes_each"] = peaks
    if trace:
        device["busy_s"] = run.device.busy_s
        device["window_s"] = run.device.window_s

    metrics = {}
    for m in cell.per_layer if trace else cell.end_to_end:
        v = reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    # the check, once the program's state is freed
    for x in index:
        x.delete()
    del server, ex, index
    gc.collect()
    jax.clear_caches()
    t_ref = time.perf_counter()
    ref = Reference(corpus, config, devices=used)
    log(f"check placed {time.perf_counter() - t_ref:.2f}s")
    b = config["batcher"]
    res = check.compare(queries, answers, ref, b["terms"], b["rects"])
    limits = config["check"]["limits"]
    numbers = res["numbers"]
    correct = all(numbers[k] <= limits[k] for k in numbers)
    bad = res["missing"] | (res["rank"] > limits["rank_gap"]) | (
        res["doc"] > limits["doc_gap"]
    ) | res["dup"]
    log(f"check {time.perf_counter() - t_ref:.2f}s answers={len(answers)}")
    line = {
        "correct": bool(correct),
        "attempted": len(queries),
        "failed": int(bad.sum()),
        "metrics": metrics,
        "device": device,
    }
    if trace:
        line["breakdown"] = devtrace.breakdown(run.device)
    line["check"] = check_limits(numbers, limits)
    return line
