#!/usr/bin/env python3
"""Chip benchmark of the geo search engine: one run of one cell.

    python3 benchmarks/chip/run.py --workload geoweb.zipf --seed 7 \\
        --seconds 40 --trace 0

Run from the root of a checkout, on a machine that holds the chips the
cell asks for.  The cell (``--workload``) is an entry of ``BENCHMARK.json``.
The run builds the cell's deployment from ``--seed``, warms it up, serves
the cell's traffic for ``--seconds``, checks every answer of the window
against the plain reference and prints one JSON line as the last line of
standard output: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics, or with ``--trace 1`` its per-layer ones, read
from a profiler trace of the window), ``device`` and ``check`` (each
number compared, with its limit; they are also the last lines of standard
error).  ``--data-seed`` serves the deployment over the documents of
another seed than its configuration's ``data_seed``, to check the answers
on another corpus; a cell's runs leave it out.  Without a TPU, or with
fewer chips than the cell asks for, it prints no result and exits 3.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--data-seed", type=int, default=None)
    args = ap.parse_args(argv)
    # the checkout's program and benchmark, ahead of any installed copy
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"no result: no program under {src}", file=sys.stderr)
        return 2
    sys.path[:0] = [src, ROOT]
    from benchmarks.chip import harness

    cell = harness.load_cell(ROOT, args.workload)
    if args.data_seed is not None:
        cell.config["data_seed"] = args.data_seed
    try:
        harness.chips(cell.chips)
    except harness.NoChip as e:
        print(f"no result: {e}", file=sys.stderr)
        return 3
    harness.enable_compile_cache(ROOT)
    line = harness.run_cell(
        cell, args.seed, args.seconds, bool(args.trace), T_START, ROOT
    )
    print(json.dumps(line), flush=True)
    for name, c in line["check"].items():
        print(f"check {name}={c['value']!r} limit={c['limit']!r}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
