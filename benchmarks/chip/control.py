#!/usr/bin/env python3
"""The control of the check: the plain reference in the program's place,
computed in bfloat16, the precision below the float32 that the
configuration states.  The comparison (``check.py``) has to find it not
correct; its readings set the upper end of each limit.

    python3 benchmarks/chip/control.py --workload geoweb.zipf \\
        --seeds 11,12,13 --queries 192

For each seed it builds the cell's corpus and stream as a run does, takes
the ``--queries`` queries a window would serve first, answers them with
the bfloat16 reference, and prints the comparison's numbers against the
float32 reference, one JSON line a seed.  The benchmark's runs never run
it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


class Answer:
    def __init__(self, ids, scores):
        self.ids, self.scores = ids, scores


def control_answers(queries, ref_low, d_terms: int, q_rects: int, batch: int = 8):
    """The low-precision reference's answer to each query (one call per
    distinct query object)."""
    import numpy as np

    from benchmarks.chip.reference import pad_queries

    distinct = list({id(q): q for q in queries}.values())
    got = {}
    for s in range(0, len(distinct), batch):
        part = distinct[s : s + batch]
        pad = part + part[:1] * (batch - len(part))
        terms, rects, amps = pad_queries(pad, d_terms, q_rects)
        served = np.full((batch, ref_low.k), -1, np.int32)
        ids, scores, _ = ref_low.answer(terms, rects, amps, served)
        for j, q in enumerate(part):
            got[id(q)] = Answer(ids[j], scores[j])
    return [got[id(q)] for q in queries]


def readings(cell, seed: int, n_queries: int, devices=None) -> dict:
    """The comparison's numbers for the control on one seed."""
    import jax.numpy as jnp

    from benchmarks.chip import check
    from benchmarks.chip.harness import inputs
    from benchmarks.chip.reference import Reference

    config, mix = cell.config, cell.mix
    corpus, stream = inputs(cell, seed)
    start = config["warm_chunks"] * mix["chunk"]
    queries = stream[start : start + n_queries]
    b = config["batcher"]
    low = Reference(corpus, config, dtype=jnp.bfloat16, devices=devices)
    answers = control_answers(queries, low, b["terms"], b["rects"])
    del low
    ref = Reference(corpus, config, devices=devices)
    return check.compare(queries, answers, ref, b["terms"], b["rects"])["numbers"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--queries", type=int, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    from benchmarks.chip import harness

    cell = harness.load_cell(ROOT, args.workload)
    devs = harness.chips(cell.chips)[: cell.chips]
    harness.enable_compile_cache(ROOT)
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        nums = readings(cell, seed, args.queries, devices=devs)
        limits = cell.config["check"]["limits"]
        failed = [k for k in nums if nums[k] > limits[k]]
        print(json.dumps({"seed": seed, "numbers": nums, "fails": failed,
                          "seconds": time.perf_counter() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
