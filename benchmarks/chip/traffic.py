"""Query streams, driven by a traffic mix's data file.

A mix (``traffic/<name>.json``) names its generator under ``generator``:
``generators/<name>.py``, whose ``generate(corpus, mix, seed)`` returns
the stream.  Every mix sets

* ``queries`` — length of the stream generated (the window takes what it
  needs from the front, in chunks);
* ``chunk`` — queries a client sends as one closed-loop round;

and whatever else its generator reads (see each generator's docstring).
A generator draws the searches from the mix's own fixed seed; the run's
seed only orders the searches inside each chunk (:func:`shuffle_chunks`),
so every seed does the same work, chunk by chunk.
"""
from __future__ import annotations

import importlib.util
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def zipf_pmf(a: float, n: int) -> np.ndarray:
    """P(rank k) for k = 1..n, proportional to k^-a."""
    p = np.arange(1, n + 1, dtype=np.float64) ** -a
    return p / p.sum()


def shuffle_chunks(items: np.ndarray, size: int, seed) -> None:
    """Permute ``items`` in place inside each run of ``size``, in an order
    drawn from ``seed``."""
    order = np.random.default_rng(seed)
    for s in range(0, len(items), size):
        items[s : s + size] = order.permutation(items[s : s + size])


def generator(name: str):
    """The ``generate(corpus, mix, seed)`` function of ``generators/<name>.py``."""
    path = os.path.join(HERE, "generators", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"chip_generator_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.generate


def generate(corpus, mix: dict, seed) -> list:
    """The stream of the mix's generator over ``corpus`` for ``seed``."""
    return generator(mix["generator"])(corpus, mix, seed)


def chunks(stream: list, size: int) -> list[list]:
    return [stream[i : i + size] for i in range(0, len(stream), size)]
