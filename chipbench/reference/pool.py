"""Runs a reference over a run's members in worker processes.

The references are plain numpy, vectorised over members, and each member's
numbers come from its own seed alone, so the members of a unit split into
slices with no effect on any answer. The workers start fresh (``spawn``):
the benchmark's own process holds the chip, and a worker imports only
numpy and the reference.
"""

from __future__ import annotations

import multiprocessing
import os
from concurrent.futures import ProcessPoolExecutor
from typing import Callable, Dict, Sequence

import numpy as np


MAX_WORKERS = 16


def workers() -> int:
    """Worker processes: one per core this process may run on, one core
    left to the rest of the machine."""
    return max(1, min(MAX_WORKERS, len(os.sched_getaffinity(0)) - 1))


def map_members(fn: Callable[..., Dict[str, np.ndarray]],
                seeds: Sequence[int], *args) -> Dict[str, np.ndarray]:
    """``fn(seeds, *args)``: a dict of arrays whose first axis is the
    member, computed over slices of ``seeds`` in parallel and joined."""
    seeds = [int(s) for s in seeds]
    k = min(workers(), len(seeds))
    if k <= 1:
        return fn(seeds, *args)
    parts = [[int(s) for s in p] for p in np.array_split(seeds, k)]
    with ProcessPoolExecutor(
            k, mp_context=multiprocessing.get_context("spawn")) as ex:
        outs = list(ex.map(fn, parts, *[[a] * k for a in args]))
    return {key: np.concatenate([o[key] for o in outs]) for key in outs[0]}

