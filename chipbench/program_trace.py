"""The program's own spans and counters against a profiler trace.

A live ``repro.obs`` recorder puts each span on the profiler's clock as a
``TraceAnnotation`` named ``polca/<name>``. ``program_split`` reduces one
traced unit by those spans, apart from ``chipbench.trace.summarize`` (which
reads only the benchmark's own ``chipbench/`` spans): for each name, how
long the unit spent inside it, and how much of that the device was idle,
inside a loop or outside one. ``span_mean`` and ``counter_mean`` read the
recorder's snapshots, one per unit.

What the batched engine's spans and counters give:

- transfer: ``batched/h2d`` + ``batched/d2h`` seconds, and the counters
  ``batched_h2d_bytes_total`` + ``batched_d2h_bytes_total``;
- host work around the scan: ``batched/operands`` + ``batched/unpack``;
- the scan's gaps: the idle seconds inside ``batched/run`` (the runner's
  call until its outputs are ready).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from chipbench.trace import SPAN_PREFIX, Interval, TraceEvents, clip_total, \
    union

PROGRAM_PREFIX = "polca/"

TRANSFER_SPANS = ("batched/h2d", "batched/d2h")
HOST_SPANS = ("batched/operands", "batched/unpack")
RUN_SPAN = "batched/run"
BYTE_COUNTERS = ("batched_h2d_bytes_total", "batched_d2h_bytes_total")


def read_program_spans(xspace: bytes) -> List[Tuple[str, float, float]]:
    """The program's spans on the host planes: (name, start, end), seconds,
    the name without its ``polca/``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_serialized_xspace(xspace)
    spans = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PROGRAM_PREFIX):
                    s = ev.start_ns * 1e-9
                    spans.append((ev.name[len(PROGRAM_PREFIX):], s,
                                  s + ev.duration_ns * 1e-9))
    return spans


def _overlaps(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """The stretches where an interval of ``a`` meets one of ``b``."""
    return [(max(s, lo), min(e, hi)) for s, e in a for lo, hi in b
            if min(e, hi) > max(s, lo)]


def _merged(intervals) -> Tuple[np.ndarray, np.ndarray]:
    return union(np.array([x[0] for x in intervals], float),
                 np.array([x[1] for x in intervals], float))


@dataclass
class ProgramSplit:
    """The traced unit by the program's spans, each name's spans merged.
    Seconds per name: inside the spans, of those with no leaf op running
    (averaged over the chips), and of that idle time the part inside an
    enclosing op (the gaps between a loop's steps)."""

    chips: int
    span_s: Dict[str, float]
    idle_s: Dict[str, float]
    loop_idle_s: Dict[str, float]


def program_split(ev: TraceEvents,
                  program_spans: Sequence[Tuple[str, float, float]],
                  unit_label: str) -> ProgramSplit:
    """Reduce the program's spans of one traced unit against the device
    ops; the unit's window is its benchmark span, as in ``summarize``."""
    units = [(s, e) for label, s, e in ev.spans if label == unit_label]
    if not units:
        raise ValueError(f"no {SPAN_PREFIX}{unit_label} span in the trace")
    window = [(min(s for s, _ in units), max(e for _, e in units))]
    inside: Dict[str, List[Interval]] = {}
    for name in sorted({name for name, _, _ in program_spans}):
        s, e = _merged([(a, b) for n, a, b in program_spans if n == name])
        inside[name] = _overlaps(list(zip(s.tolist(), e.tolist())), window)
    span_s = {n: sum(e - s for s, e in w) for n, w in inside.items()}
    idle = dict.fromkeys(inside, 0.0)
    loop_idle = dict.fromkeys(inside, 0.0)
    for lf, enc in zip(ev.leaf, ev.enclosing):
        merged_leaf = _merged(lf)
        es, ee = _merged(enc)
        loops = list(zip(es.tolist(), ee.tolist()))
        for name, w in inside.items():
            if not w:
                continue
            idle[name] += span_s[name] - clip_total(merged_leaf, w)
            in_loops = _overlaps(w, loops)
            if in_loops:
                loop_idle[name] += (sum(e - s for s, e in in_loops)
                                    - clip_total(merged_leaf, in_loops))
    n = max(1, len(ev.leaf))
    return ProgramSplit(chips=len(ev.leaf), span_s=span_s,
                        idle_s={k: v / n for k, v in idle.items()},
                        loop_idle_s={k: v / n for k, v in loop_idle.items()})


def span_mean(snapshots, names: Sequence[str]) -> Optional[float]:
    """Seconds per unit in the program's spans ``names``, summed, over the
    units' recorder snapshots; None where no snapshot has any of them."""
    totals = [[s.total_s for (n, _), s in snap.spans.items() if n in names]
              for snap in snapshots]
    if not any(totals):
        return None
    return float(np.mean([sum(t) for t in totals]))


def counter_mean(snapshots, names: Sequence[str]) -> Optional[float]:
    """The program's counters ``names`` per unit, summed, over the units'
    recorder snapshots; None where no snapshot has any of them."""
    totals = [[v for (n, _), v in snap.counters.items() if n in names]
              for snap in snapshots]
    if not any(totals):
        return None
    return float(np.mean([sum(t) for t in totals]))


def idle_s(split: Optional[ProgramSplit], name: str) -> Optional[float]:
    """Seconds of the traced unit inside the program's span ``name`` with
    no leaf op running, averaged over the chips; None without a TPU plane
    or without that span."""
    if split is None or not split.chips or name not in split.idle_s:
        return None
    return split.idle_s[name]
