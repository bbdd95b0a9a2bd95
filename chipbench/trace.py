"""Reduction of a profiler trace to the benchmark's device metrics.

One unit runs under the profiler; nothing is written to disk. From the
trace's TPU planes come the device operations ("XLA Ops"), from its host
plane the benchmark's own spans (``TraceAnnotation``s named
``chipbench/<label>``), all on the profiler's one clock.

- busy: the union of the intervals in which a leaf operation ran. The ops
  that only enclose others (a ``while`` loop, a conditional, a call) are
  left out, so the gaps between the steps of a loop count as idle.
- device time of a layer: the union of every operation, enclosing ones
  included, clipped to that layer's host spans: how long the device was
  executing what the layer launched.
- idle time: the complement of busy in the traced window, each stretch of
  it put to the innermost host span that covers it (``other`` if none).
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Sequence, Tuple

import numpy as np

SPAN_PREFIX = "chipbench/"
ENCLOSING_OPS = ("while", "conditional", "call")

Interval = Tuple[float, float]


def union(starts: np.ndarray, ends: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Merge intervals into disjoint sorted ones."""
    if len(starts) == 0:
        return np.zeros(0), np.zeros(0)
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], np.maximum.accumulate(ends[order])
    cut = np.nonzero(s[1:] > e[:-1])[0]
    return (s[np.concatenate([[0], cut + 1])],
            e[np.concatenate([cut, [len(s) - 1]])])


def clip_total(merged: Tuple[np.ndarray, np.ndarray],
               windows: Sequence[Interval]) -> float:
    """Length of the disjoint intervals ``merged`` inside the windows."""
    ws, we = union(np.array([w[0] for w in windows], float),
                   np.array([w[1] for w in windows], float))
    s, e = merged
    total = 0.0
    for lo, hi in zip(ws, we):
        total += float(np.clip(np.minimum(e, hi) - np.maximum(s, lo),
                               0.0, None).sum())
    return total


def op_name(hlo_text: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return hlo_text.split(" = ", 1)[0].lstrip("%")


_OPCODE = re.compile(r"(?:^|\s)([a-z][a-z0-9\-]*)\(")


def op_kind(hlo_text: str) -> str:
    """The HLO opcode of an op's text (``while``, ``fusion``, ...): the
    first lower-case word after the result's shape that opens a list."""
    m = _OPCODE.search(hlo_text.split(" = ", 1)[-1])
    return m.group(1) if m else ""


@dataclass
class TraceEvents:
    """What the reduction reads from one trace."""

    leaf: List[List[Tuple[float, float, str]]]  # per chip: (start, end, op)
    enclosing: List[List[Interval]]  # per chip
    spans: List[Tuple[str, float, float]]  # (label, start, end), seconds


def read_xspace(xspace: bytes) -> TraceEvents:
    from jax.profiler import ProfileData

    pd = ProfileData.from_serialized_xspace(xspace)
    leaf, enclosing, spans = [], [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lf, enc = [], []
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    e = s + ev.duration_ns * 1e-9
                    if op_kind(ev.name) in ENCLOSING_OPS:
                        enc.append((s, e))
                    else:
                        lf.append((s, e, op_name(ev.name)))
            leaf.append(lf)
            enclosing.append(enc)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        s = ev.start_ns * 1e-9
                        spans.append((ev.name[len(SPAN_PREFIX):], s,
                                      s + ev.duration_ns * 1e-9))
    return TraceEvents(leaf=leaf, enclosing=enclosing, spans=spans)


@dataclass
class TraceSummary:
    window_s: float
    chips: int  # device planes in the trace
    busy_s: float  # averaged over the chips traced
    device_s: Dict[str, float]  # layer label -> device seconds in its spans
    op_seconds: Dict[str, float] = field(default_factory=dict)
    idle_by_label: Dict[str, float] = field(default_factory=dict)

    @property
    def idle_pct(self) -> float:
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def breakdown(self, n: int = 10) -> dict:
        ops = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])[:n]
        idle = sorted(self.idle_by_label.items(), key=lambda kv: -kv[1])[:n]
        return dict(device_ops=[[k, v] for k, v in ops],
                    idle_gaps=[[k, v] for k, v in idle])


def summarize(ev: TraceEvents, unit_label: str) -> TraceSummary:
    """Reduce one traced unit: its window is the unit's host span."""
    units = [(s, e) for label, s, e in ev.spans if label == unit_label]
    if not units:
        raise ValueError(f"no {SPAN_PREFIX}{unit_label} span in the trace")
    window = (min(s for s, _ in units), max(e for _, e in units))
    labels = sorted({label for label, _, _ in ev.spans})
    busy, device_s = [], {label: 0.0 for label in labels}
    op_seconds: Dict[str, float] = {}
    idle: Dict[str, float] = {}
    inner_first = sorted(ev.spans, key=lambda x: x[2] - x[1])
    for lf, enc in zip(ev.leaf, ev.enclosing):
        ls = np.array([x[0] for x in lf], float)
        le = np.array([x[1] for x in lf], float)
        merged_leaf = union(ls, le)
        busy.append(clip_total(merged_leaf, [window]))
        merged_all = union(np.concatenate([ls, [x[0] for x in enc]]),
                           np.concatenate([le, [x[1] for x in enc]]))
        for label in labels:
            device_s[label] += clip_total(
                merged_all, [(s, e) for lb, s, e in ev.spans if lb == label])
        inside = np.clip(np.minimum(le, window[1]) - np.maximum(ls, window[0]),
                         0.0, None)
        for (_, _, name), t in zip(lf, inside.tolist()):
            if t > 0.0:
                op_seconds[name] = op_seconds.get(name, 0.0) + t
        # idle time between consecutive span boundaries belongs to the
        # innermost span that covers that stretch
        cuts = sorted({window[0], window[1]} | {
            t for _, s, e in ev.spans for t in (s, e)
            if window[0] < t < window[1]})
        for a, b in zip(cuts[:-1], cuts[1:]):
            mid = 0.5 * (a + b)
            label = next((lb for lb, s, e in inner_first if s <= mid <= e),
                         "other")
            gap = (b - a) - clip_total(merged_leaf, [(a, b)])
            if gap > 0.0:
                idle[label] = idle.get(label, 0.0) + gap
    n = max(1, len(ev.leaf))
    return TraceSummary(
        window_s=window[1] - window[0], chips=len(ev.leaf),
        busy_s=float(np.sum(busy)) / n,
        device_s={k: v / n for k, v in device_s.items()},
        op_seconds={k: v / n for k, v in op_seconds.items()},
        idle_by_label={k: v / n for k, v in idle.items()})


class Tracer:
    """The profiler around one unit, kept in memory."""

    def __init__(self):
        import jax

        self.options = jax.profiler.ProfileOptions()
        self.options.python_tracer_level = 0
        self.options.host_tracer_level = 2
        self.session = None
        self.xspace = b""

    def start(self) -> None:
        import jax
        from jax._src.lib import _profiler

        jax.devices()  # the TPU tracer needs the backend up first
        self.session = _profiler.ProfilerSession(self.options)

    def stop(self) -> None:
        self.xspace = self.session.stop()
        self.session = None

    def reduce(self, unit_label: str) -> TraceSummary:
        return summarize(read_xspace(self.xspace), unit_label)
