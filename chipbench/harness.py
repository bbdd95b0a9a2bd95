"""The benchmark harness: finds a cell's files by name and runs it.

Everything that belongs to one configuration, traffic mix, entry kind or
metric lives in a file of its own, found by the name ``BENCHMARK.json``
gives it:

- ``configs/<config>.json``   the deployment, as it is run;
- ``traffic/<traffic>.json``  the parameters of the traffic mix, whose
  ``kind`` names the entry driver;
- ``drivers/<kind>.py``       ``setup``, ``unit`` and ``compare`` of an entry;
- ``metrics/<metric>.py``     ``read(run)`` for one metric; a metric split
  by the end-to-end metric it moves (``lower_s.plan``, ``lower_s.tail``)
  is read by the file of its name before the last dot (``lower_s.py``)
  where it has none of its own.

A run sets up (build, warm every shape the cell uses), measures whole units
until ``--seconds`` have passed, reads its metrics, and then compares what
the timed units produced with the plain reference. The last line of
standard output is one JSON object; the numbers compared are printed with
their limits as the last lines of standard error and, under ``checks``, as
the last key of that object.
"""

from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import sys
import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from pathlib import Path
from types import ModuleType
from typing import Callable, Dict, List, Optional

import numpy as np

HERE = Path(__file__).resolve().parent


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def load_module(path: Path) -> ModuleType:
    """Import a file by path (metric files carry dots in their names)."""
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    driver: ModuleType
    end_to_end: List[dict]
    per_layer: List[dict]
    readers: Dict[str, Callable]


def cell_metrics(manifest: dict, cell: str) -> tuple:
    """The end-to-end and per-layer metrics a cell reports."""
    e2e = [m for m in manifest["end_to_end"]
           if cell in m.get("workloads", [cell])]
    names = {m["name"] for m in e2e}
    layer = [m for m in manifest["per_layer"]
             if cell in m.get("workloads", [cell] if m["moves"] in names
                              else [])]
    return e2e, layer


def metric_file(base: Path, name: str) -> Path:
    """The reader of metric ``name``: its own file, or that of its name
    before the last dot."""
    path = base / "metrics" / f"{name}.py"
    if not path.is_file() and "." in name:
        path = base / "metrics" / f"{name.rsplit('.', 1)[0]}.py"
    return path


def resolve_cell(manifest: dict, name: str, base: Path = HERE) -> Cell:
    """Find a cell's configuration, traffic, driver and metric readers."""
    entry = next((w for w in manifest["workloads"] if w["name"] == name),
                 None)
    if entry is None:
        known = ", ".join(w["name"] for w in manifest["workloads"])
        raise KeyError(f"unknown workload {name!r}; known: {known}")
    config = json.loads((base / "configs" / f"{entry['config']}.json")
                        .read_text())
    traffic = json.loads((base / "traffic" / f"{entry['traffic']}.json")
                         .read_text())
    driver = load_module(base / "drivers" / f"{traffic['kind']}.py")
    e2e, layer = cell_metrics(manifest, name)
    readers = {m["name"]: load_module(metric_file(base, m["name"])).read
               for m in e2e + layer}
    return Cell(name=name, chips=int(entry["chips"]), config=config,
                traffic=traffic, driver=driver, end_to_end=e2e,
                per_layer=layer, readers=readers)


def unit_seed(seed: int, u: int) -> int:
    """The seed of unit ``u`` of a run: fresh per unit, fixed per seed."""
    ss = np.random.SeedSequence([int(seed), int(u)])
    return int(ss.generate_state(1, np.uint32)[0] >> 1)


WARMUP_UNIT = 1 << 20  # unit index of the set-up's warm-up unit


@dataclass
class Run:
    """Everything a metric reader may read."""

    cell: Cell
    seed: int
    setup_s: float = 0.0
    window_s: float = 0.0
    records: List[dict] = field(default_factory=list)
    unit_s: List[float] = field(default_factory=list)
    spans: List[Dict[str, float]] = field(default_factory=list)
    calls: List[List[dict]] = field(default_factory=list)
    trace_calls: List[dict] = field(default_factory=list)
    trace: Optional[object] = None  # chipbench.trace.TraceSummary
    device: Dict[str, object] = field(default_factory=dict)
    peaks: Dict[str, float] = field(default_factory=dict)


class Spans:
    """Host spans from the benchmark's own wrappers around the module
    attributes an entry calls: seconds per label for the current unit,
    the arguments of each call, and, while the profiler runs, a
    ``TraceAnnotation`` named ``chipbench/<label>`` on its clock."""

    def __init__(self):
        self.current: Dict[str, float] = {}
        self.calls: List[dict] = []
        self._undo: List[tuple] = []

    @contextmanager
    def span(self, label: str):
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("chipbench/" + label):
            try:
                yield
            finally:
                self.current[label] = (self.current.get(label, 0.0)
                                       + time.perf_counter() - t0)

    def wrap(self, module: ModuleType, attr: str, label: str,
             describe: Optional[Callable] = None) -> None:
        orig = getattr(module, attr)

        def wrapped(*args, **kwargs):
            if describe is not None:
                self.calls.append(dict(label=label, **describe(*args, **kwargs)))
            with self.span(label):
                return orig(*args, **kwargs)
        setattr(module, attr, wrapped)
        self._undo.append((module, attr, orig))

    def take(self):
        out, calls = self.current, self.calls
        self.current, self.calls = {}, []
        return out, calls

    def close(self):
        for module, attr, orig in reversed(self._undo):
            setattr(module, attr, orig)
        self._undo = []


class CompileEvents:
    """Counts JAX's compile events (backend compiles and persistent-cache
    hits and misses) from its monitoring stream."""

    def __init__(self):
        self.counts: Dict[str, int] = {}

    def __call__(self, event: str, *args, **kwargs):
        if "compil" in event:
            self.counts[event] = self.counts.get(event, 0) + 1

    def total(self) -> int:
        return sum(self.counts.values())


def device_info(chips: int, *, require_tpu: bool = True) -> dict:
    import jax

    devices = jax.devices()
    dev = devices[0]
    if require_tpu and dev.platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {dev.platform!r}")
    if len(devices) < chips:
        raise NoChip(f"needs {chips} chips; JAX found {len(devices)}")
    return dict(platform=dev.platform, kind=dev.device_kind, count=chips)


def peak_table(kind: str) -> Dict[str, float]:
    """The chip's published peaks, from ``peaks.json``; a device that is
    not in the table is an error, not a default."""
    table = json.loads((HERE / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table["devices"][kind]


def memory_peak_bytes(chips: int) -> Optional[int]:
    import jax

    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def measure(cell: Cell, seed: int, seconds: float, trace: bool, *,
            t_start: float, require_tpu: bool = True,
            say=print) -> dict:
    """Set up, measure, read metrics and check outputs; returns the result
    object (without printing it)."""
    import jax

    run = Run(cell=cell, seed=seed)
    run.device = device_info(cell.chips, require_tpu=require_tpu)
    run.peaks = peak_table(run.device["kind"]) if require_tpu else {}

    events = CompileEvents()
    jax.monitoring.register_event_listener(events)
    try:
        return _measure(run, events, seed, seconds, trace, t_start, say)
    finally:
        jax.monitoring.unregister_event_listener(events)


def _measure(run: Run, events: CompileEvents, seed: int, seconds: float,
             trace: bool, t_start: float, say) -> dict:
    cell = run.cell

    drv = cell.driver
    t_driver = time.perf_counter()
    state = drv.setup(cell.config, cell.traffic, seed,
                      unit_seed(seed, WARMUP_UNIT))
    run.setup_s = time.perf_counter() - t_start
    setup = dict(seconds=run.setup_s, driver_s=time.perf_counter() - t_driver,
                 compile_events=dict(events.counts))
    compiles_before = events.total()
    traces_before = drv.trace_count()

    spans = Spans() if trace else None

    def one_unit(u: int) -> None:
        t = time.perf_counter()
        if spans is None:
            run.records.append(drv.unit(state, unit_seed(seed, u)))
            run.unit_s.append(time.perf_counter() - t)
            return
        with spans.span(drv.UNIT_SPAN):
            run.records.append(drv.unit(state, unit_seed(seed, u)))
        run.unit_s.append(time.perf_counter() - t)
        s, calls = spans.take()
        run.spans.append(s)
        run.calls.append(calls)

    if spans is not None:
        drv.wrap(spans)
    try:
        t0 = time.perf_counter()
        while True:
            one_unit(len(run.records))
            if time.perf_counter() - t0 >= seconds:
                break
        run.window_s = time.perf_counter() - t0
        window_compiles = dict(compile_events=events.total() - compiles_before,
                               runner_traces=drv.trace_count() - traces_before)
        say(json.dumps(dict(setup=setup,
                            window=dict(units=len(run.records),
                                        seconds=run.window_s,
                                        **window_compiles))))
        if any(window_compiles.values()):
            print(f"warning: the measured window compiled: {window_compiles}",
                  file=sys.stderr)
        run.device["memory_peak_bytes"] = memory_peak_bytes(cell.chips)
        if trace:
            # one more unit, after the window, under the profiler: its trace
            # gives the device metrics, the window's spans the host ones
            from chipbench.trace import Tracer

            tracer = Tracer()
            tracer.start()
            one_unit(len(run.records))
            tracer.stop()
            run.trace_calls = run.calls.pop()
            run.spans.pop()
            run.trace = tracer.reduce(drv.UNIT_SPAN)
            run.device["busy_s"] = run.trace.busy_s
            run.device["window_s"] = run.trace.window_s
    finally:
        if spans is not None:
            spans.close()

    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = cell.readers[m["name"]](run)
        if value is not None:
            metrics[m["name"]] = dict(value=float(value), unit=m["unit"])

    # the program's state goes before the reference runs
    del state
    gc.collect()
    values = drv.compare(cell.config, cell.traffic, run.records, seed)
    limits = cell.traffic["limits"]
    checks = {k: dict(value=v, limit=float(limits[k]))
              for k, v in values.items()}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    out = dict(correct=bool(correct), attempted=len(run.records), failed=0,
               metrics=metrics, device=run.device)
    if run.trace is not None:
        out["breakdown"] = run.trace.breakdown()
    # each unit's own seconds, the traced one last where there is one
    out["unit_s"] = run.unit_s
    out["checks"] = checks
    return out


def main(argv, *, t_start: float, root: Path) -> int:
    ap = argparse.ArgumentParser(description="Run one cell of the chip "
                                 "benchmark on the chips of this machine.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    cell = resolve_cell(manifest, args.workload)

    import jax

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    # every program the cell runs, however quick to compile, is cached
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        out = measure(cell, args.seed, args.seconds, bool(args.trace),
                      t_start=t_start)
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(f"correct: {out['correct']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0
