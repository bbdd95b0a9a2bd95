"""Each entry driver at a size a test run holds, on the host CPU: one unit
through the driver agrees with the reference; the float32 control does
not; and a run whose timed path is broken underneath comes out not
correct, once for each fault the cell can have."""

import dataclasses
import json
import time
from pathlib import Path

import numpy as np
import pytest

from chipbench import harness

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "chipbench"
SEED = 2**31 + 977
# one hour at 2 s ticks: long enough for the planner's probes to brake and
# for float32 to drift from float64 by more than the limits
DURATION_S = 3600.0
SMALL = {"plan": dict(n_seeds=4), "tail": dict(n_seeds=16, check_units=2)}
CELLS = {"plan": ("row40", "plan_s8"), "tail": ("row40", "tail_n1024")}


def small_cell(kind: str) -> harness.Cell:
    config_name, traffic_name = CELLS[kind]
    config = json.loads((BENCH / "configs" / f"{config_name}.json")
                        .read_text())
    config["scenario"]["duration_s"] = DURATION_S
    traffic = json.loads((BENCH / "traffic" / f"{traffic_name}.json")
                         .read_text())
    traffic.update(SMALL[kind])
    return harness.Cell(
        name=f"{kind}.small", chips=1, config=config, traffic=traffic,
        driver=harness.load_module(BENCH / "drivers" / f"{kind}.py"),
        end_to_end=[dict(name="setup_s", unit="s")], per_layer=[],
        readers={"setup_s": lambda run: run.setup_s})


def within_limits(cell, values) -> bool:
    return all(v <= cell.traffic["limits"][k] for k, v in values.items())


@pytest.mark.parametrize("kind", sorted(CELLS))
def test_unit_agrees_with_the_reference(kind):
    cell = small_cell(kind)
    drv = cell.driver
    state = drv.setup(cell.config, cell.traffic, SEED, SEED + 1)
    records = [drv.unit(state, SEED + 2)]
    values = drv.compare(cell.config, cell.traffic, records, SEED)
    assert set(values) == set(cell.traffic["limits"])
    assert within_limits(cell, values), values


@pytest.mark.parametrize("kind", sorted(CELLS))
def test_float32_control_is_not_correct(kind):
    cell = small_cell(kind)
    drv = cell.driver
    state = drv.setup(cell.config, cell.traffic, SEED, SEED + 1)
    records = [drv.unit(state, SEED + 2)]
    values = drv.compare(cell.config, cell.traffic, records, SEED,
                         control=True)
    assert not within_limits(cell, values), values


def _unchanged(run):
    """The scan hands back its initial state: no brake, no power."""
    z = np.zeros_like
    return dataclasses.replace(
        run, n_brakes=z(run.n_brakes), peak_frac=z(run.peak_frac),
        mean_frac=z(run.mean_frac), impacts_hp=z(run.impacts_hp),
        impacts_lp=z(run.impacts_lp))


def _half(run):
    """Half of the members left out: the other half's answers stand in."""
    n = len(run.peak_frac)
    idx = np.arange(n) % max(1, n // 2)
    return dataclasses.replace(
        run, n_brakes=run.n_brakes[idx], peak_frac=run.peak_frac[idx],
        mean_frac=run.mean_frac[idx], impacts_hp=run.impacts_hp[idx],
        impacts_lp=run.impacts_lp[idx])


def _one_member(field, alter):
    """One member of each scan altered where it is made: the last lane of
    the block, a power reading or a brake count."""
    def fault(run):
        values = np.array(getattr(run, field), copy=True)
        values[-1] = alter(values[-1])
        return dataclasses.replace(run, **{field: values})
    return fault


FAULTS = {"unchanged": _unchanged, "half": _half,
          "one_power": _one_member("mean_frac", lambda v: v * (1 + 1e-5)),
          "one_brake": _one_member("n_brakes", lambda v: v + 1)}


def _break_scan(monkeypatch, fault):
    from repro.provisioning import batched

    orig = batched.run_tick_model
    monkeypatch.setattr(batched, "run_tick_model",
                        lambda *a, **k: fault(orig(*a, **k)))


def _alter_answer(monkeypatch, kind):
    """One answer altered where it is produced: a brake count in the
    ensemble, or the planner's decision."""
    if kind == "tail":
        from repro.provisioning import batched

        orig = batched._to_ensemble_result

        def altered(*a, **k):
            ens = orig(*a, **k)
            ens.brake_counts = ens.brake_counts + 1
            return ens
        monkeypatch.setattr(batched, "_to_ensemble_result", altered)
    else:
        from repro.provisioning import planner

        orig = planner.plan_capacity

        def altered(*a, **k):
            r = orig(*a, **k)
            r.safe_added_servers += 1
            return r
        monkeypatch.setattr(planner, "plan_capacity", altered)


@pytest.mark.parametrize("fault", [*FAULTS, "altered"])
@pytest.mark.parametrize("kind", sorted(CELLS))
def test_broken_timed_path_is_not_correct(kind, fault, monkeypatch):
    cell = small_cell(kind)
    if fault == "altered":
        _alter_answer(monkeypatch, kind)
    else:
        _break_scan(monkeypatch, FAULTS[fault])
    out = harness.measure(cell, SEED, 0.01, False,
                          t_start=time.perf_counter(), require_tpu=False,
                          say=lambda line: None)
    assert out["correct"] is False, out["checks"]
