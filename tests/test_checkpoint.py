"""Checkpoint/restore + crash-restart + elastic resharding."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import checkpointer
from repro.configs import smoke_config
from repro.data.pipeline import DataConfig, SyntheticTokenPipeline
from repro.launch.inputs import make_rules
from repro.launch.mesh import make_local_mesh
from repro.launch.steps import build_train_step
from repro.models import model as model_mod
from repro.models.config import ShapeConfig
from repro.models.param import init_params
from repro.optim import make_optimizer
from repro.runtime.fault_tolerance import (
    BrakeSentinel,
    FaultInjector,
    StragglerMonitor,
    TrainSupervisor,
)


def _tiny_state():
    return {"params": {"w": jnp.arange(6.0).reshape(2, 3), "b": jnp.ones((3,))},
            "opt": {"mu": {"w": jnp.zeros((2, 3)), "b": jnp.zeros((3,))},
                    "count": jnp.asarray(4, jnp.int32)}}


def test_roundtrip(tmp_path):
    st = _tiny_state()
    checkpointer.save(str(tmp_path), 7, st)
    step, st2 = checkpointer.restore_latest(str(tmp_path), st)
    assert step == 7
    for a, b in zip(jax.tree.leaves(st), jax.tree.leaves(st2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_gc_keeps_latest(tmp_path):
    st = _tiny_state()
    for s in range(6):
        checkpointer.save(str(tmp_path), s, st, keep=3)
    assert checkpointer.list_steps(str(tmp_path)) == [3, 4, 5]


def test_torn_write_fallback(tmp_path):
    st = _tiny_state()
    checkpointer.save(str(tmp_path), 1, st)
    checkpointer.save(str(tmp_path), 2, st)
    # corrupt the newest checkpoint (simulated kill mid-write + bad rename)
    with open(os.path.join(tmp_path, "step_3.npz"), "wb") as f:
        f.write(b"not a zip")
    step, _ = checkpointer.restore_latest(str(tmp_path), st)
    assert step == 2


def test_supervisor_crash_restart_replays_exactly(tmp_path, mesh1):
    """Injected faults mid-run: the supervisor restores and the final state
    equals the fault-free run (step-addressable pipeline => exact replay)."""
    cfg = smoke_config("llama3.2-1b")
    shape = ShapeConfig("t", 32, 2, "train")
    rules = make_rules(cfg, shape, mesh1)
    opt = make_optimizer(cfg.optimizer)
    pspecs = model_mod.model_specs(cfg, 1)
    with jax.set_mesh(mesh1):
        params = init_params(pspecs, jax.random.key(0))
        opt_state = init_params(opt.init_specs(pspecs), jax.random.key(1))
    state0 = {"params": params, "opt": opt_state}
    pipeline = SyntheticTokenPipeline(cfg, DataConfig(2, 32))
    base_step = jax.jit(build_train_step(cfg, mesh1, rules, opt))

    def clean_step(state, batch):
        with jax.set_mesh(mesh1):
            s, m = base_step(state, {k: jnp.asarray(v) for k, v in batch.items()})
        return s, m

    sup_clean = TrainSupervisor(clean_step, pipeline, str(tmp_path / "clean"),
                                ckpt_interval=4)
    final_clean, _ = sup_clean.run(jax.tree.map(lambda x: x, state0), 12)

    inj = FaultInjector(fail_at=[6, 9])
    calls = {"n": 0}

    def faulty_step(state, batch):
        step_idx = len(sup_faulty.history)
        inj.maybe_fail(step_idx)
        return clean_step(state, batch)

    sup_faulty = TrainSupervisor(faulty_step, pipeline, str(tmp_path / "faulty"),
                                 ckpt_interval=4)
    final_faulty, _ = sup_faulty.run(jax.tree.map(lambda x: x, state0), 12)
    assert sup_faulty.n_restarts == 2
    for a, b in zip(jax.tree.leaves(final_clean["params"]),
                    jax.tree.leaves(final_faulty["params"])):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=1e-6)


def test_fault_injector_reset_reinjects():
    inj = FaultInjector(fail_at=[2])
    with pytest.raises(RuntimeError):
        inj.maybe_fail(2)
    inj.maybe_fail(2)  # already seen: silent
    inj.reset()
    with pytest.raises(RuntimeError):
        inj.maybe_fail(2)  # same timeline fires again after reset


class _CountingPipeline:
    """Step-addressable stub: the supervisor only calls batch_at(step)."""

    def batch_at(self, step):
        return {"step": step}


def test_supervisor_power_event_checkpoints_and_drains(tmp_path):
    """A sustained-brake power event checkpoints the run and drains at the
    next step boundary — the straggler mitigation, triggered by the power
    plane. Other events are recorded + forwarded but do not drain."""
    seen = []

    def step_fn(state, batch):
        n = int(state["x"])
        if n == 3:
            sup.power_event("sustained-brake")
        return {"x": state["x"] + 1.0}, {"loss": 0.0}

    sup = TrainSupervisor(step_fn, _CountingPipeline(), str(tmp_path),
                          ckpt_interval=100, on_power_event=seen.append)
    sup.power_event("brake-cleared")  # informational: no drain
    state, step = sup.run({"x": np.asarray(0.0)}, 10)
    assert step == 4, "drain must happen at the boundary after the event"
    assert float(state["x"]) == 4.0
    assert sup.power_events == ["brake-cleared", "sustained-brake"]
    assert seen == sup.power_events, "on_power_event hook sees every event"
    assert checkpointer.list_steps(str(tmp_path))[-1] == 4
    # the drain is one-shot: resuming completes the run
    state, step = sup.run(state, 10, start_step=step)
    assert step == 10 and float(state["x"]) == 10.0


def test_brake_sentinel_fires_on_sustained_runs_only():
    s = BrakeSentinel(sustain_ticks=3)
    pattern = [False, True, True, False, True, True, True, True]
    fired = [s.observe(float(i), b) for i, b in enumerate(pattern)]
    # one event, exactly at the 3rd consecutive braked tick; a longer run
    # does not re-fire
    assert fired == [None, None, None, None, None, None,
                     "sustained-brake", None]
    assert s.events == [6.0]


def test_brake_sentinel_scan_real_telemetry_drains_supervisor(tmp_path):
    """End to end: a row simulation braked by an undersized budget produces
    a braked_series whose sustained run the sentinel converts into the
    supervisor power event that checkpoints + drains the training loop."""
    from repro.experiments import get_scenario, run_experiment

    o = run_experiment(get_scenario("fig14-plus30").with_(
        duration_s=900.0, budget=14_000.0, compare_to_reference=False))
    assert o.result.braked_series is not None

    def step_fn(state, batch):
        return {"x": state["x"] + 1.0}, {"loss": 0.0}

    sup = TrainSupervisor(step_fn, _CountingPipeline(), str(tmp_path))
    fired = BrakeSentinel(sustain_ticks=3).scan(o.result, supervisor=sup)
    assert fired, "an undersized budget must yield a sustained brake"
    assert "sustained-brake" in sup.power_events
    state, step = sup.run({"x": np.asarray(0.0)}, 5)
    assert step == 0, "pending drain fires before the first step"
    assert checkpointer.list_steps(str(tmp_path)) == [0]


def test_straggler_monitor_flags_outliers():
    mon = StragglerMonitor(threshold=2.0)
    for i in range(10):
        mon.observe(i, 0.1)
    assert mon.observe(10, 0.5)
    assert mon.flagged_steps == [10]
    assert not mon.observe(11, 0.12)


def test_elastic_reshard_roundtrip(mesh1):
    """Host state re-placed onto a new mesh keeps values and new shardings."""
    from repro.runtime.fault_tolerance import elastic_reshard
    from jax.sharding import NamedSharding, PartitionSpec as P

    host_state = {"w": np.arange(8.0, dtype=np.float32).reshape(2, 4)}

    def template_fn(mesh):
        return {"w": jax.ShapeDtypeStruct((2, 4), jnp.float32,
                                          sharding=NamedSharding(mesh, P(None, None)))}

    out = elastic_reshard(template_fn, host_state, mesh1)
    np.testing.assert_array_equal(np.asarray(out["w"]), host_state["w"])
    assert out["w"].sharding.mesh.shape == mesh1.shape
