"""chip_smoke.py's phases at smoke size on the host CPU, and the process
hygiene the chip run relies on: one fixed compile-cache path, no fork pool
once a chip is held, and a clear refusal of the float64 kernel on TPU.

``main()`` alone demands a TPU; the phase functions run anywhere."""

import importlib.util
from pathlib import Path

import jax
import pytest

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_planner_phase(smoke):
    out = smoke.phase_planner(n_seeds=2, duration_s=1800.0)
    assert out["n_probes"] >= 1
    assert 0 <= out["safe_added_servers"] <= 12


def test_dense_tail_phase(smoke, capsys):
    out = smoke.phase_dense_tail(n_members=12, n_oracle=4, n_rows=3,
                                 duration_s=3600.0)
    assert out["disagreeing_brake_ticks"] == 0
    assert out["oracle_brake_ticks"] > 0, "the tail must exercise brakes"
    assert out["power_rel_err_row"] <= smoke.POWER_RTOL
    assert "disagreeing 0" in capsys.readouterr().out


def test_sharded_tail_phase(smoke):
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices (tests/conftest.py forces 8 host CPUs)")
    out = smoke.phase_sharded_tail(n_devices=4, n_members=10, n_rows=2,
                                   duration_s=1800.0)
    assert out["n_devices"] == 4


def test_serve_phase(smoke):
    out = smoke.phase_serve(smoke=True, n_requests=2, prompt=16, out_tokens=4)
    assert out["prefill_decode_rel"] < smoke.PREFILL_DECODE_RTOL


def test_main_refuses_without_tpu(smoke, capsys):
    assert jax.devices()[0].platform != "tpu"
    assert smoke.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out


def test_compile_cache_prefers_env(monkeypatch, tmp_path):
    from repro.launch import compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_fixed_repo_path(monkeypatch):
    from repro.launch import compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.enable_compile_cache()
        assert path == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_fork_pool_runs_inline_once_a_chip_is_held(monkeypatch):
    from repro.provisioning import montecarlo

    def no_fork(*_a, **_k):
        raise AssertionError("forked a pool while holding a chip")

    monkeypatch.setattr(montecarlo, "_holds_accelerator", lambda: True)
    monkeypatch.setattr(montecarlo.multiprocessing, "get_context", no_fork)
    monkeypatch.setattr(montecarlo, "_run_shard", lambda sh: sh[2])
    assert montecarlo._map_shards([([], 60.0, 0), ([], 60.0, 1)], 2) == [0, 1]


def test_holds_accelerator_false_on_cpu():
    from repro.provisioning import montecarlo

    jax.devices()
    assert not montecarlo._holds_accelerator()


def test_pallas_engine_refuses_float64_on_tpu(monkeypatch):
    from repro.provisioning import batched
    from repro.provisioning.montecarlo import EnsembleSpec
    from conftest import parity_scenario

    model, _, _ = batched.lower_ensemble(
        EnsembleSpec(parity_scenario(duration_s=600.0), n_seeds=2))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(NotImplementedError, match="float64"):
        batched._run_pallas(model, keep_series=False)

