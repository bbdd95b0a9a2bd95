"""Compile each cell's scan program at its cell shape for a described TPU
v5e, on a host with no chip, and print its ``memory_analysis()``.

    JAX_PLATFORMS=cpu python3 chipbench/tools/aot_compile.py [<cell> ...]

Nothing runs; the TPU compiler refuses what the chip would refuse.
"""

import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(cells) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from chipbench import harness
    from repro.experiments.scenario import Scenario
    from repro.provisioning import batched
    from repro.provisioning.montecarlo import EnsembleSpec

    jax.config.update("jax_enable_compilation_cache", False)
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    for name in cells or [w["name"] for w in manifest["workloads"]]:
        cell = harness.resolve_cell(manifest, name)
        sc = Scenario.from_dict(cell.config["scenario"])
        model, _, _ = batched.lower_ensemble(
            EnsembleSpec(sc, n_seeds=int(cell.traffic["n_seeds"]), seed0=1),
            budget_w=cell.config["budget_w"])
        keep_series, keep_fire, _ = batched._auto_flags(model, None, None,
                                                        None)
        cfg, _, idx = batched._plan_bucket(
            [model], keep_series=keep_series, keep_fire=keep_fire,
            member_chunk=None, mesh=None)
        operands = batched._bucket_operands([model], idx)
        shapes = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=chip), operands)
        t0 = time.perf_counter()
        with jax.enable_x64(True):
            compiled = batched._jax_runner(cfg, None).lower(*shapes).compile()
        m = compiled.memory_analysis()
        print(json.dumps(dict(
            cell=name, chunk=cfg.chunk, keep_series=keep_series,
            keep_fire=keep_fire, compile_s=time.perf_counter() - t0,
            argument_bytes=m.argument_size_in_bytes,
            output_bytes=m.output_size_in_bytes,
            temp_bytes=m.temp_size_in_bytes,
            alias_bytes=m.alias_size_in_bytes,
            f64_shapes=compiled.as_text().count("f64["))), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
