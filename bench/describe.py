"""Described-chip compile of a cell's served programs, without the chip.

    JAX_PLATFORMS=cpu python3 bench/describe.py --workload yi6b_chat

Compiles the engine's prefill at the cell's largest bucket and its decode
chunk (decode_chunk steps) for one chip of a described v5e:2x2 topology,
with the Pallas kernels compiled by Mosaic, and prints each program's
`memory_analysis()` beside the bytes of the parameters and the cache. It
runs nothing, so it gives no time, only whether the compiler takes the
programs and how many bytes they need.
"""

from __future__ import annotations

import argparse
import os
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import cells  # noqa: E402
import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    jax.config.update("jax_enable_compilation_cache", False)
    import repro.kernels.systolic_gemm.ops as ops
    ops.interpret_mode = lambda: False       # compile the kernels by Mosaic
    from repro.models.model import Model
    from repro.serve.engine import ServeEngine

    cell = cells.cell(cells.load_benchmark(), args.workload)
    conf = cells.load_json("configs", cell["config"])
    mix = cells.load_json("traffic", cell["traffic"])
    dep = conf["deployment"]
    cfg = run.arch_config(conf)
    model = Model(cfg, use_pallas=True)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
            tree)

    pshapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    engine = ServeEngine(model, pshapes, slots=dep["slots"],
                         max_len=dep["max_len"],
                         decode_chunk=dep["decode_chunk"])
    params, cache = on_chip(pshapes), on_chip(engine.cache)
    S = dep["slots"]
    b = run.buckets(mix, dep["max_len"])[-1]

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=chip)

    nbytes = lambda t: sum(a.size * a.dtype.itemsize
                           for a in jax.tree.leaves(t))
    print(f"{conf['name']}: params {nbytes(pshapes)} bytes, cache "
          f"{nbytes(engine.cache)} bytes ({S} slots x {dep['max_len']})",
          flush=True)
    progs = {
        f"prefill[{S},{b}]": lambda: engine._prefill_fn.lower(
            params, i32(S, b), cache, i32(S), i32(S)),
        f"decode chunk {dep['decode_chunk']}": lambda: engine._decode_fn.lower(
            params, cache, i32(S), i32(S), i32(S),
            jax.ShapeDtypeStruct((S,), jnp.bool_, sharding=chip),
            n=dep["decode_chunk"]),
    }
    for name, lower in progs.items():
        compiled = lower().compile()
        m = compiled.memory_analysis()
        kernels = compiled.as_text().count("tpu_custom_call")
        print(f"{name}: args {m.argument_size_in_bytes} out "
              f"{m.output_size_in_bytes} temp {m.temp_size_in_bytes} alias "
              f"{m.alias_size_in_bytes} -> args+temp+out-alias "
              f"{m.argument_size_in_bytes + m.temp_size_in_bytes + m.output_size_in_bytes - m.alias_size_in_bytes}"
              f"; tpu_custom_call {kernels}", flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
