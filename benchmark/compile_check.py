"""Scratch: compile each configuration's top rung for a described v5e
(no chip needed) and print what the compiler says it holds.

    JAX_PLATFORMS=cpu python3 benchmark/compile_check.py [config ...] [--chips 4]

One program at a time: the compiler counts the arguments (the weights
among them), the output and its temporaries, not what else the process
keeps on the device. A compile that passes is not a chip run.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("configs", nargs="*", default=sorted(
        f[:-5] for f in os.listdir(os.path.join(HERE, "configs"))
        if f.endswith(".json")))
    ap.add_argument("--chips", type=int, default=1)
    ap.add_argument("--rung", type=int, default=None,
                    help="rows of the rung (default: the top one)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from jax.sharding import SingleDeviceSharding

    from odigos_tpu.models.transformer import TraceTransformer
    from odigos_tpu.training.checkpoint import make_model_config

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    n = args.chips
    for name in args.configs:
        with open(os.path.join(HERE, "configs", name + ".json")) as f:
            stanza = json.load(f)["tpuanomaly"]
        model = TraceTransformer(make_model_config(
            stanza.get("model", "transformer"), stanza["model_config"]))
        L = model.cfg.max_len
        rows = args.rung or (int(stanza["trace_bucket"]) * n
                             << (int(stanza["bucket_ladder"]) - 1))
        variables = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        if n == 1:
            one = SingleDeviceSharding(topo.devices[0])
            rep = row = row3 = one
        else:
            mesh = Mesh(topo.devices[:n], ("data",))
            rep = NamedSharding(mesh, P())
            row = NamedSharding(mesh, P("data", None))
            row3 = NamedSharding(mesh, P("data", None, None))
        v = jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=rep), variables)
        args_ = (v,
                 jax.ShapeDtypeStruct((rows, L, 5), jnp.int32, sharding=row3),
                 jax.ShapeDtypeStruct((rows, L, 3), jnp.float32,
                                      sharding=row3),
                 jax.ShapeDtypeStruct((rows, L), jnp.int32, sharding=row),
                 jax.ShapeDtypeStruct((rows, L), jnp.int32, sharding=row))
        t0 = time.perf_counter()
        compiled = jax.jit(model._score_packed_impl,
                           out_shardings=row).lower(*args_).compile()
        m = compiled.memory_analysis()
        print(json.dumps({
            "config": name, "chips": n, "rows": rows, "row_length": L,
            "compile_s": round(time.perf_counter() - t0, 1),
            "argument_bytes": m.argument_size_in_bytes,
            "output_bytes": m.output_size_in_bytes,
            "temp_bytes": m.temp_size_in_bytes,
            "total_bytes_per_device": m.argument_size_in_bytes
            + m.output_size_in_bytes + m.temp_size_in_bytes}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
