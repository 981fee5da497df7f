import os
if "XLA_FLAGS" not in os.environ:  # dry-run path needs the big fake mesh
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""SPMD-ingest launcher + dry-run.

--dryrun lowers and compiles the shard_map ingest step (bucket ->
all_to_all -> minor compaction) over the 'data' axis of the production
meshes — proving the paper's distributed BatchWriter path is coherent at
pod scale, same as the model cells.

  PYTHONPATH=src python -m repro.launch.ingest --dryrun --mesh both
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..db.spmd import make_spmd_ingest_step, stacked_empty
from ..kernels.common import I32_MAX
from .mesh import make_production_mesh


def dryrun(multi_pod: bool, capacity: int = 1 << 20, batch_cap: int = 1 << 15):
    mesh = make_production_mesh(multi_pod=multi_pod)
    s = mesh.shape["data"] * (mesh.shape.get("pod", 1))
    # ingest axis = flattened (pod, data): one ingestor per data shard
    flat = jax.make_mesh((s,), ("data",), devices=jax.devices()[:s],
                         axis_types=(jax.sharding.AxisType.Auto,))
    # unwrap the host-side metrics wrapper: AOT lowering wants the raw
    # jitted step (tracing through the wrapper would count trace-time)
    step = make_spmd_ingest_step(flat, "data", s, id_capacity=1 << 22)
    step = getattr(step, "__wrapped__", step)
    tablets = stacked_empty(s, capacity)
    sh2 = NamedSharding(flat, P("data", None))
    sh1 = NamedSharding(flat, P("data"))
    t_sds = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tablets)
    b_sds = jax.ShapeDtypeStruct((s, batch_cap), jnp.int32)
    v_sds = jax.ShapeDtypeStruct((s, batch_cap), jnp.float32)
    shardings = (jax.tree.map(
        lambda x: sh2 if len(x.shape) > 1 else sh1, t_sds), sh2, sh2, sh2)
    with flat:
        lowered = jax.jit(step, in_shardings=shardings,
                          donate_argnums=(0,)).lower(t_sds, b_sds, b_sds, v_sds)
        compiled = lowered.compile()
    ma = compiled.memory_analysis()
    import re
    colls = {}
    for kind in ("all-to-all", "all-reduce", "all-gather", "collective-permute"):
        n = len(re.findall(kind + r"[\.\(]", compiled.as_text()))
        if n:
            colls[kind] = n
    tag = "2x16x16(flat 512)" if multi_pod else "16x16(flat 256)"
    print(f"[ingest dry-run × {tag}] ingestors={s} "
          f"args={ma.argument_size_in_bytes/1e9:.2f}GB "
          f"temps={ma.temp_size_in_bytes/1e9:.2f}GB colls={colls}")
    return {"mesh": tag, "ingestors": s, "colls": colls,
            "arg_bytes": ma.argument_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dryrun", action="store_true")
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="both")
    args = ap.parse_args()
    if args.dryrun:
        recs = []
        if args.mesh in ("single", "both"):
            recs.append(dryrun(False))
        if args.mesh in ("multi", "both"):
            recs.append(dryrun(True))
        return recs
    raise SystemExit("only --dryrun is supported in this container")


if __name__ == "__main__":
    main()
