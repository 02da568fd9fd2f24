"""What one grow iteration of the batched leaf-wise grower holds, as the TPU's
compiler reckons it: compile ``engine.train.audit_iteration_fn`` ahead of time
for a described v5e (no chip needed, nothing runs) and print
``memory_analysis()`` a shape.

    JAX_PLATFORMS=cpu python3 scripts/envelope_aot.py 2270296,136,12,255 400000,2000,6,63

Each argument is ``rows,features,max_depth,num_leaves[,shards]`` (256 bins,
binary objective, pallas histograms; ``shards`` 2 or 4 compiles the sharded
iteration for a mesh of that many of the described chips, rows the global
count, and the sizes printed are one device's).  The rows of ``COMPILED`` in
``tests/test_rank_plan.py`` are this script's lines; the constants of
``config.leafwise_fast_supported`` envelop them.  A shape the chip cannot hold
prints the compiler's RESOURCE_EXHAUSTED error instead of sizes.  A 10M-row
shape compiles for four to six minutes."""

import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv) -> int:
    import jax
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, SingleDeviceSharding
    from jax.sharding import PartitionSpec as P

    from dryad_tpu.config import make_params
    from dryad_tpu.engine.distributed import AXIS, make_mesh, padded_rows
    from dryad_tpu.engine.train import audit_iteration_args, audit_iteration_fn

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    for arg in argv:
        rows, features, depth, leaves, shards = (tuple(int(v) for v in arg.split(",")) + (1,))[:5]
        p = make_params(dict(objective="binary", growth="leafwise", num_leaves=leaves,
                             max_depth=depth, max_bins=256, min_child_weight=100,
                             hist_precision="exact", hist_backend="pallas"))
        line = {"rows": rows, "features": features, "max_depth": depth, "num_leaves": leaves,
                "shards": shards}
        t0 = time.time()
        try:
            mesh = make_mesh(topo.devices[:shards]) if shards > 1 else None
            padded = padded_rows(rows, shards)

            def placed(s):
                # row-indexed arguments are sharded over the mesh as
                # train_device shards them; the tree tables and masks replicated
                if mesh is None:
                    return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)
                by_rows = s.ndim and s.shape[0] == padded
                spec = P(AXIS, *(None,) * (s.ndim - 1)) if by_rows else P()
                return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=NamedSharding(mesh, spec))

            args = jax.tree_util.tree_map(placed, audit_iteration_args(p, padded, features))
            memory = jax.jit(audit_iteration_fn(p, 256, False, mesh, "tpu", rows,
                                                pad=padded - rows)).lower(
                *args).compile().memory_analysis()
            line.update(temp_size_in_bytes=int(memory.temp_size_in_bytes),
                        argument_size_in_bytes=int(memory.argument_size_in_bytes))
        except Exception as e:  # noqa: BLE001 - the compiler's refusal is the reading
            line["error"] = repr(e)[:400]
        line["compile_s"] = round(time.time() - t0, 1)
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
