"""What one grow iteration of the batched leaf-wise grower holds, as the TPU's
compiler reckons it: compile ``engine.train.audit_iteration_fn`` ahead of time
for a described v5e (no chip needed, nothing runs) and print
``memory_analysis()`` a shape.

    JAX_PLATFORMS=cpu python3 scripts/envelope_aot.py 2270296,136,12,255 400000,2000,6,63

Each argument is ``rows,features,max_depth,num_leaves`` (256 bins, binary
objective, pallas histograms).  The rows of ``COMPILED`` in
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
    from jax.sharding import SingleDeviceSharding

    from dryad_tpu.config import make_params
    from dryad_tpu.engine.train import audit_iteration_args, audit_iteration_fn

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    for arg in argv:
        rows, features, depth, leaves = (int(v) for v in arg.split(","))
        p = make_params(dict(objective="binary", growth="leafwise", num_leaves=leaves,
                             max_depth=depth, max_bins=256, min_child_weight=100,
                             hist_precision="exact", hist_backend="pallas"))
        line = {"rows": rows, "features": features, "max_depth": depth, "num_leaves": leaves}
        t0 = time.time()
        try:
            args = jax.tree_util.tree_map(
                lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
                audit_iteration_args(p, rows, features))
            memory = jax.jit(audit_iteration_fn(p, 256, False, None, "tpu", rows)).lower(
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
