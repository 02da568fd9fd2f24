"""r5 Epsilon-axis measurements (VERDICT r4 #4).

Three questions, CLAUDE.md methodology (K dependent reps in ONE jit,
perturbation reaching every stage, device-resident inputs):

1. partition: masked reduce vs per-row gather at the Epsilon shape
   (400k x 2000 u8) — backs the partition_prefers_reduce gate.
2. natural-order pass at the 800 MB Epsilon matrix: the nat gate has
   excluded this shape since r3 WITHOUT a measurement; record
   admit/reject evidence (kernel wall + any buffer-pressure stall).
3. warm per-iteration marginal with the r5 settings, for STATUS.

Usage: PYTHONPATH=. python scripts/exp_r5_eps.py
"""

import time

import jax
import jax.numpy as jnp
import numpy as np

N, F, B = 400_000, 2000, 256


def loop_time(fn, *arrays, K=8):
    def prog(s0, *arrays):
        return jax.lax.fori_loop(0, K, lambda i, s: fn(s, *arrays), s0)

    f = jax.jit(prog)
    # each timed call ends in a host fetch of the carried scalar, which
    # waits for the device
    float(f(jnp.float32(0), *arrays))                  # compile + warm
    t0 = time.perf_counter()
    float(f(jnp.float32(1), *arrays))
    return (time.perf_counter() - t0) / K * 1000


def main():
    rng = np.random.default_rng(0)
    print(f"device={jax.devices()[0]}  shape {N}x{F}x{B}", flush=True)
    Xb = jnp.asarray(rng.integers(1, B, size=(N, F), dtype=np.uint8))
    g = jnp.asarray(rng.normal(size=N).astype(np.float32))
    h = jnp.asarray(rng.uniform(0.1, 1, N).astype(np.float32))
    rf_np = rng.integers(0, F, N).astype(np.int32)
    rf = jnp.asarray(rf_np)

    # ---- 1. partition: reduce vs gather ------------------------------------
    def part_reduce(s, Xb, rf):
        # integer-meaningful perturbation: s advances by whole units per
        # rep (the earlier s + eps*sum draft rounded to a CONSTANT under
        # the int cast and XLA hoisted the whole stage — the CLAUDE.md
        # dead-input trap)
        rfp = (rf + s.astype(jnp.int32)) % F
        iota_f = jnp.arange(F, dtype=jnp.int32)
        bins = jnp.max(jnp.where(rfp[:, None] == iota_f[None, :], Xb,
                                 jnp.zeros((), Xb.dtype)),
                       axis=1).astype(jnp.int32)
        return s + 1.0 + jnp.sum(bins).astype(jnp.float32) * 1e-20

    def part_gather(s, Xb, rf):
        rfp = (rf + s.astype(jnp.int32)) % F
        bins = jnp.take_along_axis(Xb, rfp[:, None], axis=1)[:, 0]
        return s + 1.0 + jnp.sum(bins.astype(jnp.int32)).astype(jnp.float32) * 1e-20

    t_red = loop_time(part_reduce, Xb, rf)
    t_gat = loop_time(part_gather, Xb, rf)
    print(f"partition  masked-reduce {t_red:7.1f} ms   "
          f"per-row gather {t_gat:7.1f} ms", flush=True)

    # ---- 2. natural-order pass at the Epsilon shape ------------------------
    from dryad_tpu.engine import pallas_hist

    P = 16
    sel_np = rng.integers(0, P, N).astype(np.int32)
    sel = jnp.asarray(sel_np)
    t0 = time.perf_counter()
    nat = pallas_hist.natural_tiles(Xb, B)
    float(jnp.sum(nat[0, 0, 0].astype(jnp.float32)))   # REAL fetch
    t_tiles = time.perf_counter() - t0
    print(f"nat tiles build: {t_tiles:.1f} s "
          f"(buffer {nat.size * nat.dtype.itemsize / 1e9:.2f} GB)",
          flush=True)

    def nat_step(s, nat, g, h, sel):
        selp = (sel + s.astype(jnp.int32)) % P          # perturb the SLOT
        out = pallas_hist.build_hist_small(nat, g, h, selp, P, B, F)
        return s + 1.0 + out[0, 0, 0, 0] * 1e-20

    t_nat = loop_time(nat_step, nat, g, h, sel, K=3)

    # plan-path comparison at the same selection
    from dryad_tpu.engine.histogram import build_hist_segmented

    def plan_step(s, Xb, g, h, sel):
        selp = (sel + s.astype(jnp.int32)) % P
        out = build_hist_segmented(Xb, g, h, selp, P, B, backend="pallas")
        return s + 1.0 + out[0, 0, 0, 0] * 1e-20

    t_plan = loop_time(plan_step, Xb, g, h, sel, K=3)
    print(f"16-slot level pass  nat {t_nat:7.0f} ms   plan(sort+gather+"
          f"kernel) {t_plan:7.0f} ms", flush=True)

    # ---- 3. warm marginal with r5 settings ---------------------------------
    import dryad_tpu as dryad

    y_np = (rng.random(N) < 0.5).astype(np.float32)
    X_np = np.asarray(Xb, np.float32) + rng.random((N, F)).astype(np.float32)
    ds = dryad.Dataset(X_np, y_np)
    for trees in (2, 6):
        t0 = time.perf_counter()
        dryad.train(dict(objective="regression", num_trees=trees,
                         num_leaves=255, max_depth=8), ds, backend="tpu")
        print(f"{trees}-tree wall {time.perf_counter() - t0:6.1f} s",
              flush=True)


if __name__ == "__main__":
    main()
