"""The categorical subset scan's ordering step alone, one full level's shape:
each form vmapped over the children of a level, ``(F, B)`` float32 planes of
G, H and C a child, on the attached device.

    python3 scripts/cat_scan_forms.py                      # 4096 x 8 x 256, on the chip
    JAX_PLATFORMS=cpu python3 scripts/cat_scan_forms.py --children 64 --reps 3

Forms (``engine/split.py``'s ``has_cat`` block; the key is the ratio
``G / (H + 10)`` of a categorical feature, empty bins last, and a numeric
feature's own bin index):

- ``argsort``: the stable ``argsort`` of the ratio alone;
- ``gathers``: ``argsort`` -> ``where(is_cat, order, iota)`` -> three
  ``take_along_axis`` (the scan as it stood before the multi-operand sort);
- ``sort5``: one stable ``lax.sort`` of ``(key, G, H, C, iota)``, one key;
- ``sort4``: the same without ``iota``, plus a stable re-sort of one ``(B,)``
  key row a child (the winner's) for the order its membership row needs.

Each form is compiled and run once, then timed ``--reps`` times on the host
clock around ``block_until_ready``; a line a form gives the median and the
quartiles in ms.  ``sort5`` and ``sort4`` are checked bitwise against
``gathers`` on the same device first (``"bitwise": true``).  The last line is
one JSON object with every form and the device."""

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CAT_SMOOTH = 10.0


def _key(hg, hh, hc, is_cat):
    import jax.numpy as jnp

    B = hg.shape[-1]
    ratio = jnp.where(hc > 0, hg / (hh + CAT_SMOOTH), jnp.inf)
    return ratio, jnp.where(is_cat[:, None], ratio, jnp.arange(B, dtype=jnp.float32))


def forms():
    import jax
    import jax.numpy as jnp
    from jax import lax

    def argsort(hist, is_cat):
        ratio, _ = _key(hist[0], hist[1], hist[2], is_cat)
        return (jnp.argsort(ratio, axis=1, stable=True).astype(jnp.int32),)

    def gathers(hist, is_cat):
        hg, hh, hc = hist[0], hist[1], hist[2]
        iota = jnp.arange(hg.shape[-1], dtype=jnp.int32)
        ratio, _ = _key(hg, hh, hc, is_cat)
        cat_order = jnp.argsort(ratio, axis=1, stable=True).astype(jnp.int32)
        order = jnp.where(is_cat[:, None], cat_order, iota[None, :])
        return (order, jnp.take_along_axis(hg, order, axis=1),
                jnp.take_along_axis(hh, order, axis=1),
                jnp.take_along_axis(hc, order, axis=1))

    def sort5(hist, is_cat):
        hg, hh, hc = hist[0], hist[1], hist[2]
        iota = lax.broadcasted_iota(jnp.int32, hg.shape, 1)
        _, key = _key(hg, hh, hc, is_cat)
        _, hg_o, hh_o, hc_o, order = lax.sort((key, hg, hh, hc, iota), dimension=1,
                                              is_stable=True, num_keys=1)
        return order, hg_o, hh_o, hc_o

    def sort4(hist, is_cat, f):
        hg, hh, hc = hist[0], hist[1], hist[2]
        F, B = hg.shape
        _, key = _key(hg, hh, hc, is_cat)
        _, hg_o, hh_o, hc_o = lax.sort((key, hg, hh, hc), dimension=1,
                                       is_stable=True, num_keys=1)
        # the winner's key row by a masked select, then its own stable sort
        row = jnp.where((jnp.arange(F) == f)[:, None], key, -jnp.inf).max(axis=0)
        _, order_f = lax.sort((row, jnp.arange(B, dtype=jnp.int32)), is_stable=True,
                              num_keys=1)
        return order_f, hg_o, hh_o, hc_o

    return {
        "argsort": jax.jit(jax.vmap(argsort, in_axes=(0, None))),
        "gathers": jax.jit(jax.vmap(gathers, in_axes=(0, None))),
        "sort5": jax.jit(jax.vmap(sort5, in_axes=(0, None))),
        "sort4": jax.jit(jax.vmap(sort4, in_axes=(0, None, 0))),
    }


def planes(children: int, features: int, bins: int, seed: int):
    """G, H, C of a level: counts with a third of the bins empty, whole rows
    empty now and then, G and H on coarse grids so that ratios tie."""
    import jax
    import jax.numpy as jnp

    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (children, features, bins)
    hc = jnp.floor(jax.random.exponential(k1, shape) * 40.0)
    hc = jnp.where(jax.random.uniform(k2, shape) < 0.33, 0.0, hc)
    hc = hc.at[::97].set(0.0)
    hg = jnp.round(jax.random.normal(k3, shape) * 4.0) * 0.25 * jnp.sqrt(hc)
    hh = 0.25 * hc
    return jnp.stack([hg, hh, hc], axis=1).astype(jnp.float32)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--children", type=int, default=4096)
    ap.add_argument("--features", type=int, default=8)
    ap.add_argument("--bins", type=int, default=256)
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=3900000001)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    dev = jax.devices()[0]
    hist = planes(args.children, args.features, args.bins, args.seed % 2**31)
    # Expo's columns: DepTime (3) and Distance (7) numeric, the six others categorical
    is_cat = jnp.asarray([i not in (3, 7) for i in range(args.features)])
    f = jnp.arange(args.children, dtype=jnp.int32) % args.features
    fns = forms()
    call = {name: (lambda fn=fn, name=name: fn(hist, is_cat, f) if name == "sort4"
                   else fn(hist, is_cat)) for name, fn in fns.items()}

    ref = [np.asarray(a) for a in call["gathers"]()]
    got5 = [np.asarray(a) for a in call["sort5"]()]
    got4 = [np.asarray(a) for a in call["sort4"]()]
    rows = ref[0][np.arange(args.children), np.asarray(f)]
    bitwise = {
        "sort5": all(np.array_equal(a.view(np.uint32) if a.dtype == np.float32 else a,
                                    b.view(np.uint32) if b.dtype == np.float32 else b)
                     for a, b in zip(ref, got5)),
        "sort4": bool(np.array_equal(rows, got4[0])) and all(
            np.array_equal(a.view(np.uint32), b.view(np.uint32))
            for a, b in zip(ref[1:], got4[1:])),
    }

    out = {"device": {"platform": dev.platform, "kind": dev.device_kind,
                      "count": jax.device_count()},
           "shape": [args.children, args.features, args.bins], "reps": args.reps,
           "bitwise": bitwise, "ms": {}}
    for name, fn in call.items():
        jax.block_until_ready(fn())
        times = []
        for _ in range(args.reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            times.append((time.perf_counter() - t0) * 1e3)
        q = statistics.quantiles(times, n=4) if len(times) > 1 else times * 3
        out["ms"][name] = {"median": statistics.median(times), "q1": q[0], "q3": q[2]}
        print(f"{name:8s} median {out['ms'][name]['median']:.3f} ms "
              f"(q1 {q[0]:.3f}, q3 {q[2]:.3f})", flush=True)
    print(json.dumps(out))
    return 0 if all(bitwise.values()) else 1


if __name__ == "__main__":
    sys.exit(main())
