"""Natural-order multi-slot histogram experiment (levels with <= 16 leaves).

Shallow depthwise levels pay the full tile plan (sort over N) + row gather
for a handful of candidates.  But 16 slots x 8 weight rows = the 128-row
MXU tile exactly: packing per-slot limb rows (slot s rows 8s..8s+6, row
8s+7 carries the slot id itself) lets ONE natural-order pass compute all
slots' histograms — no sort, no gather, and the (n_fb, n_tiles, Fc, T)
bin tiles are a pure function of Xb (buildable once per tree).

Measures the kernel vs the segmented path at 10M rows, P=8, and checks
values against the XLA oracle.
"""

import functools
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dryad_tpu.engine.pallas_hist import (
    _TILE_ROWS, _feature_chunk, _pow2_bins, _split3, _tiles_from_rows,
)

T = _TILE_ROWS
_NSLOTS = 16
_ROWS_PER_SLOT = 8


def _nat_kernel(x_ref, w_ref, o_ref, *, padded_bins):
    i = pl.program_id(1)
    x = x_ref[0, 0].astype(jnp.int32)              # (Fc, T)
    Fc, Tl = x.shape
    Bp = padded_bins
    shift = Fc.bit_length() - 1
    x_rep = pltpu.repeat(x, Bp, axis=0)
    iota_b = jax.lax.broadcasted_iota(jnp.int32, (Fc * Bp, Tl), 0) >> shift
    onehot = (x_rep == iota_b).astype(jnp.bfloat16)

    limbs = w_ref[0]                               # (8, T): 7 limbs + sel row
    sel = limbs[7:8, :].astype(jnp.int32)          # (1, T) slot per row
    w = pltpu.repeat(limbs, _NSLOTS, axis=0)       # (128, T), row r = limbs[r%8]
    row_iota = jax.lax.broadcasted_iota(jnp.int32, (_NSLOTS * 8, Tl), 0)
    slot_of_row = row_iota >> 3
    keep = (slot_of_row == sel) & ((row_iota & 7) != 7)
    w = jnp.where(keep, w, jnp.bfloat16(0))
    part = jax.lax.dot_general(
        w, onehot, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)        # (128, Fc*Bp)

    @pl.when(i == 0)
    def _():
        o_ref[0] = part

    @pl.when(i != 0)
    def _():
        o_ref[0] = o_ref[0] + part


@functools.partial(jax.jit, static_argnames=("total_bins", "num_features"))
def hist_nat16(Xt, g, h, sel, *, total_bins, num_features):
    """(16, 3, F, B) from natural-order tiles; sel (N,) in [0, 16]=drop."""
    B = int(total_bins)
    F = int(num_features)
    Bp = _pow2_bins(B)
    n_fb, n_tiles, Fc, Tl = Xt.shape
    N = g.shape[0]
    pad = n_tiles * Tl - N
    gp = jnp.pad(g.astype(jnp.float32), (0, pad))
    hp = jnp.pad(h.astype(jnp.float32), (0, pad))
    sp = jnp.pad(sel.astype(jnp.int32), (0, pad), constant_values=31)
    valid = (sp < _NSLOTS).astype(jnp.float32)
    gv = (gp * valid).reshape(n_tiles, Tl)
    hv = (hp * valid).reshape(n_tiles, Tl)
    cnt = valid.astype(jnp.bfloat16).reshape(n_tiles, Tl)
    selr = jnp.minimum(sp, 31).astype(jnp.bfloat16).reshape(n_tiles, Tl)
    W = jnp.stack([*_split3(gv), *_split3(hv), cnt, selr], axis=-2)  # (nt,8,T)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        grid=(n_fb, n_tiles),
        in_specs=[
            pl.BlockSpec((1, 1, Fc, Tl), lambda j, i: (j, i, 0, 0)),
            pl.BlockSpec((1, 8, Tl), lambda j, i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, _NSLOTS * 8, Fc * Bp), lambda j, i: (j, 0, 0)),
    )
    out = pl.pallas_call(
        functools.partial(_nat_kernel, padded_bins=Bp),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_fb, _NSLOTS * 8, Fc * Bp),
                                       jnp.float32),
        interpret=jax.default_backend() == "cpu",
    )(Xt, W)
    # untangle: (n_fb, 128, Fc*Bp) -> (16, 8, F, B)
    out = (out.reshape(n_fb, _NSLOTS, 8, Bp, Fc)
              .transpose(1, 2, 0, 4, 3)
              .reshape(_NSLOTS, 8, n_fb * Fc, Bp))[:, :, :F, :B]
    hg = out[:, 0] + out[:, 1] + out[:, 2]
    hh = out[:, 3] + out[:, 4] + out[:, 5]
    hc = out[:, 6]
    return jnp.stack([hg, hh, hc], axis=1)         # (16, 3, F, B)


def main():
    N = int(sys.argv[1]) if len(sys.argv) > 1 else 10_000_000
    P = 8
    K = int(sys.argv[2]) if len(sys.argv) > 2 else 5
    F, B = 28, 256
    rng = np.random.default_rng(0)
    plat = jax.devices()[0].platform
    print(f"rows={N} P={P} device={jax.devices()[0]}")

    Xb = jnp.asarray(rng.integers(1, B, size=(N, F), dtype=np.uint8))
    g = jnp.asarray(rng.normal(size=N).astype(np.float32))
    h = jnp.asarray(rng.uniform(0.1, 1.0, size=N).astype(np.float32))
    sel_np = rng.integers(0, 2 * P, size=N).astype(np.int32)
    sel_np = np.where(sel_np < P, sel_np, 31)
    sel = jnp.asarray(sel_np)

    pad = (-N) % T
    n_tiles = (N + pad) // T
    Xt = jax.block_until_ready(jax.jit(
        lambda X: _tiles_from_rows(jnp.pad(X, ((0, pad), (0, 0))),
                                   n_tiles, T, B))(Xb))

    # correctness vs XLA segmented oracle
    from dryad_tpu.engine.histogram import build_hist_segmented

    want = np.asarray(jax.jit(
        lambda X, gg, hh, ss: build_hist_segmented(
            X, gg, hh, jnp.where(ss < 16, ss, 16), 16, B, backend="xla"))(
        Xb, g, h, sel))
    got = np.asarray(hist_nat16(Xt, g, h, sel, total_bins=B,
                                num_features=F))
    np.testing.assert_array_equal(got[:, 2], want[:, 2])   # counts exact
    np.testing.assert_allclose(got, want, atol=2e-2, rtol=2e-5)
    print("nat16 matches XLA oracle (counts exact)")

    def loop_time(tag, step, *arrays):
        f = jax.jit(lambda s0, *a: jax.lax.fori_loop(
            0, K, lambda i, s: step(s, *a), s0))
        _ = float(f(jnp.float32(0.0), *arrays))
        t0 = time.perf_counter()
        _ = float(f(jnp.float32(0.0), *arrays))
        print(f"{tag:42s} {(time.perf_counter()-t0)/K*1e3:9.1f} ms")

    j32 = lambda s: (s * 1e-30).astype(jnp.int32)
    loop_time("nat16 (no sort, no gather)", lambda s, xt, gg, hh, ss:
              hist_nat16(xt, gg, hh, ss + j32(s), total_bins=B,
                         num_features=F)[0, 0, 0, 0] * 1e-30, Xt, g, h, sel)
    loop_time("segmented pallas P=8 (plan+gather)", lambda s, X, gg, hh, ss:
              build_hist_segmented(
                  X, gg, hh, jnp.minimum(ss + j32(s), 8), 8, B,
                  backend="pallas", rows_bound=N // 2 + 1,
                  platform=plat)[0, 0, 0, 0] * 1e-30, Xb, g, h, sel)


if __name__ == "__main__":
    main()
