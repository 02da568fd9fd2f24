"""The best-first reference with its row blocks spread over several chips.

``gbdt_bestfirst.BestFirst`` and ``gbdt.py``'s kernels, imported unedited: the
same plain float32 reference over **all** the rows as one table.  It knows
nothing of the job's shards (how the program splits its rows, what it reduces
and when): it only takes its own histogram passes, nine tenths of its time,
faster.  A pass sums blocks of 2048 rows; here the blocks are dealt to the
devices in contiguous runs, each device runs ``gbdt._level_hist`` over its
run, and the devices' float32 sums are added on the host in float64 (on one
device: ``BestFirst`` itself).  Everything else (gradients, routing, leaf
sums, scores, the valid metric) stays on the first device as ``gbdt.py`` has
it.  It imports nothing of the program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from benchmark.reference.gbdt import BINS, _level_hist, _merge, _pad_slots
from benchmark.reference.gbdt_bestfirst import BestFirst, Rows  # noqa: F401  (Rows: the callers')

AXIS = "blocks"


@functools.lru_cache(maxsize=None)
def _spread_hist(mesh: Mesh, slots: int, group: int, bf16: bool):
    """``_level_hist`` over each device's run of blocks: [devices, channels *
    slots, group * BINS], one float32 partial sum a device."""
    rows, rep = PartitionSpec(AXIS), PartitionSpec()

    def one(q, slot, g, h, f0):
        return _level_hist(q, slot, g, h, f0, slots=slots, group=group, bf16=bf16)[None]

    # check_vma off: ``_level_hist`` starts its sums from a plain zero, which
    # the checker wants marked as differing from device to device
    return jax.jit(jax.shard_map(one, mesh=mesh, in_specs=(rows, rows, rows, rows, rep),
                                 out_specs=rows, check_vma=False))


class BestFirstSpread(BestFirst):
    """``BestFirst`` whose ``level_hist`` runs on every device of ``devices``."""

    def __init__(self, params: dict, train: Rows, valid: Rows | None, depth_cap: int,
                 devices=None):
        super().__init__(params, train, valid, depth_cap)
        devices = list(devices or [])
        self.mesh = Mesh(np.asarray(devices), (AXIS,)) if len(devices) > 1 else None
        if self.mesh is not None:
            n = len(devices)
            self.pad = -train.outer % n             # whole blocks of no row
            self.by_blocks = NamedSharding(self.mesh, PartitionSpec(AXIS))
            self.q_spread = self.spread(train.q, 0)

    def spread(self, x, fill):
        """``x`` [outer, ...] padded to whole runs and dealt to the devices."""
        if self.pad:
            x = jnp.pad(x, ((0, self.pad),) + ((0, 0),) * (x.ndim - 1), constant_values=fill)
        return jax.device_put(x, self.by_blocks)

    def level_hist(self, slot, g, h, n: int, bf16: bool) -> np.ndarray:
        """float64 [3, n, F, BINS]: G, H and count of every node of a level."""
        if self.mesh is None:
            return super().level_hist(slot, g, h, n, bf16)
        channels = 3 if bf16 else 7
        slots = _pad_slots(n, channels)
        F = self.train.features
        slot, g, h = self.spread(slot, -1), self.spread(g, 0), self.spread(h, 0)
        fn = _spread_hist(self.mesh, slots, self.group, bf16)
        parts = []
        for f0 in range(0, F, self.group):
            out = np.asarray(fn(self.q_spread, slot, g, h, jnp.int32(f0)), np.float64).sum(axis=0)
            out = out.reshape(channels, slots, self.group, BINS)[:, :n]
            parts.append(_merge(out, bf16))
        return np.concatenate(parts, axis=2)
