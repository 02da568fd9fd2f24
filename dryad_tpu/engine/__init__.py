"""dryad_tpu.engine — the TPU-native training/predict engine.

The reference's three CUDA kernels (per-feature histogram builder, split-gain
scan, row-partition/apply — BASELINE.json:5) map here to XLA/Pallas programs
designed for the MXU + VMEM memory hierarchy rather than for CUDA's
atomic-scatter model:

* histogram.py — scatter-add has no TPU atomics, so the histogram is a
  masked one-hot matmul (MXU) or a Pallas row-tiled VMEM accumulation.
* split.py — split-gain scan as a vectorized cumsum + masked argmax.
* grower.py — the leaf-wise grower as a fixed-trip-count ``lax.fori_loop``
  with slot masking (XLA needs static shapes; the reference's dynamic
  host-side loop becomes compiled control flow).
* train.py / predict.py — the ``dryad.train`` / ``dryad.predict`` device
  backends; the histogram allreduce rides ``jax.lax.psum`` over ICI/DCN in
  place of the reference's NCCL (SURVEY.md §2 #13-14).
"""

import os

import jax

from dryad_tpu.obs import spans as _spans

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def place_compile_cache() -> str:
    """Give jax's persistent compilation cache a directory; return it.

    The wide chunk programs compile for minutes, and every train, predict,
    serve, probe and profile process imports this package before its first
    device program, so this is the one place the cache is placed.  Where
    ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and nothing
    is set in code.  Otherwise the cache goes to ``<checkout>/.jax_cache``,
    derived from this package's own location: the directory is part of
    what makes a later process find the entries, so it is never built from
    a temporary name, a pid or the time.
    """
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


place_compile_cache()

# every obs span (train.fetch.*, train.chunk_dispatch, the serve spans)
# is also a TraceAnnotation: under ``dryad.train(profile_dir=...)`` it
# shows by its path on the profile's host plane, on the device
# operations' time axis.  obs stays jax-free; this package is where jax
# and the spans meet.
_spans.set_annotator(jax.profiler.TraceAnnotation)
