"""Test env: force JAX onto 8 virtual CPU devices (SURVEY.md §4).

The same shard_map/psum code paths that run on a real TPU host then
execute in CI with no TPU attached.  ``JAX_PLATFORMS`` must be set before
the first jax import; the config update below repeats it so a platform
pinned by the surrounding environment cannot win.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
# Compile-boundary introspection (engine/introspect.py) re-traces each new
# program once (~0.7 s for a small chunk program on CPU) — across the full
# suite's hundreds of compile boundaries that would blow the 870 s tier-1
# budget, so the suite pins it OFF and the obs/introspection tests opt
# back in per test (monkeypatch.setenv("DRYAD_PROG", "1")).  Production
# default stays ON (bench/smokes/CLI), where captures amortize over runs.
os.environ.setdefault("DRYAD_PROG", "0")
# The r18 train-completion reference-profile capture (data/profile.py) is
# likewise pinned OFF for the suite: hundreds of tiny trains would each
# pay a subsample + CPU predict for a baseline no test reads.  Drift/
# profile tests opt back in per test (monkeypatch.setenv) or call
# build_reference_profile directly; production default stays ON.
os.environ.setdefault("DRYAD_PROFILE", "0")
# The persistent compilation cache the engine places at import
# (engine/__init__.py) stays OFF for the suite and the subprocesses it
# spawns: the per-module jit-cache clearing below was tuned against
# XLA-CPU compiling every program afresh, and a tier-1 run must not
# depend on what an earlier run left on disk.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_per_module():
    """Drop compiled-executable caches between test MODULES.

    With the round-4 test additions the full suite accumulates enough XLA
    CPU executables that the compiler deterministically segfaults inside
    backend_compile_and_load at ~70% (three identical crashes at
    test_sparse_train; no half-suite subset reproduces it).  Clearing per
    module caps live executables; shared programs recompile at most once
    per module."""
    yield
    jax.clear_caches()
