"""dryadlint (dryad_tpu/analysis layer 1): every rule must (a) pass on the
shipped tree, (b) FAIL on a seeded violation — the mutation check: a rule
that cannot catch its own violation class is a green light painted on a
wall — and (c) honor the waiver syntax, reasons mandatory.

Mutation fixtures patch REAL repo files in memory (SourceTree overrides),
so the checks exercise the exact file set CI lints, not toy snippets.
"""

from __future__ import annotations

import textwrap

import pytest

from dryad_tpu.analysis.lint import SourceTree, parse_waivers, run_lint
from dryad_tpu.analysis.lint import LintReport

ROOT = __file__.rsplit("/tests/", 1)[0]


def _violations(rule, overrides=None):
    report = run_lint(ROOT, rule_names=[rule], overrides=overrides)
    return report


def _rule_hits(report, rule):
    return [v for v in report.violations if v.rule == rule]


# ---------------------------------------------------------------------------
# the shipped tree is clean

def test_shipped_tree_clean_all_rules():
    report = run_lint(ROOT)
    assert report.ok, "\n".join(v.format() for v in report.violations)
    # the waiver budget is intentional and visible — additions are a
    # review event, not background noise; since r15 the bound is the
    # COMMITTED ratchet the CLI enforces (goldens/waiver_budget.json),
    # so the test and CI can never disagree about it
    import json

    with open(f"{ROOT}/dryad_tpu/analysis/goldens/waiver_budget.json") as f:
        budget = json.load(f)["waivers"]
    assert len(report.waived) <= budget


# ---------------------------------------------------------------------------
# wired-grower-sort

def test_wired_grower_sort_seeded_tile_plan():
    src = SourceTree(ROOT).read("dryad_tpu/engine/levelwise.py")
    bad = src + "\n_resurrected = tile_plan\n"
    rep = _violations("wired-grower-sort",
                      {"dryad_tpu/engine/levelwise.py": bad})
    assert any("tile_plan" in v.message for v in
               _rule_hits(rep, "wired-grower-sort"))


def test_wired_grower_sort_seeded_row_sort():
    src = SourceTree(ROOT).read("dryad_tpu/engine/leafwise_fast.py")
    bad = src + ("\ndef _sneaky(rows):\n"
                 "    return jnp.argsort(rows)\n")
    rep = _violations("wired-grower-sort",
                      {"dryad_tpu/engine/leafwise_fast.py": bad})
    assert _rule_hits(rep, "wired-grower-sort")


def test_wired_grower_existing_slot_argsort_is_waived():
    rep = _violations("wired-grower-sort")
    assert not rep.violations
    assert any(w.rule == "wired-grower-sort" for _, w in rep.waived), \
        "the (L,)-slot gain argsort must be waived, not invisible"


# ---------------------------------------------------------------------------
# batcher-device-fetch

@pytest.mark.parametrize("snippet", [
    "import jax\n",
    "from jax import numpy as jnp\n",
    "def _f(x):\n    return np.asarray(x)\n",
    "def _f(x):\n    return jax_dev.device_get(x)\n",
])
def test_batcher_fetch_seeded(snippet):
    src = SourceTree(ROOT).read("dryad_tpu/serve/batcher.py")
    rep = _violations("batcher-device-fetch",
                      {"dryad_tpu/serve/batcher.py": src + "\n" + snippet})
    assert _rule_hits(rep, "batcher-device-fetch")


# ---------------------------------------------------------------------------
# obs-jax-free (direct + transitive)

def test_obs_direct_jax_import_seeded():
    src = SourceTree(ROOT).read("dryad_tpu/obs/spans.py")
    rep = _violations("obs-jax-free",
                      {"dryad_tpu/obs/spans.py": src + "\nimport jax\n"})
    assert _rule_hits(rep, "obs-jax-free")


def test_obs_lazy_function_level_jax_import_also_banned():
    # obs is STRICTLY jax-free: even a lazy in-function import is flagged
    src = SourceTree(ROOT).read("dryad_tpu/obs/spans.py")
    bad = src + "\ndef _lazy():\n    import jax\n    return jax\n"
    rep = _violations("obs-jax-free", {"dryad_tpu/obs/spans.py": bad})
    assert _rule_hits(rep, "obs-jax-free")


# ---------------------------------------------------------------------------
# fleet-jax-free (direct + transitive, r14)

def test_fleet_direct_jax_import_seeded():
    src = SourceTree(ROOT).read("dryad_tpu/fleet/router.py")
    rep = _violations("fleet-jax-free",
                      {"dryad_tpu/fleet/router.py": src + "\nimport jax\n"})
    assert _rule_hits(rep, "fleet-jax-free")


def test_fleet_lazy_jax_import_also_banned():
    src = SourceTree(ROOT).read("dryad_tpu/fleet/supervisor.py")
    bad = src + "\ndef _lazy():\n    from jax import numpy\n    return numpy\n"
    rep = _violations("fleet-jax-free",
                      {"dryad_tpu/fleet/supervisor.py": bad})
    assert _rule_hits(rep, "fleet-jax-free")


def test_fleet_transitive_jax_import_seeded():
    # an innocent-looking module-level import of an engine helper pulls
    # jax into `import dryad_tpu.fleet` — the chain must be reported
    src = SourceTree(ROOT).read("dryad_tpu/fleet/replica.py")
    bad = "from dryad_tpu.engine.distributed import make_mesh\n" + src
    rep = _violations("fleet-jax-free",
                      {"dryad_tpu/fleet/replica.py": bad})
    hits = _rule_hits(rep, "fleet-jax-free")
    assert hits and any("transitive" in v.message for v in hits)


def test_fleet_device_fetch_shape_banned():
    src = SourceTree(ROOT).read("dryad_tpu/fleet/router.py")
    bad = src + "\ndef _peek(x):\n    return x.addressable_data(0)\n"
    rep = _violations("fleet-jax-free", {"dryad_tpu/fleet/router.py": bad})
    assert _rule_hits(rep, "fleet-jax-free")


def test_obs_transitive_jax_import_seeded():
    # registry.py -> engine.distributed -> jax: no obs file mentions jax,
    # only the import-graph walk can see it (the r11 upgrade over grep)
    src = SourceTree(ROOT).read("dryad_tpu/obs/registry.py")
    bad = ("from dryad_tpu.engine.distributed import make_mesh  # innocent\n"
           + src)
    rep = _violations("obs-jax-free", {"dryad_tpu/obs/registry.py": bad})
    hits = _rule_hits(rep, "obs-jax-free")
    assert any("transitive" in v.message for v in hits), \
        [v.message for v in hits]


def test_obs_transitive_through_new_internal_module():
    # two hops through a module that itself looks harmless
    helper = "import jax\n\ndef now():\n    return 0.0\n"
    src = SourceTree(ROOT).read("dryad_tpu/obs/spans.py")
    bad = "from dryad_tpu._timeutil import now\n" + src
    rep = _violations("obs-jax-free", {
        "dryad_tpu/_timeutil.py": helper,
        "dryad_tpu/obs/spans.py": bad,
    })
    assert any("transitive" in v.message
               for v in _rule_hits(rep, "obs-jax-free"))


def test_obs_clean_tree_has_no_transitive_jax():
    rep = _violations("obs-jax-free")
    assert not rep.violations


# ---------------------------------------------------------------------------
# jit-closure-constant

_CLOSURE_BAD = textwrap.dedent("""
    import jax
    import jax.numpy as jnp
    import numpy as np

    def run(n):
        big = np.zeros((n,), np.float32)

        @jax.jit
        def f(x):
            return x + big

        return f
""")

_CLOSURE_OK = textwrap.dedent("""
    import jax
    import jax.numpy as jnp
    import numpy as np

    def run(n):
        big = np.zeros((n,), np.float32)

        @jax.jit
        def f(x, big):
            return x + big

        return f(jnp.ones((n,)), big)
""")


def test_jit_closure_constant_seeded():
    rep = _violations("jit-closure-constant",
                      {"dryad_tpu/_fixture_jit.py": _CLOSURE_BAD})
    hits = _rule_hits(rep, "jit-closure-constant")
    assert hits and "big" in hits[0].message


def test_jit_closure_constant_lambda_and_partial_forms():
    src = textwrap.dedent("""
        import jax
        import jax.numpy as jnp
        from functools import partial

        def run(n):
            table = jnp.arange(n)
            f = jax.jit(lambda x: x + table)
            g = partial(jax.jit, static_argnames=())(lambda x: x * table)
            return f, g
    """)
    rep = _violations("jit-closure-constant",
                      {"dryad_tpu/_fixture_jit.py": src})
    assert len(_rule_hits(rep, "jit-closure-constant")) == 2


def test_jit_closure_constant_argument_passing_is_clean():
    rep = _violations("jit-closure-constant",
                      {"dryad_tpu/_fixture_jit.py": _CLOSURE_OK})
    assert not _rule_hits(rep, "jit-closure-constant")


def test_jit_closure_shipped_tree_clean():
    rep = _violations("jit-closure-constant")
    assert not rep.violations


# ---------------------------------------------------------------------------
# bench-real-fetch

_BENCH_BAD = textwrap.dedent("""
    import time
    import jax

    def probe(step, s0):
        prog = jax.jit(lambda s: jax.lax.fori_loop(0, 8, step, s))
        t0 = time.perf_counter()
        prog(s0)
        return time.perf_counter() - t0
""")


def test_bench_real_fetch_seeded():
    rep = _violations("bench-real-fetch",
                      {"scripts/_fixture_probe.py": _BENCH_BAD})
    assert _rule_hits(rep, "bench-real-fetch")


def test_bench_real_fetch_float_fetch_is_clean():
    ok = _BENCH_BAD.replace("prog(s0)\n", "float(prog(s0))\n")
    rep = _violations("bench-real-fetch",
                      {"scripts/_fixture_probe.py": ok})
    assert not _rule_hits(rep, "bench-real-fetch")


def test_bench_real_fetch_shipped_bench_is_clean():
    rep = _violations("bench-real-fetch")
    assert not rep.violations


# ---------------------------------------------------------------------------
# dead-perturbation

def test_dead_perturbation_seeded_astype():
    src = ("import jax.numpy as jnp\n"
           "def f(s, tab):\n"
           "    return tab[(s + 0.001).astype(jnp.int32)]\n")
    rep = _violations("dead-perturbation",
                      {"scripts/_fixture_perturb.py": src})
    assert _rule_hits(rep, "dead-perturbation")


def test_dead_perturbation_seeded_int_cast():
    src = ("import jax.numpy as jnp\n"
           "def f(s, tab):\n"
           "    return tab[jnp.int32(s + 1e-3)]\n")
    rep = _violations("dead-perturbation",
                      {"scripts/_fixture_perturb.py": src})
    assert _rule_hits(rep, "dead-perturbation")


def test_dead_perturbation_whole_unit_advance_is_clean():
    src = ("import jax.numpy as jnp\n"
           "def f(s, tab):\n"
           "    return tab[(s + 1.0).astype(jnp.int32)]\n")
    rep = _violations("dead-perturbation",
                      {"scripts/_fixture_perturb.py": src})
    assert not _rule_hits(rep, "dead-perturbation")


# ---------------------------------------------------------------------------
# waiver machinery

def test_waiver_suppresses_and_is_counted():
    src = SourceTree(ROOT).read("dryad_tpu/serve/batcher.py")
    bad = (src + "\ndef _peek(x):\n"
           "    # dryadlint: disable=batcher-device-fetch -- fixture reason\n"
           "    return x.addressable_data(0)\n")
    rep = _violations("batcher-device-fetch",
                      {"dryad_tpu/serve/batcher.py": bad})
    assert not _rule_hits(rep, "batcher-device-fetch")
    assert any(w.reason == "fixture reason" for _, w in rep.waived)


def test_waiver_without_reason_is_an_error():
    rep = LintReport()
    parse_waivers("x.py", "y = 1  # dryadlint: disable=some-rule\n", rep)
    assert rep.errors and "reason" in rep.errors[0]


def test_file_level_waiver_covers_whole_file():
    src = SourceTree(ROOT).read("dryad_tpu/serve/batcher.py")
    bad = ("# dryadlint: disable-file=batcher-device-fetch -- fixture\n"
           + src + "\ndef _peek(x):\n    return x.addressable_data(0)\n")
    rep = _violations("batcher-device-fetch",
                      {"dryad_tpu/serve/batcher.py": bad})
    assert not _rule_hits(rep, "batcher-device-fetch")
    assert rep.waived


def test_unknown_rule_name_rejected():
    with pytest.raises(ValueError):
        run_lint(ROOT, rule_names=["no-such-rule"])


# ---------------------------------------------------------------------------
# CLI

def test_cli_list_rules_and_lint_pass():
    from dryad_tpu.analysis.__main__ import main

    assert main(["--list-rules"]) == 0
    assert main(["--lint", "-q"]) == 0


def test_cli_lint_failure_exit_code(tmp_path):
    # a minimal bad tree: exit code 2 distinguishes lint from audit fails
    pkg = tmp_path / "dryad_tpu" / "obs"
    pkg.mkdir(parents=True)
    (pkg / "bad.py").write_text("import jax\n")
    from dryad_tpu.analysis.__main__ import main

    assert main(["--lint", "-q", "--root", str(tmp_path)]) == 2


def test_wired_grower_sort_seeded_aliased_import():
    """Review r11: `from ... import tile_plan as _tp` dodges a Name scan —
    the import itself must trip the rule."""
    src = SourceTree(ROOT).read("dryad_tpu/engine/levelwise.py")
    bad = src + "\nfrom dryad_tpu.engine.pallas_hist import tile_plan as _tp\n"
    rep = _violations("wired-grower-sort",
                      {"dryad_tpu/engine/levelwise.py": bad})
    assert any("import" in v.message for v in
               _rule_hits(rep, "wired-grower-sort"))


def test_wired_grower_sort_seeded_lexsort():
    src = SourceTree(ROOT).read("dryad_tpu/engine/levelwise.py")
    bad = src + "\ndef _sneaky(a, b):\n    return jnp.lexsort((a, b))\n"
    rep = _violations("wired-grower-sort",
                      {"dryad_tpu/engine/levelwise.py": bad})
    assert _rule_hits(rep, "wired-grower-sort")


def test_bench_real_fetch_host_scalar_float_is_not_a_fetch():
    """Review r11: float(K) converts a host scalar — it must NOT satisfy
    the fetch requirement (only conversions of call results count)."""
    bad = _BENCH_BAD.replace("return time.perf_counter() - t0\n",
                             "return (time.perf_counter() - t0) / float(8)\n")
    rep = _violations("bench-real-fetch",
                      {"scripts/_fixture_probe.py": bad})
    assert _rule_hits(rep, "bench-real-fetch")


def test_bench_real_fetch_float_of_call_result_name_counts():
    ok = _BENCH_BAD.replace("prog(s0)\n", "r = prog(s0)\n        float(r)\n")
    rep = _violations("bench-real-fetch",
                      {"scripts/_fixture_probe.py": ok})
    assert not _rule_hits(rep, "bench-real-fetch")


# ---------------------------------------------------------------------------
# introspect-compile-only (r12)

def test_introspect_cost_analysis_seeded_outside_introspect():
    src = SourceTree(ROOT).read("dryad_tpu/engine/levelwise.py")
    bad = src + ("\ndef _peek(fn, x):\n"
                 "    return fn.lower(x).cost_analysis()\n")
    rep = _violations("introspect-compile-only",
                      {"dryad_tpu/engine/levelwise.py": bad})
    assert any("cost_analysis" in v.message for v in
               _rule_hits(rep, "introspect-compile-only"))


def test_introspect_aot_compile_seeded_in_serve():
    src = SourceTree(ROOT).read("dryad_tpu/serve/cache.py")
    bad = src + ("\ndef _aot(fn, x):\n"
                 "    return fn.lower(x).compile()\n")
    rep = _violations("introspect-compile-only",
                      {"dryad_tpu/serve/cache.py": bad})
    assert any(".compile()" in v.message for v in
               _rule_hits(rep, "introspect-compile-only"))


def test_introspect_re_compile_with_args_is_clean():
    # re.compile(pattern) takes arguments — only the zero-arg AOT form is
    # the banned shape (resilience/faults.py uses re.compile today)
    src = SourceTree(ROOT).read("dryad_tpu/resilience/faults.py")
    bad = src + '\n_EXTRA_PAT = re.compile("x")\n'
    rep = _violations("introspect-compile-only",
                      {"dryad_tpu/resilience/faults.py": bad})
    assert not _rule_hits(rep, "introspect-compile-only")


def test_introspect_capture_inside_traced_body_seeded():
    src = SourceTree(ROOT).read("dryad_tpu/engine/levelwise.py")
    bad = src + (
        "\ndef _hot(n, s, fn):\n"
        "    def body(i, carry):\n"
        "        introspect.capture('train.chunk', ('k',), fn)\n"
        "        return carry\n"
        "    return jax.lax.fori_loop(0, n, body, s)\n")
    rep = _violations("introspect-compile-only",
                      {"dryad_tpu/engine/levelwise.py": bad})
    assert any("traced body" in v.message for v in
               _rule_hits(rep, "introspect-compile-only"))


def test_introspect_expensive_call_in_loop_inside_introspect_py():
    src = SourceTree(ROOT).read("dryad_tpu/engine/introspect.py")
    bad = src + ("\ndef _sweep(lowereds):\n"
                 "    out = []\n"
                 "    for low in lowereds:\n"
                 "        out.append(low.cost_analysis())\n"
                 "    return out\n")
    rep = _violations("introspect-compile-only",
                      {"dryad_tpu/engine/introspect.py": bad})
    assert _rule_hits(rep, "introspect-compile-only")


def test_introspect_shipped_tree_clean():
    rep = _violations("introspect-compile-only")
    assert not rep.violations, "\n".join(
        v.format() for v in rep.violations)


def test_obs_trends_is_covered_by_the_transitive_jax_walk():
    """The r12 satellite's explicit check: obs/trends.py rides the
    obs-jax-free TRANSITIVE walk — a jax import seeded there (directly or
    through an innocent-looking helper) must be flagged."""
    src = SourceTree(ROOT).read("dryad_tpu/obs/trends.py")
    rep = _violations("obs-jax-free",
                      {"dryad_tpu/obs/trends.py": src + "\nimport jax\n"})
    assert _rule_hits(rep, "obs-jax-free")
    helper = "import jax\n\ndef rev():\n    return 'x'\n"
    bad = "from dryad_tpu._gitutil import rev\n" + src
    rep = _violations("obs-jax-free", {
        "dryad_tpu/_gitutil.py": helper,
        "dryad_tpu/obs/trends.py": bad,
    })
    assert any("transitive" in v.message
               for v in _rule_hits(rep, "obs-jax-free"))


# ---------------------------------------------------------------------------
# unharnessed-timed-fori (r13)

_UNHARNESSED = textwrap.dedent("""
    import time
    import jax

    def my_loop_time(step, s0):
        prog = jax.jit(lambda s: jax.lax.fori_loop(0, 8, step, s))
        float(prog(s0))
        t0 = time.perf_counter()
        float(prog(s0))
        return time.perf_counter() - t0
""")


def test_unharnessed_fori_seeded_in_profile_script():
    """A hand-rolled timed fori in a living measurement script is a
    violation — the discipline lives in engine/probes.timed_fori."""
    rep = _violations("unharnessed-timed-fori",
                      {"scripts/profile_fixture.py": _UNHARNESSED})
    assert _rule_hits(rep, "unharnessed-timed-fori")


def test_unharnessed_fori_seeded_in_bench():
    src = SourceTree(ROOT).read("bench.py")
    rep = _violations("unharnessed-timed-fori",
                      {"bench.py": src + "\n" + _UNHARNESSED})
    assert _rule_hits(rep, "unharnessed-timed-fori")


def test_unharnessed_fori_harness_call_is_clean():
    ok = textwrap.dedent("""
        from dryad_tpu.engine.probes import timed_fori

        def measure(step, args):
            ms, spread = timed_fori(step, 3, 2, *args, label="x")
            return ms
    """)
    rep = _violations("unharnessed-timed-fori",
                      {"scripts/profile_fixture.py": ok})
    assert not _rule_hits(rep, "unharnessed-timed-fori")


def test_unharnessed_fori_shipped_tree_clean_and_exps_out_of_scope():
    """The migrated bench/profile/bench_* scripts are clean, and the
    archived exp_* one-shots (kept verbatim for provenance) are OUTSIDE
    the rule's targets rather than waived: the same seeded violation
    that fires in a profile script must produce zero hits in an exp_
    fixture."""
    rep = _violations("unharnessed-timed-fori")
    assert not rep.violations
    rep = _violations("unharnessed-timed-fori",
                      {"scripts/exp_fixture_probe.py": _UNHARNESSED})
    assert not _rule_hits(rep, "unharnessed-timed-fori")


def test_bench_real_fetch_covers_the_harness_module():
    """r13 rescope: engine/probes.py is in bench-real-fetch's targets —
    strip the harness's terminal fetches and the rule must fire."""
    src = SourceTree(ROOT).read("dryad_tpu/engine/probes.py")
    assert src.count("float(out[1])") == 3      # the three fetch sites
    bad = src.replace("float(out[1])", "out[1]")
    rep = _violations("bench-real-fetch",
                      {"dryad_tpu/engine/probes.py": bad})
    assert any(v.path == "dryad_tpu/engine/probes.py"
               for v in _rule_hits(rep, "bench-real-fetch"))


def test_dead_perturbation_covers_the_harness_module():
    src = SourceTree(ROOT).read("dryad_tpu/engine/probes.py")
    bad = src + ("\ndef _sneaky(s, tab):\n"
                 "    import jax.numpy as jnp\n"
                 "    return tab[(s + 0.001).astype(jnp.int32)]\n")
    rep = _violations("dead-perturbation",
                      {"dryad_tpu/engine/probes.py": bad})
    assert any(v.path == "dryad_tpu/engine/probes.py"
               for v in _rule_hits(rep, "dead-perturbation"))
