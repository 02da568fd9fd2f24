"""The dryadlint rule catalog.

Every rule here machine-checks an invariant this repo MEASURED (CLAUDE.md
"measuring" + "lowering facts" sections) or pinned by construction
(STATUS round deltas), so the rule docstrings cite the discipline, not
style.  Migrated from scripts/ci.sh greps in round 11:

=====================  =====================================================
rule                   invariant
=====================  =====================================================
wired-grower-sort      nothing on the wired grower paths sorts rows or
                       reaches the retired per-level tile_plan helpers
batcher-device-fetch   the serve dispatch loop never touches device results
                       (the ONE fetch lives in cache.execute_raw)
obs-jax-free           dryad_tpu/obs imports no jax, directly OR transitively
fleet-jax-free         dryad_tpu/fleet likewise (r14): the router/supervisor
                       must start and respawn while a device is wedged
jit-closure-constant   big arrays captured by jit closures become program
                       constants, copied into every compile of the program
bench-real-fetch       timed fori programs end in a REAL host fetch
dead-perturbation      a perturbation consumed only through integer rounding
                       is a dead input — XLA hoists the stage (2x-fast lies)
introspect-compile-only  cost_analysis/memory_analysis/AOT-compile() live in
                       engine/introspect.py ONLY, and never in a loop or a
                       traced (fori/scan) body — the recompile tripwire
                       must never become a per-iteration host sync (r12)
unharnessed-timed-fori  the timed-fori discipline lives in exactly one
                       place (engine/probes.timed_fori, with the runtime
                       liveness proof); bench/profile scripts must not
                       re-copy it around a raw lax.fori_loop (r13)
=====================  =====================================================
"""

from __future__ import annotations

import ast
from typing import Iterable, Optional

from dryad_tpu.analysis.importgraph import find_banned_chains
from dryad_tpu.analysis.lint import Rule, Violation, register

# ---------------------------------------------------------------------------
# AST helpers


def dotted(node: ast.AST) -> Optional[str]:
    """'jax.lax.sort' for Name/Attribute chains, else None."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _calls(tree: ast.AST) -> Iterable[ast.Call]:
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            yield node


def _imports_of(tree: ast.AST, roots: tuple) -> Iterable[tuple[int, str]]:
    """(line, module) for any import whose root package is in ``roots`` —
    function-local imports included (callers that need only module-level
    edges use importgraph instead)."""
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                if alias.name.split(".")[0] in roots:
                    yield node.lineno, alias.name
        elif isinstance(node, ast.ImportFrom) and node.level == 0:
            mod = node.module or ""
            if mod.split(".")[0] in roots:
                yield node.lineno, mod


# ---------------------------------------------------------------------------
# wired-grower-sort

_SORTISH = {"sort", "argsort", "lexsort", "sort_key_val", "top_k"}


def _check_wired_grower(path, src, tree):
    out = []
    for node in ast.walk(tree):
        if isinstance(node, (ast.Import, ast.ImportFrom)):
            # aliased imports would dodge the Name/Attribute scan below
            # (`from ...plan import tile_plan as _tp`)
            names = [getattr(node, "module", None) or ""]
            for alias in node.names:
                names += [alias.name, alias.asname or ""]
            for n in names:
                if "tile_plan" in n:
                    out.append(Violation(
                        "wired-grower-sort", path, node.lineno,
                        f"import of retired per-level sort helper {n!r} in "
                        "a wired grower — the per-level sort/gather is gone "
                        "(r6/r10); route legacy configs through "
                        "build_hist_segmented"))
        if isinstance(node, (ast.Attribute, ast.Name)):
            leaf = node.attr if isinstance(node, ast.Attribute) else node.id
            if "tile_plan" in leaf:
                out.append(Violation(
                    "wired-grower-sort", path, node.lineno,
                    f"reference to retired per-level sort helper {leaf!r} — "
                    "the wired growers' whole point is that the per-level "
                    "sort/gather is gone (r6/r10); route legacy configs "
                    "through build_hist_segmented"))
        if isinstance(node, ast.Call):
            name = dotted(node.func)
            leaf = name.rsplit(".", 1)[-1] if name else None
            if leaf in _SORTISH:
                out.append(Violation(
                    "wired-grower-sort", path, node.lineno,
                    f"{name}(...) in a wired grower — nothing on the wired "
                    "path sorts rows (the layout replaces the per-level "
                    "sort); if this sorts an (L,)-sized slot table, waive "
                    "with the shape rationale"))
    return out


register(Rule(
    name="wired-grower-sort",
    doc="wired growers must not sort rows nor reach tile_plan helpers",
    targets=("dryad_tpu/engine/levelwise.py",
             "dryad_tpu/engine/leafwise_fast.py"),
    check=_check_wired_grower,
))


# ---------------------------------------------------------------------------
# batcher-device-fetch

def _check_batcher(path, src, tree):
    out = []
    for line, mod in _imports_of(tree, ("jax", "jaxlib")):
        out.append(Violation(
            "batcher-device-fetch", path, line,
            f"import {mod} in the serve batcher — the collect/dispatch "
            "loop is host-only; the single result fetch belongs in "
            "cache.execute_raw"))
    for node in ast.walk(tree):
        if isinstance(node, ast.Attribute) and node.attr in (
                "device_get", "asnumpy", "addressable_data"):
            out.append(Violation(
                "batcher-device-fetch", path, node.lineno,
                f".{node.attr} in the serve batcher — a fetch growing back "
                "into the dispatch loop serializes the overlapped pipeline"))
        if isinstance(node, ast.Call):
            name = dotted(node.func)
            if name in ("np.asarray", "numpy.asarray", "np.array",
                        "numpy.array"):
                out.append(Violation(
                    "batcher-device-fetch", path, node.lineno,
                    f"{name}(...) in the serve batcher — materializing here "
                    "would fetch device buffers inside the dispatch loop"))
    return out


register(Rule(
    name="batcher-device-fetch",
    doc="serve/batcher.py stays fetch-free and jax-free",
    targets=("dryad_tpu/serve/batcher.py",),
    check=_check_batcher,
))


# ---------------------------------------------------------------------------
# obs-jax-free (direct bans per file + transitive import closure)

def _check_obs_direct(path, src, tree):
    out = []
    for line, mod in _imports_of(tree, ("jax", "jaxlib")):
        out.append(Violation(
            "obs-jax-free", path, line,
            f"import {mod} in dryad_tpu/obs — obs collectors are host-side "
            "only and the package is jax-free by lint (r9); record values "
            "the engine already fetched"))
    for node in ast.walk(tree):
        if isinstance(node, ast.Attribute) and node.attr in (
                "device_get", "addressable_data", "asnumpy"):
            out.append(Violation(
                "obs-jax-free", path, node.lineno,
                f".{node.attr} in dryad_tpu/obs — obs must never touch "
                "device buffers (CLAUDE.md never-fetch-per-iteration)"))
        if isinstance(node, ast.Call):
            name = dotted(node.func)
            if name in ("np.asarray", "numpy.asarray"):
                out.append(Violation(
                    "obs-jax-free", path, node.lineno,
                    f"{name}(...) in dryad_tpu/obs — materializing arrays "
                    "here is the device-fetch shape the r9 lint bans"))
    return out


def _tree_check_obs(sources, tree):
    out = []
    chains = find_banned_chains(sorted(sources), tree,
                                banned_roots=("jax", "jaxlib"))
    for chain, banned in chains:
        entry = chain[0]
        out.append(Violation(
            "obs-jax-free", _module_rel(entry, tree), 1,
            "transitive jax import: " + " -> ".join(chain)
            + " — importing dryad_tpu.obs must not pull in jax "
            "(jax-free-by-construction contract, r9/r11)"))
    return out


def _module_rel(mod: str, tree) -> str:
    from dryad_tpu.analysis.importgraph import module_path_candidates

    for cand in module_path_candidates(mod):
        if tree.exists(cand):
            return cand
    return mod


register(Rule(
    name="obs-jax-free",
    doc="dryad_tpu/obs is jax-free, directly and transitively",
    targets=("dryad_tpu/obs/**",),
    check=_check_obs_direct,
    tree_check=_tree_check_obs,
))


# ---------------------------------------------------------------------------
# fleet-jax-free (r14) — the same contract as obs, for the same reason:
# the fleet router and supervisor are host-side process/socket machinery
# that must start, route, and respawn while a replica's device is wedged.
# A jax import here would (a) couple router startup to device init and
# (b) tempt a device fetch into the routing loop.  Direct bans are strict
# (lazy in-function imports included); the transitive check walks
# module-level imports — e.g. an innocent helper pulled from engine/
# would flag the whole chain.

def _check_fleet_direct(path, src, tree):
    out = []
    for line, mod in _imports_of(tree, ("jax", "jaxlib")):
        out.append(Violation(
            "fleet-jax-free", path, line,
            f"import {mod} in dryad_tpu/fleet — the fleet layer is "
            "host-side process/socket supervision and jax-free by lint "
            "(r14); replicas own the devices, the fleet owns processes"))
    for node in ast.walk(tree):
        if isinstance(node, ast.Attribute) and node.attr in (
                "device_get", "addressable_data", "asnumpy"):
            out.append(Violation(
                "fleet-jax-free", path, node.lineno,
                f".{node.attr} in dryad_tpu/fleet — the router/supervisor "
                "must never touch device buffers; every value crosses HTTP"))
    return out


def _tree_check_fleet(sources, tree):
    out = []
    chains = find_banned_chains(sorted(sources), tree,
                                banned_roots=("jax", "jaxlib"))
    for chain, banned in chains:
        entry = chain[0]
        out.append(Violation(
            "fleet-jax-free", _module_rel(entry, tree), 1,
            "transitive jax import: " + " -> ".join(chain)
            + " — importing dryad_tpu.fleet must not pull in jax "
            "(jax-free-by-construction contract, r14; import from the "
            "jax-free leaf modules — obs, resilience.faults/journal/"
            "policy — not the packages that wrap them)"))
    return out


register(Rule(
    name="fleet-jax-free",
    doc="dryad_tpu/fleet is jax-free, directly and transitively",
    targets=("dryad_tpu/fleet/**",),
    check=_check_fleet_direct,
    tree_check=_tree_check_fleet,
))


# ---------------------------------------------------------------------------
# continual-jax-free (r19) — the retrain scheduler and probation publisher
# live in the fleet control plane: they must tail the journal, debounce,
# launch, push, and roll back while a replica's (or the retrain worker's)
# device is wedged.  The retrain itself is a SUBPROCESS
# (`python -m dryad_tpu retrain`) — that is the only jax-importing piece
# of the continual loop, and it is outside this package by construction.

def _check_continual_direct(path, src, tree):
    out = []
    for line, mod in _imports_of(tree, ("jax", "jaxlib")):
        out.append(Violation(
            "continual-jax-free", path, line,
            f"import {mod} in dryad_tpu/continual — the scheduler/publisher "
            "are control-plane machinery and jax-free by lint (r19); the "
            "retrain worker subprocess owns the devices"))
    for node in ast.walk(tree):
        if isinstance(node, ast.Attribute) and node.attr in (
                "device_get", "addressable_data", "asnumpy"):
            out.append(Violation(
                "continual-jax-free", path, node.lineno,
                f".{node.attr} in dryad_tpu/continual — the control plane "
                "must never touch device buffers; artifacts cross the "
                "filesystem, verdicts cross HTTP"))
    return out


def _tree_check_continual(sources, tree):
    out = []
    chains = find_banned_chains(sorted(sources), tree,
                                banned_roots=("jax", "jaxlib"))
    for chain, banned in chains:
        entry = chain[0]
        out.append(Violation(
            "continual-jax-free", _module_rel(entry, tree), 1,
            "transitive jax import: " + " -> ".join(chain)
            + " — importing dryad_tpu.continual must not pull in jax "
            "(r19; the booster/mapper stay out — model_has_profile sniffs "
            "artifacts with numpy+json, the retrain worker subprocess does "
            "the loading)"))
    return out


register(Rule(
    name="continual-jax-free",
    doc="dryad_tpu/continual is jax-free, directly and transitively",
    targets=("dryad_tpu/continual/**",),
    check=_check_continual_direct,
    tree_check=_tree_check_continual,
))


# ---------------------------------------------------------------------------
# policy-jax-free (r23) — the thresholds dict keys dispatch decisions
# and must load in the fleet control plane (serve /stats, the supervisor)
# while a device is wedged; resolvers are pure dict-and-compare code.
# The ONE sanctioned exception is the lazy best-effort device_kind probe
# in policy/device.py, waived inline (and counted by the ratchet).

def _check_policy_direct(path, src, tree):
    out = []
    for line, mod in _imports_of(tree, ("jax", "jaxlib")):
        out.append(Violation(
            "policy-jax-free", path, line,
            f"import {mod} in dryad_tpu/policy — gate resolution is "
            "host-side dict lookup and jax-free by lint (r23)"))
    for node in ast.walk(tree):
        if isinstance(node, ast.Attribute) and node.attr in (
                "device_get", "addressable_data", "asnumpy"):
            out.append(Violation(
                "policy-jax-free", path, node.lineno,
                f".{node.attr} in dryad_tpu/policy — a gate resolver "
                "must never touch device buffers"))
    return out


def _tree_check_policy(sources, tree):
    out = []
    chains = find_banned_chains(sorted(sources), tree,
                                banned_roots=("jax", "jaxlib"))
    for chain, banned in chains:
        entry = chain[0]
        out.append(Violation(
            "policy-jax-free", _module_rel(entry, tree), 1,
            "transitive jax import: " + " -> ".join(chain)
            + " — importing dryad_tpu.policy must not pull in jax (r23)"))
    return out


register(Rule(
    name="policy-jax-free",
    doc="dryad_tpu/policy is jax-free, directly and transitively",
    targets=("dryad_tpu/policy/**",),
    check=_check_policy_direct,
    tree_check=_tree_check_policy,
))


# ---------------------------------------------------------------------------
# gate-through-policy (r23) — the dispatch-gate functions must read their
# thresholds from policy/gates.py's thresholds dict, never from re-inlined
# literals: a constant hand-edited at ONE call site silently forks the
# gate from the dict (and from every other caller), which is
# exactly the two-copy drift select_bins' r5 review caught.  Structural
# encoding widths stay at the call sites as NAMED module constants
# (levelwise._MAX_PACKED_BINS) — the rule flags folded int literals at or
# past 512 (the smallest numeric threshold) inside the known gate
# functions only, so shape arithmetic like ``9 + F * itemsize`` passes.

_GATE_FUNCTIONS = {
    "partition_prefers_reduce", "hist_reduce_resolved",
    "deep_layout_supported", "leafwise_layout_supported",
    "resolve_backend", "stage_trees",
}
_GATE_LITERAL_FLOOR = 512


def _fold_int(node) -> Optional[int]:
    """Constant-fold an int expression (``1 << 15`` must not evade the
    rule by being spelled as a BinOp)."""
    if isinstance(node, ast.Constant) and type(node.value) is int:
        return node.value
    if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.USub):
        v = _fold_int(node.operand)
        return -v if v is not None else None
    if isinstance(node, ast.BinOp):
        left, right = _fold_int(node.left), _fold_int(node.right)
        if left is None or right is None:
            return None
        try:
            if isinstance(node.op, ast.LShift):
                return left << right
            if isinstance(node.op, ast.Add):
                return left + right
            if isinstance(node.op, ast.Sub):
                return left - right
            if isinstance(node.op, ast.Mult):
                return left * right
            if isinstance(node.op, ast.Pow) and right < 64:
                return left ** right
            if isinstance(node.op, ast.FloorDiv) and right != 0:
                return left // right
        except (OverflowError, ValueError):
            return None
    return None


def _check_gate_literals(path, src, tree):
    out = []
    for fn in ast.walk(tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if fn.name not in _GATE_FUNCTIONS:
            continue
        # fold top-down and don't descend into folded expressions, so
        # `1 << 15` reports once (as 32768), not once per operand
        stack = list(ast.iter_child_nodes(fn))
        while stack:
            node = stack.pop()
            folded = _fold_int(node) if isinstance(
                node, (ast.Constant, ast.BinOp, ast.UnaryOp)) else None
            if folded is not None:
                if abs(folded) >= _GATE_LITERAL_FLOOR:
                    out.append(Violation(
                        "gate-through-policy", path, node.lineno,
                        f"literal {folded} inside gate function "
                        f"{fn.name}() — dispatch thresholds live in "
                        "policy/gates.py's thresholds dict; resolve "
                        "through policy.gates.resolve()/gate_value() so "
                        "the dict stays the single source"))
                continue
            stack.extend(ast.iter_child_nodes(node))
    return out


register(Rule(
    name="gate-through-policy",
    doc="dispatch-gate functions read thresholds from policy/gates.py, "
        "not re-inlined literals",
    targets=("dryad_tpu/config.py", "dryad_tpu/engine/levelwise.py",
             "dryad_tpu/engine/leafwise_fast.py",
             "dryad_tpu/engine/histogram.py", "dryad_tpu/engine/predict.py",
             "dryad_tpu/serve/server.py", "dryad_tpu/resilience/policy.py"),
    check=_check_gate_literals,
))


# ---------------------------------------------------------------------------
# jit-closure-constant

_MATERIALIZERS = {
    "asarray", "array", "zeros", "ones", "full", "empty", "arange",
    "linspace", "load", "fromfile", "frombuffer", "stack", "concatenate",
    "tile", "device_put",
    # host RNG draws are dataset-scale arrays too
    "normal", "uniform", "integers", "random", "standard_normal",
    "permutation", "choice",
}
_ARRAY_ROOTS = {"np", "numpy", "jnp", "jax", "rng"}


def _is_materializer(call: ast.Call) -> bool:
    name = dotted(call.func)
    if not name or "." not in name:
        return False
    root, leaf = name.split(".", 1)[0], name.rsplit(".", 1)[-1]
    return leaf in _MATERIALIZERS and root in _ARRAY_ROOTS


def _bound_names(fn: ast.AST) -> set[str]:
    """Names bound inside a function body (params, assigns, loops, defs)."""
    bound: set[str] = set()
    if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
        a = fn.args
        for arg in (list(a.posonlyargs) + list(a.args) + list(a.kwonlyargs)
                    + ([a.vararg] if a.vararg else [])
                    + ([a.kwarg] if a.kwarg else [])):
            bound.add(arg.arg)
    for node in ast.walk(fn):
        if isinstance(node, (ast.Assign, ast.AnnAssign, ast.AugAssign,
                             ast.NamedExpr)):
            targets = (node.targets if isinstance(node, ast.Assign)
                       else [node.target])
            for t in targets:
                for sub in ast.walk(t):
                    if isinstance(sub, ast.Name):
                        bound.add(sub.id)
        elif isinstance(node, (ast.For, ast.AsyncFor)):
            for sub in ast.walk(node.target):
                if isinstance(sub, ast.Name):
                    bound.add(sub.id)
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            bound.add(node.name)
        elif isinstance(node, (ast.Import, ast.ImportFrom)):
            for alias in node.names:
                bound.add((alias.asname or alias.name).split(".")[0])
        elif isinstance(node, ast.comprehension):
            for sub in ast.walk(node.target):
                if isinstance(sub, ast.Name):
                    bound.add(sub.id)
        elif isinstance(node, ast.ExceptHandler) and node.name:
            bound.add(node.name)
        elif isinstance(node, ast.withitem) and node.optional_vars:
            for sub in ast.walk(node.optional_vars):
                if isinstance(sub, ast.Name):
                    bound.add(sub.id)
    return bound


def _free_names(fn: ast.AST) -> set[str]:
    bound = _bound_names(fn)
    free: set[str] = set()
    for node in ast.walk(fn):
        if isinstance(node, ast.Name) and isinstance(node.ctx, ast.Load):
            if node.id not in bound:
                free.add(node.id)
    return free


def _is_jit_expr(node: ast.AST) -> bool:
    """jax.jit / jit, or partial(jax.jit, ...)."""
    name = dotted(node)
    if name in ("jax.jit", "jit"):
        return True
    if isinstance(node, ast.Call):
        fname = dotted(node.func)
        if fname in ("partial", "functools.partial") and node.args:
            return _is_jit_expr(node.args[0])
    return False


def _materializer_assigns(scope: ast.AST) -> dict[str, int]:
    """name -> line for direct assignments from array materializers in this
    scope (nested function bodies excluded — their locals are not this
    scope's bindings)."""
    out: dict[str, int] = {}

    def visit(node):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.Lambda)):
                continue
            if isinstance(child, ast.Assign) and isinstance(
                    child.value, ast.Call) and _is_materializer(child.value):
                for t in child.targets:
                    if isinstance(t, ast.Name):
                        out[t.id] = child.lineno
            visit(child)

    visit(scope)
    return out


def _scope_chain(node: ast.AST, parents: dict) -> list:
    """Enclosing scopes of ``node``, outermost (Module) first, the node
    itself excluded."""
    chain = []
    cur = parents.get(id(node))
    while cur is not None:
        if isinstance(cur, (ast.Module, ast.FunctionDef,
                            ast.AsyncFunctionDef, ast.Lambda)):
            chain.append(cur)
        cur = parents.get(id(cur))
    return list(reversed(chain))


def _check_jit_closures(path, src, tree):
    out = []
    parents: dict[int, ast.AST] = {}
    for node in ast.walk(tree):
        for child in ast.iter_child_nodes(node):
            parents[id(child)] = node

    # (jitted function node, jit site line, enclosing scope chain)
    sites: list[tuple] = []
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if any(_is_jit_expr(d) for d in node.decorator_list):
                sites.append((node, node.lineno, _scope_chain(node, parents)))
        if isinstance(node, ast.Call) and _is_jit_expr(node.func) and node.args:
            target = node.args[0]
            if isinstance(target, ast.Lambda):
                sites.append((target, node.lineno,
                              _scope_chain(node, parents)))
            elif isinstance(target, ast.Name):
                # nearest def with that name whose scope chain is a prefix
                # of the call site's chain (same or enclosing scope)
                call_chain = _scope_chain(node, parents)
                best = None
                for d in ast.walk(tree):
                    if isinstance(d, (ast.FunctionDef, ast.AsyncFunctionDef))\
                            and d.name == target.id:
                        d_chain = _scope_chain(d, parents)
                        if all(any(s is c for c in call_chain)
                               for s in d_chain):
                            if best is None or len(d_chain) > len(best[1]):
                                best = (d, d_chain)
                if best is not None:
                    sites.append((best[0], node.lineno, best[1]))

    seen = set()
    for fn, line, chain in sites:
        key = (id(fn), line)
        if key in seen:
            continue
        seen.add(key)
        free = _free_names(fn)
        for scope in reversed(chain):
            mats = _materializer_assigns(scope)
            for name in sorted(free & set(mats)):
                out.append(Violation(
                    "jit-closure-constant", path, line,
                    f"jitted function closes over {name!r} (materialized at "
                    f"line {mats[name]}) — closed-over arrays become "
                    "program constants, serialized into every compile and "
                    "every cache entry of the program; pass it as an "
                    "argument"))
                free.discard(name)   # report the INNERMOST binding only
    return out


register(Rule(
    name="jit-closure-constant",
    doc="no materialized arrays captured by jit closures",
    targets=("dryad_tpu/**", "bench.py", "scripts/*.py", "__graft_entry__.py"),
    check=_check_jit_closures,
))


# ---------------------------------------------------------------------------
# bench-real-fetch

_FETCHERS = {"float", "int"}
_FETCH_CALLS = {"np.asarray", "numpy.asarray", "np.array", "numpy.array",
                "jax.device_get", "device_get"}


def _call_result_names(fn: ast.AST) -> set[str]:
    """Names bound (anywhere in the function) from a Call result — the
    light dataflow that separates ``float(result)`` (result = prog(...),
    a real device fetch) from ``float(K)`` (a host scalar conversion that
    fetches nothing)."""
    out: set[str] = set()
    for node in ast.walk(fn):
        if isinstance(node, ast.Assign) and isinstance(node.value,
                                                       (ast.Call,
                                                        ast.Subscript)):
            for t in node.targets:
                if isinstance(t, ast.Name):
                    out.add(t.id)
                elif isinstance(t, ast.Tuple):
                    for el in t.elts:
                        if isinstance(el, ast.Name):
                            out.add(el.id)
    return out


def _has_real_fetch(fn: ast.AST) -> bool:
    from_calls = _call_result_names(fn)
    for call in _calls(fn):
        name = dotted(call.func)
        if name in _FETCH_CALLS:
            return True
        if isinstance(call.func, ast.Name) and call.func.id in _FETCHERS \
                and call.args:
            arg = call.args[0]
            # only count conversions of DEVICE results: a direct call /
            # subscript, or a name assigned from one — float(K) over a
            # host scalar would otherwise silence the rule with no fetch
            if isinstance(arg, (ast.Call, ast.Subscript)):
                return True
            if isinstance(arg, ast.Name) and arg.id in from_calls:
                return True
        if isinstance(call.func, ast.Attribute) and call.func.attr in (
                "item", "tolist"):
            return True
    return False


def _check_bench_fetch(path, src, tree):
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        n_perf = sum(1 for c in _calls(node)
                     if (dotted(c.func) or "").endswith("perf_counter"))
        has_fori = any((dotted(c.func) or "").endswith("fori_loop")
                       for c in _calls(node))
        if n_perf >= 2 and has_fori and not _has_real_fetch(node):
            out.append(Violation(
                "bench-real-fetch", path, node.lineno,
                f"timed fori program in {node.name}() never fetches — "
                "dispatch is async, so the wall measures the enqueue; end "
                "the timed region with float(result) or np.asarray"))
    return out


register(Rule(
    name="bench-real-fetch",
    doc="timed fori programs must end in a real host fetch",
    # r13: the harness itself (engine/probes.py) and the profile CLI are
    # in scope — the ONE place the discipline lives must machine-check too
    targets=("bench.py", "scripts/*.py", "dryad_tpu/engine/probes.py",
             "dryad_tpu/__main__.py"),
    check=_check_bench_fetch,
))


# ---------------------------------------------------------------------------
# dead-perturbation

_INT_CASTS = {"int8", "int16", "int32", "int64",
              "uint8", "uint16", "uint32", "uint64"}


def _small_float_const(node: ast.AST) -> bool:
    return (isinstance(node, ast.Constant)
            and isinstance(node.value, float)
            and abs(node.value) < 1.0 and node.value != 0.0)


def _is_small_perturb(binop: ast.AST) -> bool:
    return (isinstance(binop, ast.BinOp)
            and isinstance(binop.op, (ast.Add, ast.Sub))
            and (_small_float_const(binop.left)
                 or _small_float_const(binop.right)))


def _check_dead_perturbation(path, src, tree):
    out = []
    for call in _calls(tree):
        # (x + 0.001).astype(int32-ish)
        if isinstance(call.func, ast.Attribute) and call.func.attr == "astype":
            if _is_small_perturb(call.func.value) and call.args:
                dt = dotted(call.args[0]) or (
                    call.args[0].value if isinstance(call.args[0], ast.Constant)
                    else "")
                if any(i in str(dt) for i in _INT_CASTS):
                    out.append(Violation(
                        "dead-perturbation", path, call.lineno,
                        "fractional perturbation rounded away by an integer "
                        "astype — the input is DEAD and XLA hoists the "
                        "stage out of the timed loop (CLAUDE.md r5b); "
                        "advance the carried scalar by whole units"))
        # jnp.int32(x + 0.001)
        name = dotted(call.func)
        leaf = name.rsplit(".", 1)[-1] if name else ""
        if leaf in _INT_CASTS and call.args and _is_small_perturb(call.args[0]):
            out.append(Violation(
                "dead-perturbation", path, call.lineno,
                "fractional perturbation consumed only through an integer "
                "cast — dead input, the timed stage hoists (CLAUDE.md r5b); "
                "advance by whole units instead"))
    return out


register(Rule(
    name="dead-perturbation",
    doc="perturbations must survive integer rounding to reach the stage",
    # engine/** already covers engine/probes.py; the profile CLI rides too
    targets=("bench.py", "scripts/*.py", "dryad_tpu/engine/**",
             "dryad_tpu/__main__.py"),
    check=_check_dead_perturbation,
))


# ---------------------------------------------------------------------------
# unharnessed-timed-fori (r13)
#
# The timed-fori discipline lives in EXACTLY one place now —
# engine/probes.timed_fori, which adds the runtime liveness proof (two
# perturbation seeds must fetch differing accumulators, so a hoisted or
# rounded-away stage raises instead of measuring 2x fast).  A bench or
# profile script that times a hand-rolled lax.fori_loop (>= 1
# perf_counter + a fori_loop call in one function) has forked the
# discipline again and bypassed the proof.  The archived r3-r5
# ``exp_*`` one-shot experiment records predate the harness and are kept
# verbatim for provenance, so the rule scopes to the LIVING measurement
# surfaces: bench.py and the maintained profile_*/bench_*/smoke_*
# scripts.

def _check_unharnessed_fori(path, src, tree):
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        has_fori = any((dotted(c.func) or "").endswith("fori_loop")
                       for c in _calls(node))
        times = any((dotted(c.func) or "").endswith("perf_counter")
                    for c in _calls(node))
        if has_fori and times:
            out.append(Violation(
                "unharnessed-timed-fori", path, node.lineno,
                f"{node.name}() times a hand-rolled lax.fori_loop — the "
                "timed-fori discipline lives in engine/probes.timed_fori "
                "(runtime liveness proof included); route the measurement "
                "through the harness instead of re-copying it"))
    return out


register(Rule(
    name="unharnessed-timed-fori",
    doc="bench/profile scripts time fori programs only through "
        "engine/probes.timed_fori (the liveness-proven harness)",
    targets=("bench.py", "scripts/profile_*.py", "scripts/bench_*.py",
             "scripts/smoke_*.py"),
    check=_check_unharnessed_fori,
))


# ---------------------------------------------------------------------------
# introspect-compile-only (r12)
#
# Compiled-program introspection (lowered cost_analysis, AOT compile +
# memory_analysis) is measured work: a lower() re-traces the program and
# an AOT compile() pays a FULL backend compile (verified on this jax: AOT
# does not share the jit executable cache).  Those calls are legal ONLY
# inside engine/introspect.py — the whitelisted compile-boundary module,
# which memoizes per program key — and NEVER inside a loop body or a
# function traced by fori_loop/scan (where they would become a
# per-iteration host sync, the exact class CLAUDE.md's never-fetch rule
# bans).  introspect.capture() itself is memoized and loop-safe on the
# HOST side, but must not appear in a traced body either.

_INTROSPECT_PATH = "dryad_tpu/engine/introspect.py"
_INTROSPECT_ATTRS = {"cost_analysis", "memory_analysis"}


def _is_aot_compile(call: ast.Call) -> bool:
    """``<expr>.compile()`` with no arguments — the AOT form; re.compile
    and friends always take the pattern/source argument."""
    return (isinstance(call.func, ast.Attribute)
            and call.func.attr == "compile"
            and not call.args and not call.keywords)


def _traced_body_fns(tree: ast.AST) -> list:
    """Function nodes passed to lax loop combinators — their bodies are
    TRACED per loop trip, so host-side introspection inside them is a
    per-iteration sync (or a trace error) by construction."""
    names: set[str] = set()
    fns: list = []
    for call in _calls(tree):
        nm = dotted(call.func) or ""
        if nm.rsplit(".", 1)[-1] in ("fori_loop", "scan", "while_loop"):
            for arg in call.args:
                if isinstance(arg, ast.Lambda):
                    fns.append(arg)
                elif isinstance(arg, ast.Name):
                    names.add(arg.id)
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                and node.name in names:
            fns.append(node)
    return fns


def _check_introspect_sites(path, src, tree):
    out = []
    in_introspect = path == _INTROSPECT_PATH
    if not in_introspect:
        for node in ast.walk(tree):
            if isinstance(node, ast.Attribute) \
                    and node.attr in _INTROSPECT_ATTRS:
                out.append(Violation(
                    "introspect-compile-only", path, node.lineno,
                    f".{node.attr} outside engine/introspect.py — compiled-"
                    "program introspection re-traces (and for memory, "
                    "recompiles); only the memoized compile-boundary "
                    "module may pay that"))
            if isinstance(node, ast.Call) and _is_aot_compile(node):
                out.append(Violation(
                    "introspect-compile-only", path, node.lineno,
                    "zero-arg .compile() outside engine/introspect.py — "
                    "AOT compile does NOT share the jit executable cache "
                    "(measured, r12): this pays a full second backend "
                    "compile; route introspection through "
                    "introspect.capture"))
    # traced fori/scan bodies may never introspect, ANYWHERE (and inside
    # introspect.py itself the expensive calls stay out of host loops too)
    hot_regions: list = list(_traced_body_fns(tree))
    if in_introspect:
        hot_regions += [n for n in ast.walk(tree)
                        if isinstance(n, (ast.For, ast.While))]
    for region in hot_regions:
        for call in _calls(region):
            nm = dotted(call.func) or ""
            leaf = nm.rsplit(".", 1)[-1]
            bad = (leaf in _INTROSPECT_ATTRS or _is_aot_compile(call)
                   or nm.endswith("introspect.capture"))
            if bad:
                out.append(Violation(
                    "introspect-compile-only", path, call.lineno,
                    f"{nm or leaf}(...) inside a loop/traced body — the "
                    "tripwire must never become a per-iteration host "
                    "sync; introspect at the compile boundary only"))
    return out


register(Rule(
    name="introspect-compile-only",
    doc="program introspection lives in engine/introspect.py, never in "
        "loops or traced bodies",
    targets=("dryad_tpu/engine/**", "dryad_tpu/serve/**",
             "dryad_tpu/resilience/**", "dryad_tpu/obs/**"),
    check=_check_introspect_sites,
))
