"""The training program names its own stages (PR 25; PR 27 the eighth,
``dryad.select``, the batched leaf-wise grower's best-first replay): eight
``dryad.*`` scopes in the lowered training programs, three named Pallas kernels, host
spans that double as profiler annotations, and a compile family of its own
for what a checkpoint compiles.  Tracing only: nothing here times anything."""

import os
import re

import jax
import numpy as np
import pytest

import dryad_tpu as dryad
from dryad_tpu.datasets import higgs_like
from dryad_tpu.engine import introspect, leafperm, pallas_hist, train
from dryad_tpu.obs import Registry, set_default_registry
from dryad_tpu.obs import spans as S

EIGHT = {"dryad.grad", "dryad.hist", "dryad.route", "dryad.layout",
         "dryad.split_scan", "dryad.select", "dryad.score", "dryad.eval"}
# what a program of the level-wise grower carries: it selects nothing
SEVEN = EIGHT - {"dryad.select"}
# the ninth (PR 34): the cross-shard exchange, in a program that runs on a mesh
NINE = EIGHT | {"dryad.reduce"}
BASE = dict(objective="binary", num_trees=4, num_leaves=7, max_depth=3,
            max_bins=32, seed=3, min_data_in_leaf=5, growth="depthwise")


@pytest.fixture(scope="module")
def sets():
    X, y = higgs_like(2400, seed=21)
    ds = dryad.Dataset(X[:2000], y[:2000], max_bins=32)
    return ds, ds.bind(X[2000:], y[2000:])


@pytest.fixture()
def fresh_registry():
    reg = Registry()
    old = set_default_registry(reg)
    yield reg
    set_default_registry(old)


class _Lowered(Exception):
    pass


def _lowered_text(monkeypatch, sets, program, params, mesh=None):
    """The MLIR text, locations included, of the first ``program`` that a
    tiny job with a valid set would dispatch; the job is stopped there."""
    monkeypatch.setenv("DRYAD_CHUNK", "1" if program == "_chunk_jit" else "0")
    jitted = getattr(train, program)

    def lower_and_stop(*args, **kw):
        raise _Lowered(jitted.lower(*args, **kw).as_text(debug_info=True))

    monkeypatch.setattr(train, program, lower_and_stop)
    ds, vds = sets
    with pytest.raises(_Lowered) as caught:
        dryad.train(params, ds, valid_sets=[vds], backend="tpu", mesh=mesh)
    return str(caught.value)


ALL_BUT_LAYOUT = SEVEN - {"dryad.layout"}
IN_A_STEP = {"dryad.grad", "dryad.hist", "dryad.route", "dryad.split_scan",
             "dryad.score"}


@pytest.mark.parametrize("program,extra,expected", [
    ("_chunk_jit", dict(hist_backend="pallas"), SEVEN),                 # wired layout on
    ("_chunk_jit", dict(hist_backend="pallas", deep_layout="legacy"), ALL_BUT_LAYOUT),
    ("_chunk_jit", dict(growth="leafwise", hist_backend="pallas"), EIGHT),   # leafwise_fast, wired
    # max_depth=-1: the unbounded_depth="auto" cap, leafwise_fast on the plan path
    ("_chunk_jit", dict(growth="leafwise", max_depth=-1), EIGHT - {"dryad.layout"}),
    ("_step_jit", dict(hist_backend="pallas"), IN_A_STEP | {"dryad.layout"}),
], ids=["chunk-wired", "chunk-legacy", "chunk-leafwise-batched",
        "chunk-leafwise-unbounded", "step-wired"])
def test_lowered_program_names_its_stages(monkeypatch, sets, program, extra, expected):
    text = _lowered_text(monkeypatch, sets, program, dict(BASE, **extra))
    found = set(re.findall(r"dryad\.[A-Za-z_0-9]+", text))
    assert found == expected
    assert found <= EIGHT


@pytest.mark.parametrize("extra,expected", [
    (dict(growth="leafwise", max_depth=-1), NINE - {"dryad.layout"}),       # psum, fused arm
    (dict(hist_reduce="feature"), SEVEN - {"dryad.layout"} | {"dryad.reduce"}),
], ids=["mesh-leafwise-fused", "mesh-levelwise-feature"])
def test_a_sharded_program_names_the_exchange(monkeypatch, sets, extra, expected):
    """On a mesh the histogram all-reduce (either arm, and the feature arm's
    all-gather of best splits) is the innermost scope of its operations."""
    from dryad_tpu.engine.distributed import make_mesh

    text = _lowered_text(monkeypatch, sets, "_chunk_jit", dict(BASE, **extra),
                         mesh=make_mesh(jax.devices()[:4]))
    assert set(re.findall(r"dryad\.[A-Za-z_0-9]+", text)) == expected
    # the collectives themselves sit directly in the scope, and nowhere else
    inside = set(re.findall(r"dryad\.reduce/(\w+)", text))
    wanted = {"reduce_scatter", "all_gather"} if "hist_reduce" in extra else {"psum"}
    assert all(any(op.startswith(w) for op in inside) for w in wanted), inside
    outside = re.findall(r'"(?:(?!dryad\.reduce)[^"])*/(?:psum|reduce_scatter|all_gather)\w*"',
                         text)
    assert not [name for name in outside if "dryad.hist" in name or "dryad.split_scan" in name]


def test_engine_sources_name_the_nine_and_no_tenth():
    """What the acceptance grep reads: every ``named_scope`` under
    ``dryad_tpu/engine`` takes one of the nine names, and each is used."""
    root = os.path.dirname(os.path.abspath(train.__file__))
    used = set()
    for name in os.listdir(root):
        if name.endswith(".py"):
            with open(os.path.join(root, name), encoding="utf-8") as f:
                used |= set(re.findall(r'named_scope\("([^"]*)"\)', f.read()))
    assert used == NINE


def _pallas_names(fn, *args, **kw):
    names = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                names.append(eqn.params["name"])
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        walk(inner)

    walk(jax.make_jaxpr(fn)(*args, **kw).jaxpr)
    return names


def test_the_three_kernels_carry_their_names():
    """The benchmark finds kernel time by these names
    (``benchmark/layer_metrics/hist_time_share.py``, ``perm_time_share.py``);
    they come from ``name=``, not from what a Python function is called."""
    from benchmark.layer_metrics import hist_time_share
    sds = jax.ShapeDtypeStruct
    T = pallas_hist._TILE_ROWS
    Xt = sds((1, 2, 8, T), np.uint8)
    assert _pallas_names(
        lambda x, w, a, b, c: pallas_hist._hist_tiles(
            x, w, a, b, c, num_cols=1, total_bins=32, num_features=8, platform="cpu"),
        Xt, sds((2, 8, T), jax.numpy.bfloat16), sds((2,), np.int32),
        sds((2,), np.int32), sds((2,), np.int32)) == ["_hist_tiles"]
    # the wired levels' in-place entry (PR 31) is a histogram kernel too:
    # hist_time_share, hist_roofline and hist_glue_device_ms match the
    # substring, so its name has to hold "_hist_tiles"
    rec_names = _pallas_names(
        lambda r, s, a, b, c: pallas_hist._hist_tiles_rec(
            r, s, a, b, c, num_cols=1, total_bins=32, num_features=8,
            bin_dtype=np.dtype(np.uint8), platform="cpu"),
        sds((3 * T, leafperm._REC_WB), np.uint8), sds((2,), np.int32),
        sds((2,), np.int32), sds((2,), np.int32), sds((2,), np.int32))
    assert rec_names == ["_hist_tiles_rec"]
    assert any(k in rec_names[0] for k in hist_time_share.KERNELS["hist"])
    assert "_hist_tiles" in rec_names[0]
    assert _pallas_names(
        lambda x, g, h, s: pallas_hist.build_hist_nat(
            x, g, h, s, total_bins=32, num_features=8, platform="cpu"),
        Xt, sds((2 * T,), np.float32), sds((2 * T,), np.float32),
        sds((2 * T,), np.int32)) == ["build_hist_nat"]
    Tl = leafperm._TILE_ROWS
    # the layout's kernels all carry "permute_records" in their names:
    # perm_time_share matches that substring, so the counting pass counts
    # as the layout's kernel time and not as its XLA bookkeeping
    assert _pallas_names(
        lambda r, t, rr: leafperm.move_level(r, t, rr, bin_dtype=np.uint8,
                                             platform="cpu"),
        sds((2 * Tl, leafperm._REC_WB), np.uint8), sds((2,), np.int32),
        sds((4, 2), np.uint32)) == ["permute_records_count", "permute_records"]


# the goldens, from before the scopes, of the arms that run no Pallas kernel:
# scopes are metadata, so these two programs digest as they did then.
# ``renewal_iteration`` grows with leafwise_fast, which since PR 27 also
# returns its two statistics (expanded_splits, selected_splits): two
# equations, so 4de6ad398110 became da449f98fd88; ``dryad.select`` itself
# moved nothing.  PR 35 rewrote the equations inside ``dryad.score`` (one
# record gather a row where two 1-D look-ups stood, train._row_records), so
# da449f98fd88 became 7707dd180638 and 57de8b33dee0 became 2086de9ea573; no
# scope was added, moved or renamed
SEED_DIGESTS = {"renewal_iteration": "7707dd180638",
                "multiclass_shared_roots": "2086de9ea573"}


@pytest.mark.parametrize("arm", sorted(SEED_DIGESTS))
def test_scopes_add_no_equation(arm):
    from dryad_tpu.analysis import jaxpr_audit
    from dryad_tpu.analysis.digests import iter_sub_jaxprs, load_goldens

    fn, args, _, _ = jaxpr_audit.ARMS[arm].build()
    closed = jax.make_jaxpr(fn)(*args)
    stacks = set()

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            stacks.add(str(eqn.source_info.name_stack))
            for _, sub, _ in iter_sub_jaxprs(eqn):
                walk(sub)

    walk(closed.jaxpr)
    scoped = {s for stack in stacks for s in re.findall(r"dryad\.[a-z_]+", stack)}
    assert {"dryad.grad", "dryad.hist", "dryad.split_scan", "dryad.score"} <= scoped <= NINE
    digest = jaxpr_audit.canonical_digest(closed)
    assert digest.startswith(SEED_DIGESTS[arm])
    assert load_goldens()["arms"][arm]["digest"] == digest


# ---- host spans on a second clock ---------------------------------------------

class _Recorder:
    def __init__(self):
        self.log = []

    def __call__(self, path):
        rec = self

        class _Cm:
            def __enter__(self):
                rec.log.append(("enter", path))

            def __exit__(self, *exc):
                rec.log.append(("exit", path))

        return _Cm()


@pytest.fixture()
def annotator():
    rec = _Recorder()
    old = S._ANNOTATOR
    S.set_annotator(rec)
    yield rec
    S.set_annotator(old)


def test_annotator_brackets_each_span_with_its_path(annotator):
    reg = Registry()
    with S.span("tree", reg):
        with S.span("level", reg):
            pass
    assert annotator.log == [("enter", "tree"), ("enter", "tree/level"),
                             ("exit", "tree/level"), ("exit", "tree")]
    by_hand = S.annotation("loop.body", reg)
    by_hand.close()
    by_hand.close()                      # closing twice leaves once
    assert annotator.log[4:] == [("enter", "loop.body"), ("exit", "loop.body")]


def test_annotator_never_runs_when_the_registry_is_disabled(annotator):
    reg = Registry()
    reg.disable()
    with S.span("tree", reg):
        pass
    assert S.annotation("loop.body", reg) is None
    assert annotator.log == []


@pytest.mark.parametrize("broken", ["factory", "enter", "exit"])
def test_an_annotator_that_raises_does_not_reach_the_caller(broken):
    class _Cm:
        def __enter__(self):
            if broken == "enter":
                raise RuntimeError("enter")

        def __exit__(self, *exc):
            if broken == "exit":
                raise RuntimeError("exit")

    def factory(path):
        if broken == "factory":
            raise RuntimeError("factory")
        return _Cm()

    old = S._ANNOTATOR
    S.set_annotator(factory)
    try:
        reg = Registry()
        with S.span("tree", reg):
            pass
        S.annotation("loop.body", reg).close()
        assert S.snapshot(reg)["tree"]["count"] == 1
    finally:
        S.set_annotator(old)


def test_the_engine_installs_the_profilers_annotation():
    import dryad_tpu.engine  # noqa: F401

    assert S._ANNOTATOR is jax.profiler.TraceAnnotation


@pytest.mark.parametrize("chunked", ["1", "0"], ids=["chunked", "per-iteration"])
def test_checkpoint_children_and_loop_spans(monkeypatch, tmp_path, sets, fresh_registry,
                                            annotator, chunked):
    """``train.fetch.checkpoint`` splits into ``/materialize`` and ``/save``
    (no more than the parent together), the callbacks get a span, the loop's
    own interval keeps its series' name, and every one of them reaches the
    annotator, so a jax profile shows them."""
    monkeypatch.setenv("DRYAD_CHUNK", chunked)
    ds, vds = sets
    seen = []
    dryad.train(dict(BASE, num_trees=3), ds, valid_sets=[vds], backend="tpu",
                callbacks=[lambda i, info: seen.append(i)],
                checkpoint_dir=str(tmp_path), checkpoint_every=1)
    snap = S.snapshot(fresh_registry)
    parent = snap["train.fetch.checkpoint"]
    mat = snap["train.fetch.checkpoint/materialize"]
    save = snap["train.fetch.checkpoint/save"]
    assert parent["count"] == mat["count"] == save["count"] == 3
    assert mat["total_s"] > 0 and save["total_s"] > 0
    assert mat["total_s"] + save["total_s"] <= parent["total_s"] + 1e-6
    assert seen == [0, 1, 2] and snap["train.callbacks"]["count"] >= 1
    loop = "train.chunk_dispatch" if chunked == "1" else "train.iteration"
    assert snap[loop]["count"] >= 1 and snap[loop]["total_s"] > 0
    entered = [p for what, p in annotator.log if what == "enter"]
    left = [p for what, p in annotator.log if what == "exit"]
    assert sorted(entered) == sorted(left)
    for path in (loop, "train.callbacks", "train.fetch.checkpoint",
                 "train.fetch.checkpoint/materialize", "train.fetch.checkpoint/save"):
        assert path in entered, path


def test_what_a_checkpoint_compiles_counts_under_its_own_family(monkeypatch, tmp_path, sets,
                                                                fresh_registry):
    """The slices ``_materialize`` takes compile new programs at every
    checkpoint (``T`` grows); they count under ``train.materialize`` and the
    boundary that was active before gets its label back: the second chunk is
    dispatched under ``train.chunk`` again.  The job's own leave clears the
    label (``tests/test_setup_spans.py``)."""
    monkeypatch.setenv("DRYAD_PROG", "1")
    monkeypatch.setenv("DRYAD_CHUNK", "1")
    introspect.reset_seen()
    jax.clear_caches()
    ds, vds = sets
    at_dispatch = []
    dryad.train(dict(BASE, num_trees=2), ds, valid_sets=[vds], backend="tpu",
                callbacks=[lambda i, info: None],
                chunk_hook=lambda site, it: site == "dispatch"
                and at_dispatch.append(introspect._tls.program),
                checkpoint_dir=str(tmp_path), checkpoint_every=1)
    compiles = fresh_registry.snapshot()["counters"]["dryad_prog_backend_compiles_total"]
    by_family = {lbl: n for lbl, n in compiles.items()}
    assert by_family.get('program="train.materialize"', 0) >= 1
    assert by_family.get('program="train.chunk"', 0) >= 1
    assert at_dispatch == ["train.setup", "train.chunk"]
    introspect.attribute("train.chunk")
    with introspect.attributed("train.materialize"):
        assert introspect._tls.program == "train.materialize"
    assert introspect._tls.program == "train.chunk"
    introspect.attribute(None)


def test_compiled_text_gives_the_scope_of_each_instruction():
    """Exact where the compiler kept the name; where it made the instruction
    itself (no ``op_name``, or one without the stack) the scope is a
    neighbour's and says so: the largest operand's, else the reader's, else
    the loop's that runs the computation."""
    text = '''HloModule jit__chunk_jit, is_scheduled=true

%fused_computation (p: f32[8]) -> f32[8] {
  ROOT %mul.4 = f32[8]{0} multiply(%p, %p), metadata={op_name="jit(_chunk_jit)/while/body/dryad.route/dryad.split_scan/mul" stack_frame_id=3}
}

%sunk_body (arg: (s32[], f32[16,8])) -> (s32[], f32[16,8]) {
  %arg = (s32[]{:T(128)}, f32[16,8]{1,0:T(8,128)}) parameter(0)
  %one = s32[]{:T(128)} constant(1), metadata={op_name="jit(_chunk_jit)/dryad.grad/jit(_take)/add"}
  %gte.0 = s32[]{:T(128)} get-tuple-element(%arg), index=0
  %gte.1 = f32[16,8]{1,0:T(8,128)} get-tuple-element(%arg), index=1
  %add.9 = s32[]{:T(128)} add(%gte.0, %one)
  %dus.1 = f32[16,8]{1,0:T(8,128)S(1)} fusion(%gte.1, %gte.0), kind=kLoop, calls=%fused_computation
  ROOT %tuple.1 = (s32[]{:T(128)}, f32[16,8]{1,0:T(8,128)}) tuple(%add.9, %dus.1)
}

ENTRY %main (a: f32[8], b: f32[16,8]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %b = f32[16,8]{1,0} parameter(1)
  %relayout.1 = f32[8]{0:T(1024)} copy(%a)
  %sin.2 = f32[8]{0} sine(%relayout.1), metadata={op_name="jit(_chunk_jit)/while/body/closed_call/dryad.route/sin"}
  %copy.1 = f32[8]{0} copy(%sin.2)
  %c = f32[] constant(2), metadata={op_name="jit(_chunk_jit)/while/body"}
  %cumsum.3 = f32[8]{0} reduce-window(%copy.1, %c), to_apply=%fused_computation, metadata={op_name="reduce_window_sum"}
  %zero = s32[] constant(0)
  %tuple.2 = (s32[], f32[16,8]{1,0}) tuple(%zero, %b)
  %while.7 = (s32[]{:T(128)}, f32[16,8]{1,0:T(8,128)}) while(%tuple.2), condition=%cond, body=%sunk_body
  %gte.5 = f32[16,8]{1,0:T(8,128)} get-tuple-element(%while.7), index=1
  %sub.6 = f32[16,8]{1,0} subtract(%gte.5, %gte.5), metadata={op_name="jit(_chunk_jit)/dryad.hist/sub"}
  ROOT %fusion.12 = f32[8]{0} fusion(%copy.1), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(_chunk_jit)/while/body/dryad.route/dryad.split_scan/mul"}
}
'''
    introspect._note_scopes(text)
    got = introspect.scope_maps()["jit__chunk_jit"]
    exact = {k: v for k, v in got.items() if not v.startswith(introspect.INFERRED)}
    assert exact == {"mul.4": "dryad.split_scan", "sin.2": "dryad.route", "one": "dryad.grad",
                     "sub.6": "dryad.hist", "fusion.12": "dryad.split_scan"}
    near = {k: v for k, v in got.items() if v.startswith(introspect.INFERRED)}
    assert near.pop("copy.1") == "~dryad.route"          # its operand's
    assert near.pop("cumsum.3") == "~dryad.route"        # the name it had is gone
    assert near.pop("relayout.1") == "~dryad.route"      # its reader's
    assert near.pop("a") == near.pop("c") == "~dryad.route"
    assert near.pop("gte.5") == near.pop("while.7") == "~dryad.hist"
    # the loop the compiler made takes the scope of the while that runs it;
    # its counter takes the shared constant's and gives it to nothing larger
    assert near.pop("dus.1") == near.pop("gte.1") == near.pop("arg") == "~dryad.hist"
    assert near.pop("add.9") == near.pop("gte.0") == "~dryad.grad"
    assert near == {}
    assert "tuple.1" not in got and "tuple.2" not in got and "b" not in got
