"""Backend compiles a checkpoint, over the whole run: the program's counter
``dryad_prog_backend_compiles_total{program="train.materialize"}`` over its
count of ``train.fetch.checkpoint`` spans."""


def read(facts):
    from dryad_tpu.obs.registry import default_registry

    counters = default_registry().snapshot()["counters"]
    compiles = [v for lbl, v in counters.get("dryad_prog_backend_compiles_total", {}).items()
                if 'program="train.materialize"' in str(lbl)]
    spans = [v for lbl, v in counters.get("dryad_span_count_total", {}).items()
             if str(lbl).rstrip('"}').endswith("train.fetch.checkpoint")]
    if not compiles or not sum(spans):
        return None
    return sum(compiles) / sum(spans)
