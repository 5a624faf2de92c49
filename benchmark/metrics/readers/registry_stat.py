"""One statistic of one histogram of the program's process-wide,
always-on registry (``obs.process_metrics()``), scaled: any key of the
histogram's snapshot — ``p50`` / ``p95`` / ``p99`` (from the registry's
reservoir), ``mean``, ``max``, ``count``.  ``registry_hist`` gives sums of
medians; a tail is read here, and the whole snapshot is logged beside it
(a tail says little without its count and its median).  ``None`` where
the program has no such registry or the histogram holds nothing, so the
metric is left out."""

from benchmark import harness


def read(ctx, params):
    try:
        from distributed_llm_scheduler_tpu.obs import process_metrics
    except ImportError:
        return None
    hist = process_metrics().snapshot()["histograms"].get(params["histogram"])
    if not hist or hist.get(params["stat"]) is None:
        return None
    harness.log(f"registry histogram {params['histogram']}: {hist}")
    return hist[params["stat"]] * float(params.get("scale", 1.0))
