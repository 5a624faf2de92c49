"""The sum of the medians of named histograms of the program's
process-wide, always-on registry (``obs.process_metrics()``), less the
medians of the histograms named under ``minus``, scaled: what the
program observed once per call where the work happens, read without
holding the call's report.  ``None`` where the program has no such
registry or a histogram is empty, so the metric is left out."""


def read(ctx, params):
    try:
        from distributed_llm_scheduler_tpu.obs import process_metrics
    except ImportError:
        return None
    hists = process_metrics().snapshot()["histograms"]
    total = 0.0
    for sign, names in ((1.0, params["histograms"]),
                        (-1.0, params.get("minus", ()))):
        for name in names:
            p50 = hists.get(name, {}).get("p50")
            if p50 is None:
                return None
            total += sign * p50
    return total * float(params.get("scale", 1.0))
