"""One counter of the program's process-wide, always-on registry
(``obs.process_metrics()``) over another: a share counted where the
work happens.  A numerator never incremented reads 0; ``None`` where the
program has no such registry or the denominator is missing or 0, so the
metric is left out."""


def read(ctx, params):
    try:
        from distributed_llm_scheduler_tpu.obs import process_metrics
    except ImportError:
        return None
    counters = process_metrics().snapshot()["counters"]
    den = counters.get(params["den"], {}).get("value")
    if not den:
        return None
    return counters.get(params["num"], {}).get("value", 0) / den
