"""A percentile of one of the program's own latency histograms
(``ServingMetrics``), restricted to nothing: the engine is built for the run,
so its counters hold the warm-up's and the window's requests."""


def read(run, histogram, q):
    metrics = run.get("program_metrics")
    if metrics is None:
        return None
    return getattr(metrics, histogram).percentile(q)
