"""A statistic, in ms, of the time to first token of every request due in the
window, timed from when it was due (the benchmark's own stamps): the
percentile ``q``, or the mean where the metric's file gives none."""

import numpy as np


def read(run, q=None):
    ttft = run.get("ttft_ms")
    if not ttft:
        return None
    ttft = np.asarray(ttft, np.float64)
    return float(np.mean(ttft) if q is None else np.percentile(ttft, q))
