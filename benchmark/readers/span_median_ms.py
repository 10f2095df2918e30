"""Median duration, in ms, of the host spans of one name in the window."""

import statistics


def read(run, span):
    t0, t1 = run["window"]
    found = run["spans"].within(t0, t1, span)
    if not found:
        return None
    return 1000.0 * statistics.median(b - a for _, a, b, _ in found)
