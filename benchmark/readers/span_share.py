"""Share of the window, in %, spent inside host spans of the given names."""


def read(run, spans):
    t0, t1 = run["window"]
    found = [s for name in spans for s in run["spans"].within(t0, t1, name)]
    if not found:
        return None
    return 100.0 * sum(min(b, t1) - a for _, a, b, _ in found) / (t1 - t0)
