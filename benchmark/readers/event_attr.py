"""A percentile ``q`` of one attribute over the program's events of one name
from the window's opening on (``tracer.event(name, **attrs)``; the recorder
keeps each with its time, so the warm-up's events are left out, and what the
drain after the window answers is kept: those requests were due in it and
count in the end-to-end metrics too).  The first call of a run prints one
line with the percentiles of every ``*_ms`` attribute of the event and of
their sum over each request.  Returns nothing where the program sends no such
event."""

import numpy as np


def read(run, event, attr, q):
    t0, _ = run["window"]
    found = [a for _, _, _, a in run["spans"].within(t0, float("inf"), event)]
    values = [a[attr] for a in found if a.get(attr) is not None]
    if not values:
        return None
    if event not in run.setdefault("_event_lines", set()):
        run["_event_lines"].add(event)
        ms = sorted(k for k in found[0] if k.endswith("_ms"))
        cols = {k: [a[k] for a in found] for k in ms}
        cols["sum"] = [sum(a[k] for k in ms) for a in found]
        print(f"{event} over {len(found)} requests, ms p50 / p95: " + "; ".join(
            f"{k} {np.percentile(v, 50):.1f} / {np.percentile(v, 95):.1f}"
            for k, v in cols.items()), flush=True)
    return float(np.percentile(np.asarray(values, np.float64), q))
