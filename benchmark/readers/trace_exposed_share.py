"""Share of the traced window, in %, in which an operation matching ``match``
ran on the device and no other operation did (mean over the devices)."""

from benchmark import trace_reduce


def read(run, match):
    tr = run.get("trace")
    if tr is None or run["platform"] != "tpu":
        return None
    per = [trace_reduce.exposed_seconds(ev, match) for ev in tr["events"].values()]
    return 100.0 * (sum(per) / len(per)) / tr["window_s"]
