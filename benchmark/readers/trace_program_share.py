"""Share of the traced window, in %, in which compiled programs whose names
match ``match`` ran on the device (the trace's line of whole programs; mean
over the devices).  Returns nothing where no such program ran."""

import re


def read(run, match):
    tr = run.get("trace")
    if tr is None or run["platform"] != "tpu":
        return None
    rx = re.compile(match)
    seconds = sum(s for n, s in tr["modules"] if rx.search(n))
    if seconds <= 0:
        return None
    return 100.0 * seconds / tr["window_s"]
