"""The whole step's share of the chips' peak, in %: the operations the model
needs for every token the window processed (``benchmark/flops.py``, "model"
convention) over the window's seconds, the chips and the chip's bf16 peak.
A device metric: nothing is returned off a TPU."""

from benchmark.peaks import peaks_of


def read(run):
    if run["platform"] != "tpu" or not run.get("model_flops"):
        return None
    t0, t1 = run["window"]
    peak = peaks_of(run["device_kind"])["flops_bf16"] * run["chips"]
    return 100.0 * run["model_flops"] / ((t1 - t0) * peak)
