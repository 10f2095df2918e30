"""The one general generator of serving traffic.

A mix is data (the ``mix`` object of ``benchmark/workloads/<cell>.json``):

  population_seed  the seed of the *population*: the set of request sizes
                   and, in an open loop, of inter-arrival gaps.  It is fixed
                   in the file, so every run of the cell offers the same set;
  prompt, output   length distributions: {"dist": "lognormal", "median",
                   "sigma", "min", "max"} or {"dist": "uniform", "min", "max"};
  greedy_share     share of requests decoded greedily (top_k 1): the ones the
                   ``correct`` comparison can judge; the rest sample (top_k 50);
  arrivals         open loop: {"process": "gamma", "cv", "rate_per_s"} — gamma
                   inter-arrival times with that coefficient of variation
                   (cv 1 is Poisson, above 1 bursty) at that mean rate;
  clients, population  closed loop: the number of clients and of requests in
                   the population they draw from in order.

The sizes, the gaps and their order are the population's: a tail under bursty
arrivals is set by where the bursts meet the long requests, so every run of a
cell offers the same requests at the same instants.  ``--seed`` decides the
prompts' tokens (and, in the kinds, the weights), never a size or an arrival.
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np

from benchmark.traffic.tokens import zipf_tokens


@dataclasses.dataclass
class Spec:
    """One request to send."""

    index: int
    prompt_len: int
    max_new: int
    greedy: bool
    due_s: float | None  # open loop: offset from the window's start
    token_seed: int

    def prompt(self, vocab: int) -> np.ndarray:
        rng = np.random.default_rng([self.token_seed, self.index])
        return zipf_tokens(rng, self.prompt_len, vocab).astype(np.int32)


def draw_lengths(rng: np.random.Generator, d: dict, n: int) -> np.ndarray:
    if d["dist"] == "lognormal":
        x = rng.lognormal(math.log(d["median"]), d["sigma"], size=n)
    elif d["dist"] == "uniform":
        x = rng.uniform(d["min"], d["max"] + 1, size=n)
    else:
        raise ValueError(f"unknown length distribution {d['dist']!r}")
    return np.clip(np.floor(x), d["min"], d["max"]).astype(np.int64)


def arrival_gaps(rng: np.random.Generator, a: dict, seconds: float) -> np.ndarray:
    """Inter-arrival gaps whose running sum stays inside ``seconds``."""
    if a["process"] != "gamma":
        raise ValueError(f"unknown arrival process {a['process']!r}")
    shape = 1.0 / (a["cv"] ** 2)
    scale = 1.0 / (a["rate_per_s"] * shape)
    n = int(a["rate_per_s"] * seconds * 2) + 16
    gaps = rng.gamma(shape, scale, size=n)
    keep = int(np.searchsorted(np.cumsum(gaps), seconds))
    return gaps[:keep]


def _population(mix: dict, n: int):
    rng = np.random.default_rng([int(mix["population_seed"]), 1])
    prompts = draw_lengths(rng, mix["prompt"], n)
    outputs = draw_lengths(rng, mix["output"], n)
    n_greedy = int(round(mix.get("greedy_share", 0.0) * n))
    greedy = np.zeros(n, bool)
    greedy[rng.permutation(n)[:n_greedy]] = True
    return prompts, outputs, greedy


def open_loop(mix: dict, seed: int, seconds: float) -> list[Spec]:
    """Requests due inside [0, seconds), in due order."""
    gaps = arrival_gaps(
        np.random.default_rng([int(mix["population_seed"]), 0]),
        mix["arrivals"], seconds)
    n = len(gaps)
    prompts, outputs, greedy = _population(mix, n)
    due = np.cumsum(gaps)
    return [Spec(i, int(prompts[i]), int(outputs[i]), bool(greedy[i]),
                 float(due[i]), int(seed)) for i in range(n)]


def closed_loop(mix: dict, seed: int) -> list[Spec]:
    """The population the clients draw from, in order."""
    n = int(mix["population"])
    prompts, outputs, greedy = _population(mix, n)
    return [Spec(i, int(prompts[i]), int(outputs[i]), bool(greedy[i]),
                 None, int(seed)) for i in range(n)]
