"""The one traffic generator: it reads a mix file (`traffic/<mix>.json`)
and turns it, with the run's seed, into the waves a cell serves.

A serve mix is closed-loop waves of offline batches: every request of a
wave arrives at engine cycle 0, and the next wave starts when the last
request of the one before has finished. A wave's W lengths are the
distribution's quantiles at i/(W-1), i = 0..W-1, ends included. Prompt
and output quantiles are paired, and the pairs ordered, by fixed
permutations that no seed changes: every wave of every run serves the
same lengths in the same order, so the engine runs the same cycles and
compiles the same shapes. (An order drawn from the seed changed which
requests share a cycle, and with it the work: on the chip, runs of
different seeds then spread ten times wider than two runs of one seed.)
The seed draws what a request holds: the weights, and each wave's trace
seed, from which the engine draws the prompts' tokens.

Distributions ("dist"): "uniform" over the integers min..max, and
"lognormal" with its "median" and "sigma", clipped to min..max.
"""
from __future__ import annotations

import json
import statistics

import numpy as np

#: the fixed pairing and order of the lengths (not the run seed)
PAIRING_SEED = 0


def load_mix(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def quantiles(spec: dict, n: int) -> np.ndarray:
    """The n lengths at quantiles i/(n-1) of `spec`, as integers."""
    q = np.linspace(0.0, 1.0, n) if n > 1 else np.array([0.5])
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "uniform":
        vals = lo + q * (hi - lo)
    elif spec["dist"] == "lognormal":
        nd = statistics.NormalDist()
        z = np.array([nd.inv_cdf(min(max(x, 1e-12), 1 - 1e-12))
                      for x in q])
        vals = float(spec["median"]) * np.exp(float(spec["sigma"]) * z)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(vals), lo, hi).astype(np.int64)


def wave_pairs(mix: dict) -> list:
    """The (prompt_len, max_new_tokens) pairs of one wave, in order."""
    w = int(mix["wave"])
    rng = np.random.default_rng(PAIRING_SEED)
    prompts = quantiles(mix["prompt"], w)
    outputs = quantiles(mix["output"], w)[rng.permutation(w)]
    return [(int(prompts[i]), int(outputs[i])) for i in rng.permutation(w)]


def derived_seed(seed: int, *path: int) -> int:
    """A 31-bit seed drawn from the run's `seed` for the stream `path`:
    (WEIGHTS,) for the weights, (WAVES, w) for wave w's trace."""
    return int(np.random.SeedSequence([seed, *path]).generate_state(1)[0]
               >> 1)


WEIGHTS, WAVES = 0, 1


def max_seq_len(pairs) -> int:
    return max(p + n for p, n in pairs)


def sample_indices(n: int, k: int, must, seed: int) -> list:
    """`k` of range(n) drawn from `seed`, always including `must`; all of
    them when k <= 0 or k >= n."""
    if k <= 0 or k >= n:
        return list(range(n))
    rng = np.random.default_rng([seed, 7])
    rest = [i for i in rng.permutation(n).tolist() if i not in must]
    return sorted(set(must) | set(rest[:max(0, k - len(set(must)))]))
