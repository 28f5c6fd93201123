"""The benchmark's one traffic generator, driven by a mix's data file.

A mix is ``bench/traffic/<name>.json``.  Its keys:

- ``arrivals``: ``"poisson"`` (open loop at ``rate`` requests per second) or
  ``"backlog"`` (``requests`` requests all due at t=0, so the waiting queue
  never empties while the run lasts);
- ``prompt`` / ``output``: lognormal lengths, ``{"median", "sigma", "min",
  "max"}`` in tokens, clipped to ``[min, max]``;
- ``shape_seed``: the seed of the sizes and the inter-arrival gaps.  Every
  run seed gets the same sizes and gaps, each shuffled only within
  consecutive blocks of ``permute_block`` requests, so any stretch of the
  run holds the same work whatever the seed; seeds differ in order and in
  token ids;
- ``lead_in_s``: seconds of traffic before the measured window opens (the
  pools fill, the scheduler reaches its steady mix);
- ``drain_cap_s``: seconds after the window closes that the run waits for
  the window's requests to finish (0: stop when the window closes);
- ``rehearse``: overrides of the above for the small rehearsal sizes.

The lognormal lengths and the Poisson arrivals are copied from
``repro.serving.traces`` (``_lognormal_lengths``, ``poisson_arrivals``), so
that the program cannot move the yardstick.
"""

from __future__ import annotations

import json
import math
import os
from typing import Dict, List

import numpy as np

TRAFFIC_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "traffic")


def load_mix(name: str, rehearse: bool = False) -> Dict:
    """The mix; ``rehearse`` applies its small ``rehearse`` overrides."""
    with open(os.path.join(TRAFFIC_DIR, name + ".json")) as f:
        mix = json.load(f)
    small = mix.pop("rehearse", {})
    return {**mix, **small} if rehearse else mix


def lognormal_lengths(rng: np.random.Generator, n: int, median: float,
                      sigma: float, lo: int, hi: int) -> np.ndarray:
    vals = rng.lognormal(mean=np.log(median), sigma=sigma, size=n)
    return np.clip(vals, lo, hi).astype(int)


def poisson_arrivals(n: int, rate: float, rng: np.random.Generator) -> np.ndarray:
    """n arrival timestamps of a Poisson process with ``rate`` req/s."""
    if rate <= 0:
        return np.zeros(n)
    return np.cumsum(rng.exponential(1.0 / rate, size=n))


def request_count(mix: Dict, seconds: float) -> int:
    """Requests a run of a ``seconds``-long window offers."""
    if mix["arrivals"] == "backlog":
        return int(mix["requests"])
    horizon = mix["lead_in_s"] + seconds + mix["drain_cap_s"]
    return int(math.ceil(mix["rate"] * horizon * 1.25)) + 16


def _block_permutation(rng: np.random.Generator, n: int, block: int) -> np.ndarray:
    """A permutation of range(n) that moves items only within consecutive
    blocks of ``block``."""
    idx = np.arange(n)
    for lo in range(0, n, block):
        idx[lo:lo + block] = lo + rng.permutation(min(block, n - lo))
    return idx


def sizes(mix: Dict, n: int):
    """(prompt lengths, output lengths, arrival times) of ``n`` requests in
    the order of ``shape_seed``, before a run seed permutes them."""
    shape = np.random.default_rng(mix["shape_seed"])
    p = mix["prompt"]
    o = mix["output"]
    prompts = lognormal_lengths(shape, n, p["median"], p["sigma"], p["min"], p["max"])
    outputs = lognormal_lengths(shape, n, o["median"], o["sigma"], o["min"], o["max"])
    if mix["arrivals"] == "backlog":
        arrivals = np.zeros(n)
    elif mix["arrivals"] == "poisson":
        arrivals = poisson_arrivals(n, mix["rate"], shape)
    else:
        raise ValueError(f"unknown arrivals {mix['arrivals']!r}")
    return prompts, outputs, arrivals


def make_requests(mix: Dict, seed: int, vocab: int, seconds: float) -> List[Dict]:
    """The run's requests in arrival order: ``{"arrival", "prompt",
    "output_len"}``."""
    n = request_count(mix, seconds)
    prompts, outputs, arrivals = sizes(mix, n)
    run = np.random.default_rng(np.random.SeedSequence(seed))
    block = int(mix["permute_block"])
    prompts = prompts[_block_permutation(run, n, block)]
    outputs = outputs[_block_permutation(run, n, block)]
    gaps = np.diff(arrivals, prepend=0.0)[_block_permutation(run, n, block)]
    arrivals = np.cumsum(gaps)
    return [{"arrival": float(a),
             "prompt": run.integers(1, vocab, size=int(pl)).tolist(),
             "output_len": int(ol)}
            for a, pl, ol in zip(arrivals, prompts, outputs)]
