"""The check that decides ``correct``: what the timed path served, against the
plain float32 reference.

Once the window has closed, a sample drawn from the seed of the requests the
run finished after set-up (the longest among them always, then the seed's
draw, preferring rows that were decoded on the host and rows whose pages were
swapped; where those hold too few tokens, requests still in flight) is run
through ``bench/reference/dense.py`` over its prompt and its served tokens.
For every served token the number read is the gap by which the reference's
logit of that token lies below the reference's best logit at its position
(0 where the served token is the reference's first choice).  Greedy decoding
serves the program's first choice, so the widest gap bounds how far the
program's logits strayed.

The limits are per cell, in ``bench/limits/<cell>.json``:

- ``max_logit_gap``: the widest gap allowed;
- ``min_tokens``: the fewest served tokens the comparison must cover;
- ``sample_tokens``: served tokens to draw (at least);
- ``rehearse``: the same keys for the small rehearsal sizes.

The control (``run(..., control=True)``) is the reference itself computed in
float8 (e4m3, one scale per tensor): at each position of the same prompts and
served tokens, the token the control puts first is read against the float32
reference the same way, and judged by the same limits.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np


def pick(engine, probe, seed: int, limits: Dict, rehearse: bool) -> Dict:
    """Copy out the prompts and served tokens of the sampled requests."""
    lim = limits["rehearse"] if rehearse else limits
    done = [r for r in probe.records if r["status"] == "finished"]
    rng = np.random.default_rng(np.random.SeedSequence([seed, 7]))
    order = sorted(done, key=lambda r: -(r["prompt_len"] + r["output_len"]))
    rest = [order[i] for i in rng.permutation(len(order)) if i != 0]
    # rows decoded on the host and swapped rows go first among the rest
    rest.sort(key=lambda r: (r["rid"] not in probe.host_rids,
                             r["rid"] not in probe.swap_rids))
    # where too few finished, requests still in flight follow, longest first
    rest += sorted((r for r in probe.records if r["status"] == "active" and r["n"] > 1),
                   key=lambda r: -r["n"])
    chosen: List[Dict] = []
    tokens = 0
    for r in order[:1] + rest:
        if tokens >= lim["sample_tokens"]:
            break
        chosen.append(r)
        tokens += r["n"]
    reqs = []
    for r in chosen:
        q = engine.requests[r["rid"]]
        reqs.append({"prompt": list(q.prompt), "served": list(q.out_tokens),
                     "host": r["rid"] in probe.host_rids,
                     "swapped": r["rid"] in probe.swap_rids})
    return {"requests": reqs, "limits": lim}


def run(sample: Dict, dims: Dict, seed: int, control: bool = False) -> Dict:
    """The comparison.  With ``control`` the control's first choices stand in
    the program's place and go through the same limits; the program's own
    reading is kept in ``info``."""
    from bench import weights
    from bench.reference.dense import Reference

    lim = sample["limits"]
    ref = Reference(weights.make(dims, seed), dims)
    program, ctrl_worst, tokens = 0.0, 0.0, 0
    host_tokens = swap_tokens = 0
    for r in sample["requests"]:
        gaps, ctrl = ref.served_gaps(r["prompt"], r["served"], control=control)
        program = max(program, float(np.max(gaps)))
        tokens += len(gaps)
        host_tokens += len(gaps) if r["host"] else 0
        swap_tokens += len(gaps) if r["swapped"] else 0
        if ctrl is not None:
            ctrl_worst = max(ctrl_worst, float(np.max(ctrl)))
    worst = ctrl_worst if control else program
    checks = {
        "max_logit_gap": {"value": worst, "limit": lim["max_logit_gap"]},
        "compared_tokens": {"value": tokens, "limit": lim["min_tokens"]},
    }
    correct = bool(worst <= lim["max_logit_gap"] and tokens >= lim["min_tokens"])
    info = {"host_row_tokens": host_tokens, "swapped_tokens": swap_tokens,
            "requests": len(sample["requests"]), "program_max_logit_gap": program}
    if control:
        info["control_max_logit_gap"] = ctrl_worst
    return {"correct": correct, "checks": checks, "tokens": tokens, "info": info}
