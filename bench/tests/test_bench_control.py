"""The check's control: the reference computed one precision below the
configuration's bfloat16 (fp8, ``--control``) is put in the program's place
and judged by the same comparison and limits; it must come out not correct in
every cell, at the rehearsal sizes, where the program's own served tokens
pass (``test_bench_rehearsal.py``).  On the chip the same flag judges the
control at each cell's own size."""

import json
import os

import pytest

from harness import rehearse  # noqa: F401 -- fixture

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELLS = [w["name"] for w in json.load(open(os.path.join(ROOT, "BENCHMARK.json")))["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_where_the_program_passes(cell, rehearse):  # noqa: F811
    out = rehearse(cell, "--control")
    c = out["checks"]
    assert out["correct"] is False, c
    assert c["max_logit_gap"]["value"] > c["max_logit_gap"]["limit"]
    assert c["compared_tokens"]["value"] >= c["compared_tokens"]["limit"]
