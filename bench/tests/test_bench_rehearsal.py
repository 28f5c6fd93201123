"""One short run of each cell at the rehearsal sizes through ``bench/run.py``
on the CPU, and a run that finds no TPU and must fail.

A rehearsal shows the harness's control flow and result line, never a
speed: the CPU is no device this benchmark measures.
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
RUN = os.path.join(ROOT, "bench", "run.py")
CELLS = [w["name"] for w in json.load(open(os.path.join(ROOT, "BENCHMARK.json")))["workloads"]]


def _run(tmp_path, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    return subprocess.run([sys.executable, RUN, *args], cwd=str(tmp_path), env=env,
                          capture_output=True, text=True, timeout=600)


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_prints_the_result_line(cell, tmp_path):
    p = _run(tmp_path, "--workload", cell, "--seed", str(2 ** 33 + 5),
             "--seconds", "2", "--trace", "0", "--rehearse")
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(out)[-1] == "checks"
    assert out["correct"] is True, out["checks"]
    assert out["device"]["platform"] == "cpu" and out["device"]["count"] == 1
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    e2e = {m["name"] for m in spec["end_to_end"] if cell in m.get("workloads", [cell])}
    assert set(out["metrics"]) == e2e
    assert out["metrics"]["output_tok_s"]["value"] > 0
    assert "limit" in p.stderr.strip().splitlines()[-1]


def test_no_tpu_no_result(tmp_path):
    p = _run(tmp_path, "--workload", CELLS[0], "--seed", "1", "--seconds", "2",
             "--trace", "0")
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr
