"""The examples/ directory must stay runnable: each demo is executed as a
subprocess (fresh interpreter, the way a user runs it) and its printed
proof-of-work is asserted. Mirrors the reference's demo-scripts-as-tests
discipline (``python/paddle/fluid/tests/demo/``). Each script runs ONCE
per session; every assertion reads the cached output."""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CASES = [
    # Heaviest demo (~13s): tier-1 time budget pushed it behind `slow`.
    pytest.param("gpt_pretrain.py", ["loss", "tokens/s", "saved"],
                 marks=pytest.mark.slow, id="gpt_pretrain"),
    pytest.param("hybrid_parallel.py", ["loss", "PartitionSpec"],
                 id="hybrid_parallel"),
    pytest.param("ps_ctr_train.py", ["table rows 500"], id="ps_ctr_train"),
    pytest.param("graph_deepwalk.py", ["cosine same-clique"],
                 id="graph_deepwalk"),
    pytest.param("export_serving.py",
                 ["matches the eager model", "decode engine: "],
                 id="export_serving"),
]

_outputs = {}


def _run_once(script: str) -> str:
    if script not in _outputs:
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "examples", script)],
            capture_output=True, text=True, timeout=420, env=env)
        assert proc.returncode == 0, proc.stderr[-2000:]
        _outputs[script] = proc.stdout
    return _outputs[script]


@pytest.mark.parametrize("script,expect", CASES)
def test_example_runs(script, expect):
    out = _run_once(script)
    for needle in expect:
        assert needle in out, (needle, out[-2000:])


def test_deepwalk_separates_cliques():
    """The deepwalk demo's learning signal is real: same-clique cosine
    must exceed cross-clique by a wide margin."""
    out = _run_once("graph_deepwalk.py")
    line = [l for l in out.splitlines() if "cosine" in l][0]
    same = float(line.split("same-clique ")[1].split(" ")[0])
    cross = float(line.split("cross-clique ")[1])
    assert same > cross + 0.3, line
