"""The check that decides ``correct``: a whole run with a fault planted
under the timed path reads incorrect, a sound one correct; and the
controls of the comparison fail it where they should.  Small sizes, on
whatever device JAX has (no chip looked for)."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
SEED = 2**31 + 29


def result(args: list[str]) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, *args], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return [json.loads(ln) for ln in p.stdout.splitlines()
            if ln.startswith("{")]


@pytest.mark.parametrize("fault", ["none", "superstep_noop", "half_batch",
                                   "answer_altered", "tree_altered",
                                   "first_answer"])
def test_planted_fault_reads_incorrect(fault):
    line = result(["bench/faults.py", "--workload", "lod-exact", "--fault",
                   fault, "--seed", str(SEED)])[-1]
    assert line["correct"] == (fault == "none"), line["checks"]
    assert line["checks"]["checked"]["value"] >= 1
    assert line["metrics"]["queries_per_s"]["value"] > 0


def test_controls():
    """bfloat16 cannot fail the exact comparison (every weight is a small
    integer, exact in bfloat16), which is why the truncated search
    (``first_answer`` above) is the control that sets the upper
    reading."""
    lines = result(["bench/control.py", "--workload", "lod-exact",
                    "--seeds", f"{SEED},{SEED + 1},{SEED + 2}",
                    "--divide", "2"])
    assert len(lines) == 3
    for line in lines:
        assert line["checked"] >= 1, line
        assert line["wrong_weights_bfloat16"] == 0, line
