"""Tier-1's drive of the benchmark's MiniCPM-SALA family: the rehearsal cell
``tiny-sala-longdoc`` (``bench/tests/``) through ``bench/run.py --rehearsal``
in a process of its own, so that the builder, the plain reference and the
family's count are exercised by the harness as a chip run exercises them
(``ROADMAP.md`` D4, for this family). The cell has a benchmark file of its
own beside the harness's (``bench/tests/BENCHMARK-sala.json``): a PR that adds
a family adds files there and edits none."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RUN = os.path.join(ROOT, "bench", "run.py")


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_cell_is_correct(trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)      # one CPU device, as the harness expects
    proc = subprocess.run(
        [sys.executable, RUN, "--rehearsal", "--workload",
         "tiny-sala-longdoc", "--seed", "3000000019", "--seconds", "1.5",
         "--trace", str(trace), "--benchmark",
         os.path.join(ROOT, "bench", "tests", "BENCHMARK-sala.json")],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert result["device"]["platform"] == "cpu"
    assert set(result["compared"]) == {"logit_gap", "gap_mean",
                                      "length_mismatch"}
    # the reference says how much of its selection survives bfloat16
    assert "_selection" in proc.stderr
    # the family's own count: 2 lightning layers (5 x 64 x 64 + 3 x 64 x
    # 128), 2 sparse layers (3 x 64 x 64 + 2 x 64 x 32 + 3 x 64 x 128) and a
    # head of 64 x 256, two bytes each
    assert result["facts"]["weight_bytes"] == 2 * (
        2 * (5 * 64 * 64 + 3 * 64 * 128)
        + 2 * (3 * 64 * 64 + 2 * 64 * 32 + 3 * 64 * 128) + 64 * 256)
    if not trace:
        assert {"setup_s", "serve_ttft_p90_ms", "serve_itl_p95_ms"} \
            <= set(result["metrics"])
