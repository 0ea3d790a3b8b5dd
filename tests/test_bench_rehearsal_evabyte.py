"""Tier-1's drive of the benchmark's EvaByte family: the rehearsal cell
``tiny-evabyte-conv`` (``bench/tests/``) through ``bench/run.py --rehearsal``
in a process of its own, so that the builder, the plain reference and the
family's count are exercised by the harness as a chip run exercises them
(``ROADMAP.md`` D4, for this family). The cell has a benchmark file of its
own beside the harness's (``bench/tests/BENCHMARK-evabyte.json``): a PR that
adds a family adds files there and edits none."""
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
         "tiny-evabyte-conv", "--seed", "3000000019", "--seconds", "1.5",
         "--trace", str(trace), "--benchmark",
         os.path.join(ROOT, "bench", "tests", "BENCHMARK-evabyte.json")],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert result["device"]["platform"] == "cpu"
    assert set(result["compared"]) == {"logit_gap", "gap_mean",
                                      "length_mismatch"}
    # the family's own count: 2 layers (4 x 64 x 64 + 3 x 64 x 128), the
    # first prediction head's 64 x 320 and one embedding row, two bytes each
    assert result["facts"]["weight_bytes"] == 2 * (
        2 * (4 * 64 * 64 + 3 * 64 * 128) + 64 * 320 + 64)
    # the new facts: a decoded token reads folded rows, 2 x 64 x 2 bytes a
    # row in each of 2 layers, never more than a window and 4 summary pages
    facts = result["facts"]
    assert facts["decode_tokens"] > 0 and facts["preemptions"] == 0
    assert 0 < facts["decode_kv_bytes"] <= facts["decode_tokens"] \
        * (32 + 4 * 8) * 2 * 64 * 2 * 2
    if not trace:
        assert {"setup_s", "serve_ttft_p90_ms", "serve_itl_p95_ms"} \
            <= set(result["metrics"])
