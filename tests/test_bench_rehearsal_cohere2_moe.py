"""Tier-1's drive of the benchmark's Cohere2-MoE family: the rehearsal cell
``tiny-commandaplus-longdoc`` (``bench/tests/``) through ``bench/run.py
--rehearsal`` in a process of its own, so that the builder, the plain
reference and the family's count are exercised by the harness as a chip run
exercises them (``ROADMAP.md`` D4, for this family). The cell has a benchmark
file of its own beside the harness's (``bench/tests/BENCHMARK-cohere2-moe.
json``): a PR that adds a family adds files there and edits none."""
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
         "tiny-commandaplus-longdoc", "--seed", "3600000019", "--seconds",
         "1.5", "--trace", str(trace), "--benchmark",
         os.path.join(ROOT, "bench", "tests",
                      "BENCHMARK-cohere2-moe.json")],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert result["device"]["platform"] == "cpu"
    assert set(result["compared"]) == {"logit_gap", "gap_mean",
                                      "length_mismatch"}
    # the family's own count at one row: 4 layers of attention (2 x 64 x 128
    # + 2 x 64 x 32), a router of 64 x 16, 2 shared experts of 3 x 64 x 32, a
    # gain, and 4 x (1 - (1 - 4/16)^1) = 1 held expert; the tied table and
    # the final gain; two bytes each
    layer = 2 * 64 * 128 + 2 * 64 * 32 + 64 * 16 + 2 * 3 * 64 * 32 + 64 \
        + 3 * 64 * 32
    facts = result["facts"]
    assert facts["weight_bytes"] == 2 * (4 * layer + 64 * 256 + 64)
    # a decoded token reads a key and a value row (2 KV heads of 16, two
    # bytes) for every position in the full layer and never more than the
    # window's 24 in the three sliding ones
    assert facts["decode_tokens"] > 0 and facts["preemptions"] == 0
    row = 2 * 2 * 16 * 2
    assert 0 < facts["decode_kv_bytes"] <= facts["decode_tokens"] \
        * (184 + 3 * 24) * row
    if not trace:
        assert {"setup_s", "serve_ttft_p90_ms", "serve_itl_p95_ms"} \
            <= set(result["metrics"])
