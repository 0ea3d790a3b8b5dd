"""``serve_common.window_facts`` on a recorded window of the tiny GPT-2: the
facts that the per-layer readers divide by (operations, bytes, least times)
come out as they did before the count moved behind the builder. The expected
numbers were read from commit a4c805b on the same records."""
import json
import os
import types

import conftest
from mxbench.models import gpt2 as builder
from mxbench.models import gqa
from mxbench.traffic import serve_common as sc

TESTS = os.path.join(conftest.BENCH, "tests")
T0, CLOSE = 100.0, 110.0

#: as a4c805b gave them
PARENT = {"attempted": 6, "failed": 2, "tokens": 134, "generated_tokens": 32,
          "prompt_tokens": 102, "requests_done": 4, "decode_tokens": 28,
          "decode_kv_bytes": 395264, "weight_bytes": 229376,
          "model_flops": 31082496.0,
          "prefill_least_s": 4.099750915750916e-06, "prefill_binds": "hbm",
          "preemptions": 2, "slots": 4}


class Engine:
    def stats(self):
        return {"page_size": 8, "preemptions": 3, "slots": 4}


def record(prompt_len, due, stamps, refused=False, finished=True):
    res = types.SimpleNamespace(ok=True, queue_wait_s=0.01 * prompt_len,
                                generated_ids=list(range(len(stamps))))
    handle = types.SimpleNamespace(done=lambda: finished, _result=res)
    return {"req": {"prompt": list(range(prompt_len)), "greedy": True,
                    "max_new": len(stamps)},
            "due": due, "sent": due + 0.001, "refused": refused,
            "handle": handle, "feed": types.SimpleNamespace(stamps=stamps)}


def recorded_window():
    return [
        record(5, 0.5, [100.6 + 0.1 * j for j in range(12)]),
        record(24, 1.0, [101.3 + 0.2 * j for j in range(8)]),
        # runs past the close: what came after it is not the window's work
        record(40, 4.0, [104.9 + 0.5 * j for j in range(20)]),
        record(9, 6.0, [], refused=True),
        # the first token after the close: its prefill is not counted
        record(17, 9.5, [110.4, 110.9]),
        record(33, 9.0, [109.7], finished=False),     # never finished
    ]


def facts_of(builder, config):
    cfg = json.load(open(os.path.join(TESTS, "configs", f"{config}.json")))
    spec = json.load(open(os.path.join(TESTS, "workloads", "tiny-chat.json")))
    peaks = json.load(open(os.path.join(conftest.BENCH, "peaks.json")))[
        "TPU v5 lite"]
    state = {"ctx": {"cfg": cfg, "spec": spec, "peaks": peaks,
                     "builder": builder, "seed": 1},
             "engine": Engine(), "preempt0": 1}
    return sc.window_facts(state, recorded_window(), T0, CLOSE, 10.0)


def test_the_facts_of_a_recorded_window_are_the_parents():
    facts = facts_of(builder, "gpt2-tiny")
    assert {k: facts[k] for k in PARENT} == PARENT


def test_another_family_is_counted_by_its_own_count():
    """The same records under the grouped-query toy: the 28 decoded tokens
    attend over 772 positions between them (395,264 / (2 * 2 * 64 * 2) in
    GPT-2's count); here a position holds 2 layers * (k, v) * 2 kv heads *
    16 * 2 bytes, half of what its 4 query heads would."""
    facts = facts_of(gqa, "gqa-tiny")
    assert facts["decode_tokens"] == 28
    assert facts["decode_kv_bytes"] == 772 * 2 * 2 * 2 * 16 * 2 == 197_632
    n = 2 * (2 * 64 * 64 + 2 * 64 * 32 + 3 * 64 * 128) + 64 * 256
    assert facts["weight_bytes"] == 2 * n == 180_224
    # four prompts whole (the last one's only token came before the close),
    # then 11 + 7 + 10 decoded tokens
    want = sum(2.0 * n * P + 4.0 * 2 * 64 * (P * (P + 1) // 2)
               for P in (5, 24, 40, 33))
    want += 2.0 * n * 28 + 4.0 * 2 * 64 * 772
    assert facts["model_flops"] == want
    assert facts["prefill_binds"] == "hbm"
