"""bench/flops.py against counts worked by hand for both configurations."""
import json
import os

import conftest  # noqa: F401  (sets up the mxbench alias)
from mxbench import flops

CONFIGS = os.path.join(conftest.BENCH, "configs")
PEAKS = json.load(open(os.path.join(conftest.BENCH, "peaks.json")))[
    "TPU v5 lite"]


def cfg(name):
    return json.load(open(os.path.join(CONFIGS, f"{name}.json")))


def test_param_counts_are_the_published_ones():
    # 354,823,168 and 1,557,611,200: the sizes the checkpoints are known by
    assert flops.gpt2_param_count(cfg("gpt2-medium")) == 354_823_168
    assert flops.gpt2_param_count(cfg("gpt2-xl")) == 1_557_611_200


def test_matmul_params_by_hand():
    # 12 D^2 per layer (qkv 3, out 1, fc 4, proj 4) and the tied head V D
    assert flops.gpt2_matmul_params(cfg("gpt2-medium")) == \
        24 * 12 * 1024 * 1024 + 50257 * 1024 == 353_453_056
    assert flops.gpt2_matmul_params(cfg("gpt2-xl")) == \
        48 * 12 * 1600 * 1600 + 50257 * 1600 == 1_554_971_200


def test_train_flops_per_token_medium():
    # 6 N + 6 L D (T + 1): 2,120,718,336 + 151,142,400
    got = flops.train_flops_per_token(cfg("gpt2-medium"), 1024)
    assert got == 6 * 353_453_056 + 6 * 24 * 1024 * 1025 == 2_271_860_736


def test_decode_step_xl_is_bandwidth_bound():
    c = cfg("gpt2-xl")
    assert flops.kv_bytes_per_token(c) == 48 * 2 * 1600 * 2 == 307_200
    got = flops.decode_step_least_s(c, [100, 300], PEAKS)
    nbytes = 1_554_971_200 * 2 + 400 * 307_200
    assert got["bytes"] == nbytes and got["binds"] == "hbm"
    assert abs(got["seconds"] - nbytes / 819e9) < 1e-12
    # two tokens: 2 * 2 N for the matrices, 4 L D per key/value row read
    assert got["flops"] == 4 * 1_554_971_200 + 4 * 48 * 1600 * 400


def test_prefill_chunks_by_hand():
    c = cfg("gpt2-xl")
    # one 16-token chunk at offset 32: rows read = 16*32 + 16*17/2 = 648
    got = flops.prefill_least_s(c, [(16, 32)], PEAKS)
    assert got["flops"] == 2 * 1_554_971_200 * 16 + 4 * 48 * 1600 * 648
    assert got["bytes"] == 1_554_971_200 * 2 + 48 * 307_200
    assert got["binds"] == "hbm"


def test_flash_attention_train_by_hand():
    # B 8, H 16, T 1024, hd 64: six products over the lower triangle
    got = flops.flash_attention_train(8, 16, 1024, 64, PEAKS)
    assert got["flops"] == 12 * 8 * 16 * (1024 * 1025 // 2) * 64 \
        == 51_589_939_200
    assert got["bytes"] == 12 * 8 * 16 * 1024 * 64 * 2
    assert got["binds"] == "flops"
    assert abs(got["seconds"] - 51_589_939_200 / 197e12) < 1e-12
