"""The yardstick: ``bench/flops.py`` and GPT-2's count (``bench/work/gpt2.py``)
against counts worked by hand for both configurations, and against the values
that ``bench/flops.py`` returned before the count moved behind the builder
(commit a4c805b): the move may change no number."""
import json
import os

import pytest

import conftest  # noqa: F401  (sets up the mxbench alias)
from mxbench import flops
from mxbench.models import gpt2 as builder

work = builder.work   # what the traffic modules call

CONFIGS = os.path.join(conftest.BENCH, "configs")
PEAKS = json.load(open(os.path.join(conftest.BENCH, "peaks.json")))[
    "TPU v5 lite"]


def cfg(name):
    return json.load(open(os.path.join(CONFIGS, f"{name}.json")))


def test_param_counts_are_the_published_ones():
    # 354,823,168 and 1,557,611,200: the sizes the checkpoints are known by
    assert work.param_count(cfg("gpt2-medium")) == 354_823_168
    assert work.param_count(cfg("gpt2-xl")) == 1_557_611_200


def test_matmul_params_by_hand():
    # 12 D^2 per layer (qkv 3, out 1, fc 4, proj 4) and the tied head V D
    assert work.matmul_params(cfg("gpt2-medium")) == \
        24 * 12 * 1024 * 1024 + 50257 * 1024 == 353_453_056
    assert work.matmul_params(cfg("gpt2-xl")) == \
        48 * 12 * 1600 * 1600 + 50257 * 1600 == 1_554_971_200


def test_train_flops_per_token_medium():
    # 6 N + 6 L D (T + 1): 2,120,718,336 + 151,142,400
    got = work.train_flops_per_token(cfg("gpt2-medium"), 1024)
    assert got == 6 * 353_453_056 + 6 * 24 * 1024 * 1025 == 2_271_860_736


def test_decode_step_xl_is_bandwidth_bound():
    c = cfg("gpt2-xl")
    assert work.cache_bytes(c, 1) == 48 * 2 * 1600 * 2 == 307_200
    got = flops.decode_step_least_s(work, c, [100, 300], PEAKS)
    nbytes = 1_554_971_200 * 2 + 400 * 307_200
    assert got["bytes"] == nbytes and got["binds"] == "hbm"
    assert abs(got["seconds"] - nbytes / 819e9) < 1e-12
    # two tokens: 2 * 2 N for the matrices, 4 L D per key/value row read
    assert got["flops"] == 4 * 1_554_971_200 + 4 * 48 * 1600 * 400


def test_prefill_chunks_by_hand():
    c = cfg("gpt2-xl")
    # one 16-token chunk at offset 32: rows read = 16*32 + 16*17/2 = 648
    got = flops.prefill_least_s(work, c, [(16, 32)], PEAKS)
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
    # as a4c805b returned it, to the last digit
    assert got["seconds"] == 0.0002618778639593909


def _decode(c):
    got = flops.decode_step_least_s(work, c, [60] * 16, PEAKS)
    return got["seconds"], got["bytes"], got["binds"]


def _prefill(c):
    got = flops.prefill_least_s(work, c, [(128, 0), (19, 128)], PEAKS)
    return got["seconds"], got["binds"]


#: the calls the traffic modules make, and what ``bench/flops.py`` of a4c805b
#: returned for them on the two published configuration files (peaks of
#: "TPU v5 lite"): name -> (call, gpt2-medium, gpt2-xl)
PINNED = {
    "weight_bytes": (lambda c: work.weight_bytes(c, 16),
                     706_906_112, 3_109_942_400),
    "cache_bytes_a_token": (lambda c: work.cache_bytes(c, 1),
                            98_304, 307_200),
    "forward_n1_start99": (lambda c: work.forward_flops(c, 1, 99),
                           716_736_512, 3_140_662_400),
    "forward_n128_start0": (lambda c: work.forward_flops(c, 128, 0),
                            91_295_580_160, 400_608_870_400),
    "train_flops_per_token_seq1024": (
        lambda c: work.train_flops_per_token(c, 1024),
        2_271_860_736, 9_802_147_200),
    "decode_step_16_rows_depth_60": (
        _decode, (0.0009783613577533578, 801_277_952, "hbm"),
        (0.004157331379731379, 3_404_854_400, "hbm")),
    "prefill_chunks_128_0_19_128": (
        _prefill, (0.0017592745103785104, "hbm"),
        (0.007697637118437119, "hbm")),
    "train_attention": (lambda c: tuple(work.train_attention(c)),
                        (16, 64, 24), (25, 64, 48)),
    "max_positions": (work.max_positions, 1024, 1024),
}


@pytest.mark.parametrize("name", sorted(PINNED))
@pytest.mark.parametrize("i,config", enumerate(("gpt2-medium", "gpt2-xl")))
def test_the_move_behind_the_builder_changed_no_number(i, config, name):
    # integers equal, seconds equal to the last digit
    assert PINNED[name][0](cfg(config)) == PINNED[name][1 + i]
