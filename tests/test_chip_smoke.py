"""chip_smoke.py's rehearsal mode and its refusal, plus the places a missing
chip used to hide: an accelerator ``Device`` aliasing the CPU, roofline peaks
defaulting to a v5e, and nothing else."""
import importlib.util
import json
import os
import subprocess
import sys

import jax
import pytest

import mxnet_tpu as mx
from mxnet_tpu.base import MXNetError

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _json_lines(text):
    return [json.loads(ln) for ln in text.splitlines() if ln.startswith("{")]


def test_tiny_rehearsal_in_process(capsys):
    """``--tiny`` runs every one-chip phase here on the CPU, and every line
    it prints says so."""
    assert _smoke().main(["--tiny"]) == 0
    lines = _json_lines(capsys.readouterr().out)
    assert [ln.get("phase") for ln in lines[1:-1]] == [
        "train", "serve", "serve_int8"]
    head, last = lines[0], lines[-1]
    assert head["backend"] == "cpu" and head["tiny"] is True
    assert head["jax"] == jax.__version__
    assert all(ln["platform"] == "cpu" for ln in lines[1:-1])
    assert last == {"ok": True, "device": {
        "platform": "cpu", "kind": jax.devices()[0].device_kind,
        "count": len(jax.devices())}}
    train, serve, int8 = lines[1:-1]
    assert train["tpu_custom_call"] is False        # no Pallas off-TPU
    assert train["losses"][-1] < train["losses"][0]
    assert serve["token_parity"] and int8["token_parity"]
    # off-TPU the int8 sites run their XLA reference and say so
    assert set(int8["launches"]) == {"reference"}


def test_tiny_dp_rehearsal_in_process(capsys):
    """``--tiny --chips 4``: only the data-parallel phase and the one-device
    run it is compared with, shards on four distinct (virtual) devices."""
    assert _smoke().main(["--tiny", "--chips", "4"]) == 0
    lines = _json_lines(capsys.readouterr().out)
    assert [ln.get("phase") for ln in lines[1:-1]] == [
        "train_one_device", "train_dp4_zero0", "train_dp4_zero1"]
    for ln in lines[2:-1]:
        assert len(set(ln["batch_shard_devices"])) == 4
    assert len(set(lines[3]["state_shard_devices"])) == 4
    assert lines[2]["state_shard_devices"] is None


def test_default_mode_refuses_without_a_tpu():
    """The default mode on a CPU-only process exits non-zero naming the
    missing TPU, before it builds a model, and prints no result."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=REPO,
        capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert proc.stdout.strip() == ""


# ------------------------------------------------ no hiding places
def test_accelerator_device_names_a_virtual_cpu_only_when_pinned():
    """Pinned to the CPU on purpose (how the tests run) mx.gpu(i)/mx.tpu(i)
    name the i-th virtual CPU device — and an id past the end raises
    instead of wrapping around."""
    assert jax.config.jax_platforms == "cpu"
    n = len(jax.devices())
    assert mx.tpu(n - 1).jax_device is jax.devices()[n - 1]
    assert mx.gpu(0).jax_device is jax.devices()[0]
    with pytest.raises(MXNetError, match="out of range"):
        mx.tpu(n).jax_device
    with pytest.raises(MXNetError, match="out of range"):
        mx.cpu(n).jax_device


def test_accelerator_device_raises_without_an_accelerator():
    """Not pinned to the CPU and no accelerator found: an accelerator
    Device raises, naming the setting that would allow the alias."""
    jax.devices()                   # backends are up; the flag is now data
    jax.config.update("jax_platforms", "")
    try:
        with pytest.raises(MXNetError, match="JAX_PLATFORMS=cpu"):
            mx.tpu(0).jax_device
        with pytest.raises(MXNetError, match="JAX_PLATFORMS=cpu"):
            mx.gpu(0).jax_device
        assert mx.cpu(0).jax_device.platform == "cpu"
    finally:
        jax.config.update("jax_platforms", "cpu")


def test_chip_gen_raises_on_an_unknown_kind(monkeypatch):
    from mxnet_tpu.observability import perf
    monkeypatch.setattr(perf, "_CHIP_GEN", None)
    with pytest.raises(MXNetError, match="no peak"):
        perf._chip_gen()                        # device_kind 'cpu'
    with pytest.raises(MXNetError, match="no peak"):
        perf.chip_peak_flops()
    monkeypatch.setattr(perf, "_CHIP_GEN", "TPU v5 lite")
    assert perf._chip_gen() == "TPU v5 lite"
    assert perf.chip_peak_flops() == 197e12
    assert perf.chip_hbm_bandwidth() == 819e9


def test_no_roofline_is_published_without_peaks(monkeypatch):
    """On a device with no peaks on record the ledger keeps its entries and
    notes but reports no MFU: none exists."""
    from mxnet_tpu.observability import perf
    monkeypatch.setattr(perf, "_CHIP_GEN", None)
    import jax.numpy as jnp
    ledger = perf.CostLedger()
    entry = ledger.record(
        "k", lowered=jax.jit(lambda x: x @ x).lower(jnp.ones((64, 64))))
    assert entry.flops > 0
    ledger.note_step("k", 0.01)
    assert ledger.summary() == {}
    assert ledger.dump()["peak_flops"] is None
    monkeypatch.setattr(perf, "_CHIP_GEN", "TPU v5 lite")
    assert ledger.summary()["k"]["mfu"] == pytest.approx(
        entry.flops / 0.01 / 197e12, abs=1e-10)   # rounded to 10 digits


def test_compare_tokens_accepts_a_tie_and_nothing_wider():
    """A parting of the two greedy sequences passes only as a tie at bf16
    resolution: every engine token must be (nearly) the argmax of the
    teacher-forced forward."""
    import dataclasses

    import numpy as onp

    from mxnet_tpu import np
    from mxnet_tpu.models.gpt import GPT_TINY
    smoke = _smoke()
    net = smoke.build_net(dataclasses.replace(GPT_TINY, dropout=0.0), 1)
    prompt = onp.arange(5, dtype=onp.int32)
    n = smoke.NEW_TOKENS
    buf = onp.zeros((1, len(prompt) + n), onp.int32)
    buf[0, :len(prompt)] = prompt
    for cur in range(len(prompt), len(prompt) + n):
        # plain greedy through the full forward (causal: the zeros past
        # ``cur`` cannot reach position cur-1; one shape, one compile)
        logits = net(np.array(buf)).asnumpy()
        buf[0, cur] = int(logits[0, cur - 1].argmax())
    greedy = [int(t) for t in buf[0, len(prompt):]]
    assert smoke.compare_tokens(net, prompt, greedy, greedy, "t") \
        == "identical"
    other = list(greedy)
    other[7] = (other[7] + 1) % 256         # the reference parted here
    verdict = smoke.compare_tokens(net, prompt, greedy, other, "t")
    assert verdict["parted_at"] == 7
    assert verdict["worst_gap_bf16_ulps"] <= smoke.BF16_TIE_ULPS
    wrong = [(t + 1) % 256 for t in greedy]  # not ties: wrong tokens
    with pytest.raises(SystemExit, match="not a tie"):
        smoke.compare_tokens(net, prompt, wrong, greedy, "t")
