"""`correct` has to come out false when it should.

1. The control: the reference put in the program's place and computed in the
   nearest precision below the configuration's (the tiny configuration is
   float32, so bfloat16), at a size a test run can hold.
2. The timed path broken underneath a whole (rehearsal) run of the harness,
   once for each fault the cells can have: a step that returns its state
   unchanged; half of the batch left out, the mean taken over the rest; a
   token altered where it is produced. (The exchange between chips left out
   belongs to a cell across chips; this benchmark has none yet.)
3. The control through a run's own comparison: ``compare`` of the traffic
   module and ``run.judge`` against the cell's limits, which is also how
   ``bench/calibrate.py`` reads it on the chip at the cell's size.
"""
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

import conftest
from mxbench.models import gpt2 as builder
from mxbench.reference import gpt2 as ref
from mxbench.traffic import train_steps as ts

TESTS = os.path.join(conftest.BENCH, "tests")
CFG = json.load(open(os.path.join(TESTS, "configs", "gpt2-tiny.json")))


def spec_of(cell):
    return json.load(open(os.path.join(TESTS, "workloads", f"{cell}.json")))


def run_cell(capsys, cell, seed=77):
    rc = conftest.run.main(["--rehearsal", "--workload", cell, "--seed",
                            str(seed), "--seconds", "1.0", "--trace", "0"])
    out = capsys.readouterr()
    return json.loads(out.out.strip().splitlines()[-1])


# ------------------------------------------------------------ the control
@pytest.mark.parametrize("seed", [5, 2147483999, 4000000003])
def test_train_control_in_lower_precision_fails(seed):
    spec = spec_of("tiny-train")
    tr, opt = spec["traffic"], spec["optimizer"]
    tokens = ts.make_batches(CFG, seed, tr["batches"], tr["global_batch"],
                             tr["seq"])
    batches = [(tokens[i, :, :-1], tokens[i, :, 1:]) for i in range(3)]
    params = builder.reference_weights(CFG, seed)
    want = ts.reference_readings(builder, params, batches, CFG, opt, spec)
    ctl = ts.reference_readings(builder, params, batches, CFG, opt, spec,
                                fake=jnp.bfloat16)
    parts = [(leaf, layer) for name in builder.program_names(CFG)
             for leaf, layer, _ in builder.parts(name, np.zeros(3))]
    numbers = ts.compare(ts.as_got(ctl, parts), want, parts)
    limits = spec["limits"]
    assert any(numbers[k] > limits[k] for k in limits), numbers
    # and the reference against itself passes every limit
    numbers = ts.compare(ts.as_got(want, parts), want, parts)
    assert all(numbers[k] <= limits[k] for k in limits)


@pytest.mark.parametrize("seed", [6, 2147484001, 4000000005])
@pytest.mark.parametrize("family,config,cell", [
    ("gpt2", "gpt2-tiny", "tiny-chat"), ("gqa", "gqa-tiny", "tiny-gqa-chat")])
def test_serve_control_in_lower_precision_fails(family, config, cell, seed):
    """The control need not decode: at each position of the same prompts and
    tokens it reads the gap of the token the lower precision puts first.
    Once for each family: the second lives wholly in this directory."""
    import importlib
    fam = importlib.import_module(f"mxbench.models.{family}")
    cfg = json.load(open(os.path.join(TESTS, "configs", f"{config}.json")))
    spec = spec_of(cell)
    params = fam.reference_weights(cfg, seed)
    rng = np.random.RandomState(seed % 2 ** 32)
    seqs = [[int(t) for t in rng.randint(0, 256, 128)] for _ in range(8)]
    plens = [1] * len(seqs)
    low = fam.ref.served_gaps(params, seqs, plens, cfg, fake=jnp.bfloat16,
                              pad_to=128)
    assert max(g for row in low for g in row) > spec["limits"]["logit_gap"]
    # the reference's own first choice at every position lies 0 below it
    lg = fam.ref.logits(params, jnp.asarray(seqs[:1], jnp.int32), cfg)
    best = [int(t) for t in jnp.argmax(lg[0], axis=-1)]
    one = [seqs[0][:40] + [best[39]]]
    gaps = fam.ref.served_gaps(params, one, [40], cfg, pad_to=128)
    assert gaps == [[0.0]]


@pytest.mark.parametrize("fake,correct", [
    (None, True), (jnp.bfloat16, False), ("int8", False)])
def test_control_through_the_runs_own_comparison(fake, correct):
    """What ``bench/calibrate.py`` does on the chip. The program's stand-in
    is the reference's own greedy continuation of six prompts; the control
    reads its own first choice at every position of 8 x 128 tokens (greedy
    continuations of this toy sit on winners too clear for bfloat16 to
    move)."""
    import jax
    from mxbench.traffic import serve_common as sc
    spec, seed = spec_of("tiny-chat"), 4000000005
    pad = int(spec["reference_pad_to"])
    rng = np.random.RandomState(seed % 2 ** 32)
    if fake is None:
        params = builder.reference_weights(CFG, seed)
        forward = jax.jit(lambda ids: ref.logits(params, ids, CFG))
        sample = []
        for _ in range(6):
            seq = [int(t) for t in rng.randint(0, 256, 24)]
            for _ in range(40):
                ids = np.zeros((1, pad), np.int32)
                ids[0, :len(seq)] = seq
                seq.append(int(jnp.argmax(forward(ids)[0, len(seq) - 1])))
            sample.append({"prompt": seq[:24], "generated": seq[24:]})
    else:
        seqs = [[int(t) for t in rng.randint(0, 256, pad)] for _ in range(8)]
        sample = [{"prompt": q[:1], "generated": q[1:]} for q in seqs]
    numbers = sc.compare(builder, CFG, spec, seed, sample, 0, fake=fake)
    got, compared, notes = conftest.run.judge(numbers, spec["limits"])
    assert got is correct, compared
    assert notes["_gaps"]["tokens"] == (240 if fake is None else 8 * (pad - 1))


# ------------------------------------------------- the timed path, broken
def test_state_left_unchanged_is_not_correct(capsys, monkeypatch):
    import mxnet_tpu as mx
    monkeypatch.setattr(mx.optimizer.Adam, "update_step",
                        lambda self, w, g, state, lr, wd, t: (w, state))
    result = run_cell(capsys, "tiny-train")
    assert result["correct"] is False
    assert result["compared"]["update_norm"]["value"] > 0.99
    assert result["compared"]["grad_norm"]["value"] > 0.99


def test_half_the_batch_left_out_is_not_correct(capsys, monkeypatch):
    from mxnet_tpu import parallel
    from mxnet_tpu.ndarray import NDArray
    real = parallel.TrainStep.step

    def half(self, inputs, labels=None):
        n = inputs.shape[0] // 2
        return real(self, NDArray(inputs._data[:n]), NDArray(labels._data[:n]))

    monkeypatch.setattr(parallel.TrainStep, "step", half)
    result = run_cell(capsys, "tiny-train")
    assert result["correct"] is False
    c = result["compared"]["grad_norm"]
    assert c["value"] > c["limit"]


@pytest.mark.parametrize("cell", ["tiny-chat", "tiny-batch",
                                  "tiny-gqa-chat"])
def test_a_token_altered_where_it_is_produced_is_not_correct(
        capsys, monkeypatch, cell):
    from mxnet_tpu.models import generation
    real = generation.sample_tokens

    def altered(logits, *args, **kwargs):
        tok = real(logits, *args, **kwargs)
        return (tok + 1) % logits.shape[-1]

    monkeypatch.setattr(generation, "sample_tokens", altered)
    result = run_cell(capsys, cell)
    assert result["correct"] is False
    c = result["compared"]["logit_gap"]
    assert c["value"] > c["limit"]


def test_a_request_cut_short_is_not_correct(capsys, monkeypatch):
    from mxbench.traffic import serve_common as sc
    real = sc.submit

    def short(engine, req):
        return real(engine, dict(req, max_new=max(1, req["max_new"] - 1)))

    monkeypatch.setattr(sc, "submit", short)
    result = run_cell(capsys, "tiny-batch")
    assert result["correct"] is False
    assert result["compared"]["length_mismatch"]["value"] > 0
