"""``correct`` has to come out false when the timed path is broken.

Each test skips the harness's look for a chip (``--cpu-rehearsal``: the tiny
sizes, the CPU backend) and drives the rest of a run with one fault planted
underneath, in the program, once for each fault a cell of this benchmark can
have. The sound run beside them shows that the same run passes unbroken.
"""
import json

import jax.numpy as jnp
import pytest

import run

TRAIN = ["--workload", "bert-large.pretrain-seq128", "--seed", "12345",
         "--seconds", "1", "--trace", "0", "--cpu-rehearsal"]
SERVE = ["--workload", "gpt2-large.chat-decode", "--seed", "12345",
         "--seconds", "3", "--trace", "0", "--cpu-rehearsal"]


def result(capsys, argv):
    assert run.main(argv) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def failing(line):
    return sorted(k for k, v in line["compared"].items()
                  if not v["value"] <= v["limit"])


@pytest.mark.parametrize("argv", [TRAIN, SERVE], ids=["train", "serve"])
def test_sound_run_is_correct(capsys, argv):
    line = result(capsys, argv)
    assert line["correct"] is True and line["failed"] == 0
    assert line["metrics"] == {} and line["device"]["platform"] == "rehearsal"


def test_step_that_returns_its_state_unchanged(capsys, monkeypatch):
    from mxnet_tpu import parallel

    real = parallel.build_train_step

    def broken(loss_fn, opt, mesh=None, **kw):
        step = real(loss_fn, opt, mesh=mesh, donate=False, **kw)

        def unchanged(params, states, t, key, batch):
            _p, _s, loss = step(params, states, t, key, batch)
            return params, states, loss
        return unchanged

    monkeypatch.setattr(parallel, "build_train_step", broken)
    line = result(capsys, TRAIN)
    assert line["correct"] is False
    assert "change3_worst_leaf_gap" in failing(line)
    assert line["compared"]["change3_worst_leaf_gap"]["value"] \
        == pytest.approx(1.0)


def test_half_of_the_batch_left_out(capsys, monkeypatch):
    from mxnet_tpu import parallel

    real = parallel.build_train_step

    def broken(loss_fn, opt, mesh=None, **kw):
        def half(params, batch, key):
            n = batch[0].shape[0] // 2
            return loss_fn(params, tuple(x[:n] for x in batch), key)
        return real(half, opt, mesh=mesh, **kw)

    monkeypatch.setattr(parallel, "build_train_step", broken)
    line = result(capsys, TRAIN)
    assert line["correct"] is False and failing(line)


def test_token_altered_where_it_is_produced(capsys, monkeypatch):
    from mxnet_tpu.serve import decoder

    real = decoder.sample_tokens

    def altered(logits, keys, positions, temps, top_k):
        tok = real(logits, keys, positions, temps, top_k)
        # every seventh position answers with the neighbouring token id
        return jnp.where(positions % 7 == 0, (tok + 1) % logits.shape[-1],
                         tok)

    monkeypatch.setattr(decoder, "sample_tokens", altered)
    line = result(capsys, SERVE)
    assert line["correct"] is False
    assert "served_logit_gap" in failing(line)
