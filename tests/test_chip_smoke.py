"""chip_smoke.py: its phases at tiny sizes on the CPU, and its refusal to
pass without a chip. The kernel-presence checks are off here (the CPU takes
the jnp branches); tests/test_tpu_compile.py compiles the kernels."""
import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

VOCAB = 512


def _tiny_bert():
    from mxnet_tpu.models.bert import BERTModel

    return BERTModel(vocab_size=VOCAB, units=128, hidden_size=256,
                     num_layers=2, num_heads=2, max_length=32)


def test_phase_train_tiny():
    out = chip_smoke.phase_train(_tiny_bert, vocab=VOCAB, batch=8, seq=32,
                                 masked=4, steps=5, gluon_batch=4, seed=0,
                                 check_kernels=False)
    assert len(out["losses"]) == 5 and out["losses"][-1] < out["losses"][0]
    assert len(out["gluon_losses"]) == 2


def test_phase_serve_tiny():
    from mxnet_tpu.models.gpt import GPTModel

    def tiny_gpt():
        return GPTModel(vocab_size=VOCAB, units=128, num_layers=2,
                        num_heads=2, max_length=128, dropout=0.0)

    out = chip_smoke.phase_serve(tiny_gpt, vocab=VOCAB,
                                 prompt_lens=(5, 12, 40, 70), new_tokens=8,
                                 slots=4, seed=0, compare=(0, 3))
    assert out["compiles_wave2"] == 0
    assert [len(t) for t in out["tokens"]] == [8] * 4


def test_phase_imperative_on_cpu():
    import mxnet_tpu as mx

    assert chip_smoke.phase_imperative(mx.cpu(), seed=0)["ops"] >= 12


def test_phase_train_dp_on_four_virtual_devices():
    out = chip_smoke.phase_train_dp(_tiny_bert, vocab=VOCAB, batch=8, seq=32,
                                    masked=4, steps=5, seed=0,
                                    devices=jax.devices()[:4])
    assert len(out["mesh_losses"]) == len(out["single_losses"]) == 5


def test_phase_dist_attach_on_four_virtual_devices():
    out = chip_smoke.phase_dist_attach(jax.devices()[:4], seed=0)
    assert len(out["attached"]) == len(out["plain"])


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_chip_smoke_fails_at_once_without_a_chip(argv):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")]
                       + argv, capture_output=True, text=True, timeout=120,
                       env=env, cwd=REPO)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "needs a TPU" in r.stderr
