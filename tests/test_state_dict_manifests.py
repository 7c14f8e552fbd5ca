"""Key+shape manifests lock the converter oracles to reality.

The offline torchvision reimplementations (tools/torch_*_ref.py) claim
byte-identical state_dict keys to torchvision; the committed manifests under
tests/fixtures/state_dict_manifests/ pin that claim three ways:

1. regenerating each ref model must match its committed manifest
   name-for-name and shape-for-shape (drift in a ref becomes a failure);
2. hand-written STRUCTURAL ANCHORS — public torchvision facts (layer names,
   classifier shapes, aux heads, block counts) written down independently of
   the ref code — must appear in the manifests (a ref that drifted from
   torchvision WITH its manifest still fails here);
3. the HF manifests are generated from the REAL transformers package (built
   from config, no download), so the BERT/GPT-2 transplant key sets are the
   genuine article.
"""
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MAN_DIR = os.path.join(REPO, "tests", "fixtures", "state_dict_manifests")
sys.path.insert(0, os.path.join(REPO, "tools"))

torch = pytest.importorskip("torch")


def _load(name):
    with open(os.path.join(MAN_DIR, "%s.json" % name)) as f:
        return json.load(f)


def _check(model, name):
    got = {k: list(v.shape) for k, v in model.state_dict().items()}
    want = _load(name)
    assert set(got) == set(want), (
        name, sorted(set(got) ^ set(want))[:10])
    mismatched = {k: (got[k], want[k]) for k in got if got[k] != want[k]}
    assert not mismatched, (name, dict(list(mismatched.items())[:5]))


def test_torchvision_refs_match_manifests():
    import torch_alexnet_ref as A
    import torch_densenet_ref as D
    import torch_inception_ref as I
    import torch_mobilenet_ref as M
    import torch_resnet_ref as R
    import torch_squeezenet_ref as S
    import torch_vgg_ref as V

    _check(R.resnet18(), "resnet18")
    _check(R.resnet34(), "resnet34")
    _check(R.resnet50(), "resnet50")
    _check(V.vgg(16), "vgg16")
    _check(V.vgg(16, batch_norm=True), "vgg16_bn")
    _check(A.alexnet(), "alexnet")
    _check(S.squeezenet1_0(), "squeezenet1_0")
    _check(S.squeezenet1_1(), "squeezenet1_1")
    _check(D.densenet121(), "densenet121")
    _check(I.inception_v3(), "inception_v3")
    _check(M.mobilenet_v2(), "mobilenet_v2")


# Public torchvision structural facts, written independently of the ref
# code: (manifest, key, shape). Shapes use torchvision conventions
# (Conv OIHW, Linear (out,in)).
_ANCHORS = [
    ("resnet18", "conv1.weight", [64, 3, 7, 7]),
    ("resnet18", "layer4.1.bn2.running_var", [512]),
    ("resnet18", "fc.weight", [1000, 512]),
    ("resnet50", "layer1.0.downsample.0.weight", [256, 64, 1, 1]),
    ("resnet50", "layer3.5.conv3.weight", [1024, 256, 1, 1]),
    ("resnet50", "fc.weight", [1000, 2048]),
    ("vgg16", "features.28.weight", [512, 512, 3, 3]),
    ("vgg16", "classifier.6.weight", [1000, 4096]),
    ("vgg16_bn", "features.41.running_mean", [512]),
    ("alexnet", "features.10.weight", [256, 256, 3, 3]),
    ("alexnet", "classifier.6.weight", [1000, 4096]),
    ("squeezenet1_0", "features.12.expand3x3.weight", [256, 64, 3, 3]),
    ("squeezenet1_0", "classifier.1.weight", [1000, 512, 1, 1]),
    ("squeezenet1_1", "features.12.expand3x3.weight", [256, 64, 3, 3]),
    ("densenet121", "features.denseblock4.denselayer16.conv2.weight",
     [32, 128, 3, 3]),
    ("densenet121", "features.norm5.running_mean", [1024]),
    ("densenet121", "classifier.weight", [1000, 1024]),
    ("inception_v3", "Conv2d_1a_3x3.conv.weight", [32, 3, 3, 3]),
    ("inception_v3", "AuxLogits.fc.weight", [1000, 768]),  # the aux head
    ("inception_v3", "Mixed_7c.branch_pool.conv.weight", [192, 2048, 1, 1]),
    ("inception_v3", "fc.weight", [1000, 2048]),
    ("mobilenet_v2", "features.18.1.running_mean", [1280]),
    ("mobilenet_v2", "classifier.1.weight", [1000, 1280]),
    ("mobilenet_v2", "features.1.conv.0.0.weight", [32, 1, 3, 3]),
    # HF (generated from the real transformers package, but anchor anyway)
    ("hf_bert_base", "embeddings.word_embeddings.weight", [30522, 768]),
    ("hf_bert_base", "encoder.layer.11.output.dense.weight", [768, 3072]),
    ("hf_gpt2", "transformer.h.11.attn.c_attn.weight", [768, 2304]),
    ("hf_gpt2", "transformer.wte.weight", [50257, 768]),
]


def test_structural_anchors_present():
    for man_name, key, shape in _ANCHORS:
        man = _load(man_name)
        assert key in man, (man_name, key)
        assert man[key] == shape, (man_name, key, man[key], shape)


def test_hf_manifests_match_real_transformers():
    transformers = pytest.importorskip("transformers")
    from transformers import (BertConfig, BertModel, GPT2Config,
                              GPT2LMHeadModel)

    bert = {k: list(v.shape)
            for k, v in BertModel(BertConfig()).state_dict().items()}
    assert bert == _load("hf_bert_base")
    gpt2 = {k: list(v.shape)
            for k, v in GPT2LMHeadModel(GPT2Config()).state_dict().items()}
    assert gpt2 == _load("hf_gpt2")


def test_load_torch_state_dataparallel_and_fp16(tmp_path):
    """module. prefixes strip; fp16 tensors land as fp32 (converters and BN
    stats do fp32 math); int tensors (num_batches_tracked) keep dtype."""
    from mxnet_tpu.gluon.model_zoo.convert import load_torch_state

    state = {"module.conv.weight": torch.randn(4, 3, 3, 3).half(),
             "module.bn.running_mean": torch.randn(4).half(),
             "module.bn.num_batches_tracked": torch.tensor(7)}
    p = tmp_path / "dp_fp16.pth"
    torch.save({"state_dict": state}, p)
    out = load_torch_state(str(p))
    assert set(out) == {"conv.weight", "bn.running_mean",
                        "bn.num_batches_tracked"}
    assert out["conv.weight"].dtype == torch.float32
    assert out["bn.num_batches_tracked"].dtype == torch.int64
    # and a prefix-free checkpoint is untouched
    torch.save({"conv.weight": torch.randn(1, 1, 1, 1)}, p)
    assert set(load_torch_state(str(p))) == {"conv.weight"}
