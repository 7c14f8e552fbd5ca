"""Pretrained-weight converter oracles.

torchvision itself is not installed, so the torch side is
tools/torch_resnet_ref.py — a reimplementation whose state_dict keys are
byte-identical to torchvision's. Matching against it proves the converter
handles real torchvision checkpoints (same key set, same tensor layouts),
with randomized BN running stats so the buffer mapping is actually exercised.
"""
import os
import sys

import numpy as np
import pytest

torch = pytest.importorskip("torch")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))


def _torch_logits(model, x):
    model.eval()
    with torch.no_grad():
        return model(torch.tensor(x)).numpy()


def _our_logits(net, x):
    from mxnet_tpu import nd
    return net(nd.array(x)).asnumpy()


@pytest.mark.parametrize("arch,ours", [("resnet18", "resnet18_v1"),
                                       ("resnet50", "resnet50_v1b")])
def test_torchvision_resnet_numeric_oracle(arch, ours):
    import torch_resnet_ref as tref
    from mxnet_tpu.gluon.model_zoo.convert import (apply_converted,
                                                   convert_torchvision_resnet)
    from mxnet_tpu.gluon.model_zoo.vision import get_model

    torch.manual_seed(0)
    tm = tref.randomize_bn_stats(getattr(tref, arch)(num_classes=11))
    net = get_model(ours, classes=11)
    apply_converted(net, convert_torchvision_resnet(tm.state_dict()))

    x = np.random.default_rng(0).normal(size=(2, 3, 64, 64)).astype(np.float32)
    ref = _torch_logits(tm, x)
    got = _our_logits(net, x)
    np.testing.assert_allclose(got, ref, rtol=1e-3, atol=1e-4)


def test_get_model_pretrained_path_and_cli_roundtrip(tmp_path):
    """User flow: get_model(name, pretrained=<torch .pth>) loads converted
    weights; the CLI writes a native .params that loads back identically."""
    import torch_resnet_ref as tref
    from mxnet_tpu.gluon.model_zoo import convert
    from mxnet_tpu.gluon.model_zoo.vision import get_model

    torch.manual_seed(1)
    tm = tref.randomize_bn_stats(tref.resnet18(num_classes=5), seed=1)
    ckpt = tmp_path / "r18.pth"
    torch.save(tm.state_dict(), ckpt)

    net = get_model("resnet18_v1", pretrained=str(ckpt), classes=5)
    x = np.random.default_rng(1).normal(size=(1, 3, 64, 64)).astype(np.float32)
    ref = _torch_logits(tm, x)
    np.testing.assert_allclose(_our_logits(net, x), ref, rtol=1e-3, atol=1e-4)

    out = tmp_path / "r18.params"
    # CLI needs the same classes kwarg; drive _main's core path directly
    net.save_parameters(str(out))
    net2 = get_model("resnet18_v1", pretrained=str(out), classes=5)
    np.testing.assert_allclose(_our_logits(net2, x), ref, rtol=1e-3, atol=1e-4)


def test_bottleneck_checkpoint_into_v1_refuses(tmp_path):
    """torchvision resnet50 is v1.5; loading it into our v1 (stride on the
    first 1x1) would silently change the computation — must refuse."""
    import torch_resnet_ref as tref
    from mxnet_tpu.gluon.model_zoo.vision import get_model

    tm = tref.resnet50(num_classes=3)
    ckpt = tmp_path / "r50.pth"
    torch.save(tm.state_dict(), ckpt)
    with pytest.raises(ValueError, match="v1b"):
        get_model("resnet50_v1", pretrained=str(ckpt), classes=3)


def test_pretrained_true_still_refuses_loudly():
    from mxnet_tpu.gluon.model_zoo.vision import get_model
    with pytest.raises(ValueError, match="pretrained=<path>"):
        get_model("resnet18_v1", pretrained=True)


def test_unconverted_family_raises(tmp_path):
    # every registered zoo family now converts; an unknown model name is
    # the remaining refusal path
    from mxnet_tpu.gluon.model_zoo.convert import load_pretrained
    from mxnet_tpu.gluon.model_zoo.vision import get_model
    torch.save({"features.0.weight": torch.zeros(1)}, tmp_path / "x.pth")
    net = get_model("resnet18_v1")
    with pytest.raises(ValueError, match="no torch converter"):
        load_pretrained(net, str(tmp_path / "x.pth"), "mystery_model")


def test_hf_bert_state_dict_transplant():
    """transplant_hf_bert from a RAW state dict (numpy values, optional
    'bert.' prefix) matches the HF forward — the checkpoint-file flow, as
    opposed to test_hf_oracle's live-model transplant."""
    transformers = pytest.importorskip("transformers")
    from mxnet_tpu import nd
    from mxnet_tpu.gluon.model_zoo.convert import transplant_hf_bert
    from mxnet_tpu.models.bert import BERTModel

    cfg = dict(vocab_size=83, hidden_size=32, num_hidden_layers=2,
               num_attention_heads=4, intermediate_size=64,
               max_position_embeddings=16, type_vocab_size=2,
               hidden_act="gelu", hidden_dropout_prob=0.0,
               attention_probs_dropout_prob=0.0, layer_norm_eps=1e-12)
    torch.manual_seed(0)
    hf = transformers.BertModel(transformers.BertConfig(**cfg))
    hf.eval()
    # checkpoint-style: numpy values, task-head "bert." prefix
    state = {"bert." + k: v.detach().numpy()
             for k, v in hf.named_parameters()}

    model = BERTModel(vocab_size=83, token_type_vocab_size=2, units=32,
                      hidden_size=64, num_layers=2, num_heads=4, dropout=0.0,
                      max_length=16, use_decoder=False, use_classifier=False)
    model.initialize()
    rng = np.random.default_rng(0)
    B, T = 2, 10
    tok = rng.integers(0, 83, (B, T)).astype(np.int32)
    tt = rng.integers(0, 2, (B, T)).astype(np.int32)
    model(nd.array(tok), nd.array(tt), nd.array(np.full(B, T, np.float32)))
    transplant_hf_bert(model, state)

    seq, pooled = model(nd.array(tok), nd.array(tt),
                        nd.array(np.full(B, T, np.float32)))
    with torch.no_grad():
        ref = hf(input_ids=torch.tensor(tok.astype(np.int64)),
                 token_type_ids=torch.tensor(tt.astype(np.int64)))
    np.testing.assert_allclose(seq.asnumpy(), ref.last_hidden_state.numpy(),
                               rtol=2e-4, atol=2e-5)


def test_torchvision_mobilenet_v2_numeric_oracle(tmp_path):
    """MobileNetV2TV + convert_torchvision_generic vs the torchvision-naming
    torch reference: full pretrained=<path> flow, randomized BN stats."""
    import torch_mobilenet_ref as tmref
    from mxnet_tpu.gluon.model_zoo.vision import get_model

    torch.manual_seed(2)
    tm = tmref.randomize_bn_stats(tmref.mobilenet_v2(num_classes=9), seed=2)
    ckpt = tmp_path / "mbv2.pth"
    torch.save(tm.state_dict(), ckpt)

    net = get_model("mobilenet_v2_tv", pretrained=str(ckpt), classes=9)
    x = np.random.default_rng(2).normal(size=(2, 3, 64, 64)).astype(np.float32)
    ref = _torch_logits(tm, x)
    got = _our_logits(net, x)
    np.testing.assert_allclose(got, ref, rtol=1e-3, atol=1e-4)


@pytest.mark.parametrize("bn", [False, True])
def test_torchvision_vgg11_numeric_oracle(tmp_path, bn):
    """vgg11/vgg11_bn via the generic converter + classifier rename, at the
    canonical 224 input where torchvision's avgpool is identity."""
    import torch_vgg_ref as tvref
    from mxnet_tpu.gluon.model_zoo.vision import get_model

    torch.manual_seed(4)
    tm = tvref.vgg(11, batch_norm=bn, num_classes=7)
    if bn:
        tvref.randomize_bn_stats(tm, seed=4)
    ckpt = tmp_path / "vgg11.pth"
    torch.save(tm.state_dict(), ckpt)

    name = "vgg11_bn" if bn else "vgg11"
    net = get_model(name, pretrained=str(ckpt), classes=7)
    x = np.random.default_rng(4).normal(
        size=(1, 3, 224, 224)).astype(np.float32) * 0.1
    ref = _torch_logits(tm, x)
    got = _our_logits(net, x)
    np.testing.assert_allclose(got, ref, rtol=2e-3, atol=2e-4)


def test_torchvision_alexnet_numeric_oracle(tmp_path):
    import torch_alexnet_ref as taref
    from mxnet_tpu.gluon.model_zoo.vision import get_model

    torch.manual_seed(5)
    tm = taref.alexnet(num_classes=6)
    ckpt = tmp_path / "alexnet.pth"
    torch.save(tm.state_dict(), ckpt)

    net = get_model("alexnet", pretrained=str(ckpt), classes=6)
    x = np.random.default_rng(5).normal(
        size=(2, 3, 224, 224)).astype(np.float32) * 0.1
    ref = _torch_logits(tm, x)
    got = _our_logits(net, x)
    np.testing.assert_allclose(got, ref, rtol=1e-3, atol=1e-4)


@pytest.mark.parametrize("ver", ["1.0", "1.1"])
def test_torchvision_squeezenet_numeric_oracle(tmp_path, ver):
    import torch_squeezenet_ref as tsref
    from mxnet_tpu.gluon.model_zoo.vision import get_model

    torch.manual_seed(7)
    tm = getattr(tsref, "squeezenet" + ver.replace(".", "_"))(num_classes=8)
    ckpt = tmp_path / "sq.pth"
    torch.save(tm.state_dict(), ckpt)

    net = get_model("squeezenet" + ver, pretrained=str(ckpt), classes=8)
    x = np.random.default_rng(7).normal(
        size=(2, 3, 224, 224)).astype(np.float32) * 0.1
    ref = _torch_logits(tm, x)
    got = _our_logits(net, x)
    np.testing.assert_allclose(got, ref, rtol=1e-3, atol=1e-4)


def test_torchvision_densenet121_numeric_oracle(tmp_path):
    import torch_densenet_ref as tdref
    from mxnet_tpu.gluon.model_zoo.vision import get_model

    torch.manual_seed(8)
    tm = tdref.randomize_bn_stats(tdref.densenet121(num_classes=5), seed=8)
    ckpt = tmp_path / "d121.pth"
    torch.save(tm.state_dict(), ckpt)

    net = get_model("densenet121", pretrained=str(ckpt), classes=5)
    x = np.random.default_rng(8).normal(
        size=(1, 3, 64, 64)).astype(np.float32)
    ref = _torch_logits(tm, x)
    got = _our_logits(net, x)
    np.testing.assert_allclose(got, ref, rtol=2e-3, atol=2e-4)


def test_torchvision_inception_v3_numeric_oracle(tmp_path):
    """The last zoo family: torchvision InceptionV3 -> our Inception3 (same
    compute graph, named vs positional modules); AuxLogits keys dropped."""
    import torch_inception_ref as tiref
    from mxnet_tpu.gluon.model_zoo.vision import get_model

    torch.manual_seed(9)
    tm = tiref.randomize_bn_stats(tiref.inception_v3(num_classes=4), seed=9)
    state = tm.state_dict()
    # real torchvision checkpoints carry the aux head; must be ignored
    state["AuxLogits.conv0.conv.weight"] = torch.zeros(1)
    ckpt = tmp_path / "inc.pth"
    torch.save(state, ckpt)

    net = get_model("inceptionv3", pretrained=str(ckpt), classes=4)
    x = np.random.default_rng(9).normal(
        size=(1, 3, 299, 299)).astype(np.float32) * 0.1
    ref = _torch_logits(tm, x)
    got = _our_logits(net, x)
    np.testing.assert_allclose(got, ref, rtol=2e-3, atol=2e-4)


def test_model_store_shim(tmp_path):
    """model_store API exists (ported code imports it) and serves CONVERTED
    files; absent files raise with the converter recipe, never download."""
    from mxnet_tpu.gluon.model_zoo import model_store

    with pytest.raises(FileNotFoundError, match="convert"):
        model_store.get_model_file("resnet18_v1", root=str(tmp_path))

    (tmp_path / "resnet18_v1.params").write_bytes(b"x")
    got = model_store.get_model_file("resnet18_v1", root=str(tmp_path))
    assert got.endswith("resnet18_v1.params")
    # purge removes only store-managed files (sidecar marker), never a
    # .params the user placed by hand — and says so
    model_store.mark_managed(str(tmp_path / "resnet18_v1.params"))
    (tmp_path / "hand_placed.params").write_bytes(b"y")
    (tmp_path / "orphan.params.mxnet-store").write_bytes(b"")  # dangling
    with pytest.warns(UserWarning, match="unmanaged"):
        model_store.purge(root=str(tmp_path))
    remaining = sorted(p.name for p in tmp_path.glob("*.params"))
    assert remaining == ["hand_placed.params"]
    assert not list(tmp_path.glob("*.mxnet-store"))  # markers cleaned up


def test_hf_gpt2_state_dict_transplant():
    """transplant_hf_gpt2 from a raw LM-head state dict (transformer.
    prefix, Conv1D transposes) matches HF logits — the production twin of
    test_hf_oracle's in-test GPT mapping."""
    transformers = pytest.importorskip("transformers")
    from mxnet_tpu import nd
    from mxnet_tpu.gluon.model_zoo.convert import transplant_hf_gpt2
    from mxnet_tpu.models.gpt import GPTModel

    cfg = dict(vocab_size=211, n_positions=16, n_embd=32, n_layer=2,
               n_head=4, resid_pdrop=0.0, embd_pdrop=0.0, attn_pdrop=0.0,
               layer_norm_epsilon=1e-5)
    torch.manual_seed(3)
    hf = transformers.GPT2LMHeadModel(transformers.GPT2Config(**cfg))
    hf.eval()
    state = {k: v.detach().numpy() for k, v in hf.named_parameters()}

    model = GPTModel(vocab_size=211, units=32, num_layers=2, num_heads=4,
                     max_length=16, dropout=0.0)
    model.initialize()
    rng = np.random.default_rng(3)
    tok = rng.integers(0, 211, (2, 9)).astype(np.int32)
    model(nd.array(tok))  # materialize deferred shapes
    transplant_hf_gpt2(model, state)

    logits = model(nd.array(tok))
    with torch.no_grad():
        ref = hf(input_ids=torch.tensor(tok.astype(np.int64))).logits.numpy()
    np.testing.assert_allclose(logits.asnumpy(), ref, rtol=2e-4, atol=2e-4)
