"""Generate state_dict key+shape manifests locking the converter oracles.

Two sources:
- the offline torchvision reimplementations (tools/torch_*_ref.py): their
  manifests are committed and cross-checked by hand-written structural
  anchors (tests/test_state_dict_manifests.py) drawn from the PUBLIC
  torchvision layouts, so a silent architecture divergence in a ref model
  becomes a test failure;
- the REAL HuggingFace transformers package (installed in this image):
  BERT/GPT-2 manifests come from genuine `transformers` models built from
  config (no download), which locks transplant_hf_bert/gpt2 to the real key
  set, not a reimplementation.

Usage: python tools/gen_state_dict_manifests.py  (writes
tests/fixtures/state_dict_manifests/*.json; rerun + commit when a ref
model legitimately changes)
"""
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
OUT_DIR = os.path.join(REPO, "tests", "fixtures", "state_dict_manifests")
sys.path.insert(0, HERE)


def manifest_of(model):
    return {k: list(v.shape) for k, v in model.state_dict().items()}


def torchvision_manifests():
    import torch_alexnet_ref as A
    import torch_densenet_ref as D
    import torch_inception_ref as I
    import torch_mobilenet_ref as M
    import torch_resnet_ref as R
    import torch_squeezenet_ref as S
    import torch_vgg_ref as V

    return {
        "resnet18": manifest_of(R.resnet18()),
        "resnet34": manifest_of(R.resnet34()),
        "resnet50": manifest_of(R.resnet50()),
        "vgg16": manifest_of(V.vgg(16)),
        "vgg16_bn": manifest_of(V.vgg(16, batch_norm=True)),
        "alexnet": manifest_of(A.alexnet()),
        "squeezenet1_0": manifest_of(S.squeezenet1_0()),
        "squeezenet1_1": manifest_of(S.squeezenet1_1()),
        "densenet121": manifest_of(D.densenet121()),
        "inception_v3": manifest_of(I.inception_v3()),
        "mobilenet_v2": manifest_of(M.mobilenet_v2()),
    }


def hf_manifests():
    from transformers import BertConfig, BertModel, GPT2Config, GPT2LMHeadModel

    bert = BertModel(BertConfig())          # bert-base-uncased architecture
    gpt2 = GPT2LMHeadModel(GPT2Config())    # gpt2 (124M) architecture
    return {"hf_bert_base": manifest_of(bert),
            "hf_gpt2": manifest_of(gpt2)}


def main():
    os.makedirs(OUT_DIR, exist_ok=True)
    manifests = {}
    manifests.update(torchvision_manifests())
    manifests.update(hf_manifests())
    for name, man in manifests.items():
        path = os.path.join(OUT_DIR, "%s.json" % name)
        with open(path, "w") as f:
            json.dump(man, f, indent=0, sort_keys=True)
        print("wrote %s (%d keys)" % (path, len(man)))


if __name__ == "__main__":
    main()
