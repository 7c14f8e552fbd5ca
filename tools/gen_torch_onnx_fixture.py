#!/usr/bin/env python
"""Generate THIRD-PARTY ONNX fixtures with torch's TorchScript exporter.

The exporter's graph construction and protobuf serialization are torch C++
code — a genuinely external producer for validating our importer. The
only part skipped is `_add_onnxscript_fn`, an optional post-processing step that needs the `onnx` pip package (not in this image)
and is a no-op for models without onnxscript custom functions.

Writes tests/fixtures/torch_cnn.onnx (+ .npz with the exact input and
torch's eval-mode output for numeric matching).

Run: python tools/gen_torch_onnx_fixture.py
"""
import os

import numpy as np
import torch
import torch.nn as nn

from torch.onnx._internal.torchscript_exporter import onnx_proto_utils

onnx_proto_utils._add_onnxscript_fn = lambda model_bytes, custom_opsets: model_bytes

FIXDIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tests", "fixtures")


class SmallCNN(nn.Module):
    """Conv/BN/pool/linear mix covering the common official-producer ops
    (Conv, BatchNormalization, Relu, MaxPool, GlobalAveragePool via mean,
    Gemm, Flatten, Add residual)."""

    def __init__(self):
        super().__init__()
        self.c1 = nn.Conv2d(3, 8, 3, padding=1)
        self.b1 = nn.BatchNorm2d(8)
        self.c2 = nn.Conv2d(8, 8, 3, padding=1)
        self.c3 = nn.Conv2d(8, 16, 3, stride=2, padding=1)
        self.fc1 = nn.Linear(16, 32)
        self.fc2 = nn.Linear(32, 10)

    def forward(self, x):
        h = torch.relu(self.b1(self.c1(x)))
        h = torch.relu(self.c2(h) + h)          # residual Add
        h = torch.relu(self.c3(h))
        h = torch.nn.functional.max_pool2d(h, 2)
        h = h.mean(dim=(2, 3))                  # ReduceMean
        h = torch.relu(self.fc1(h))
        return torch.log_softmax(self.fc2(h), dim=1)


def main():
    os.makedirs(FIXDIR, exist_ok=True)
    torch.manual_seed(0)
    net = SmallCNN()
    # distinct BN affine + running stats: a fresh BN has weight==running_var
    # (ones) and bias==running_mean (zeros), which torch's exporter dedupes
    # into Identity aliases — burn in real stats so every tensor is unique
    with torch.no_grad():
        net.b1.weight.mul_(1.5).add_(0.1)
        for _ in range(3):
            net(torch.randn(4, 3, 16, 16))
    net = net.eval()
    x = torch.randn(2, 3, 16, 16)
    with torch.no_grad():
        y = net(x)
    path = os.path.join(FIXDIR, "torch_cnn.onnx")
    # folding disabled: keep the BatchNormalization node (and its running
    # stats as initializers) in the file so the importer's arg/aux split
    # is exercised, rather than letting torch fold BN into the conv
    torch.onnx.export(net, (x,), path, dynamo=False, opset_version=13,
                      do_constant_folding=False,
                      input_names=["input"], output_names=["output"])
    np.savez(os.path.join(FIXDIR, "torch_cnn.npz"),
             x=x.numpy(), y=y.numpy())
    print("wrote", path, os.path.getsize(path), "bytes")


if __name__ == "__main__":
    main()
