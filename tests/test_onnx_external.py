"""ONNX validation against EXTERNAL artifacts:

1. a .onnx file produced by torch's TorchScript exporter (C++ graph builder
   + protobuf serializer — a genuinely third-party producer), imported and
   numerically matched against torch's own eval output;
2. the Loop importer, driven by hand-assembled spec-level protos through the
   dependency-free codec (onnx/proto.py).
"""
import os

import numpy as np
import pytest

from mxnet_tpu import nd
from mxnet_tpu import onnx as mxonnx
from mxnet_tpu.onnx import proto as P

FIXDIR = os.path.join(os.path.dirname(__file__), "fixtures")
CNN = os.path.join(FIXDIR, "torch_cnn.onnx")


@pytest.mark.skipif(not os.path.exists(CNN),
                    reason="fixture missing — run tools/gen_torch_onnx_fixture.py")
def test_torch_exported_cnn_numeric_match():
    ref = np.load(os.path.join(FIXDIR, "torch_cnn.npz"))
    blk = mxonnx.import_to_gluon(CNN)
    out = blk(nd.array(ref["x"]))
    np.testing.assert_allclose(out.asnumpy(), ref["y"], rtol=1e-4, atol=1e-5)


@pytest.mark.skipif(not os.path.exists(CNN), reason="fixture missing")
def test_torch_exported_cnn_symbol_api():
    sym, arg_params, aux_params = mxonnx.import_model(CNN)
    # BatchNorm running stats land in aux, weights in args
    assert arg_params and aux_params
    assert any("running" in k or "mean" in k or "var" in k
               for k in aux_params)


def _loop_model(M, cond_init=True):
    """Hand-assembled spec-level Loop model via the dependency-free codec:
    carried state s (f32[2]), body: s_out = s + 1; scan output = s_out;
    cond stays true. Runs M iterations -> final s = s0 + M, scan (M, 2)."""
    body = P.graph_proto(
        "body",
        nodes=[P.node_proto("Add", ["s_in", "one"], ["s_out"]),
               P.node_proto("Identity", ["cond_in"], ["cond_out"]),
               P.node_proto("Identity", ["s_out"], ["scan0"])],
        inputs=[P.value_info("iter", np.int64, ()),
                P.value_info("cond_in", np.bool_, ()),
                P.value_info("s_in", np.float32, (2,))],
        outputs=[P.value_info("cond_out", np.bool_, ()),
                 P.value_info("s_out", np.float32, (2,)),
                 P.value_info("scan0", np.float32, (2,))],
        initializers=[P.tensor_proto("one", np.ones(2, np.float32))])
    graph = P.graph_proto(
        "main",
        nodes=[P.node_proto("Loop", ["M", "cond0", "s0"],
                            ["s_final", "scan"],
                            attrs={"body": P.GraphAttr(body)})],
        inputs=[P.value_info("s0", np.float32, (2,))],
        outputs=[P.value_info("s_final", np.float32, (2,)),
                 P.value_info("scan", np.float32, (M, 2))],
        initializers=[P.tensor_proto("M", np.asarray(M, np.int64)),
                      P.tensor_proto("cond0", np.asarray(cond_init, np.bool_))])
    return P.model_proto(graph, opset=13).tobytes()


def test_loop_import_counts_and_stacks(tmp_path):
    M = 4
    path = str(tmp_path / "loop.onnx")
    with open(path, "wb") as f:
        f.write(_loop_model(M))
    blk = mxonnx.import_to_gluon(path)
    s0 = np.array([0.5, -1.0], np.float32)
    outs = blk(nd.array(s0))
    s_final, scan = (o.asnumpy() for o in outs)
    np.testing.assert_allclose(s_final, s0 + M, rtol=1e-6)
    want_scan = np.stack([s0 + i + 1 for i in range(M)])
    np.testing.assert_allclose(scan, want_scan, rtol=1e-6)


def test_loop_import_respects_initial_condition(tmp_path):
    # cond starts False -> zero iterations: state unchanged, scan all zeros
    path = str(tmp_path / "loop0.onnx")
    with open(path, "wb") as f:
        f.write(_loop_model(3, cond_init=False))
    blk = mxonnx.import_to_gluon(path)
    s0 = np.array([2.0, 3.0], np.float32)
    outs = blk(nd.array(s0))
    s_final, scan = (o.asnumpy() for o in outs)
    np.testing.assert_allclose(s_final, s0, rtol=1e-6)
    np.testing.assert_allclose(scan, np.zeros((3, 2), np.float32))


def test_checker_passes_own_exports_and_torch_file(tmp_path):
    """P.check_model structural validation over (a) the torch-produced
    fixture and (b) this repo's own exports — the spec-conformance gate
    the review asked for (onnx.checker itself is not in the image)."""
    import mxnet_tpu as mx
    from mxnet_tpu import gluon

    if os.path.exists(CNN):
        P.check_model(open(CNN, "rb").read())

    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(8, activation="relu", in_units=4),
            gluon.nn.BatchNorm(), gluon.nn.Dense(2, in_units=8))
    net.initialize()
    net(nd.ones((2, 4)))
    buf = mxonnx.export_model(net, input_shapes={"data": (2, 4)})
    P.check_model(buf)

    # and the checker actually rejects broken graphs
    bad = P.model_proto(P.graph_proto(
        "bad",
        nodes=[P.node_proto("Relu", ["nope"], ["y"])],
        inputs=[P.value_info("x", np.float32, (2,))],
        outputs=[P.value_info("y", np.float32, (2,))],
        initializers=[])).tobytes()
    with pytest.raises(ValueError, match="SSA"):
        P.check_model(bad)


def test_checker_passes_loop_model():
    P.check_model(_loop_model(3))


def test_torch_half_pixel_resize_import(tmp_path, monkeypatch):
    """A genuine torch-exported half-pixel Resize (F.interpolate) must
    import with exact numerics — while BilinearResize2D itself keeps
    MXNet's align-corners convention (two distinct resize ops)."""
    torch = pytest.importorskip("torch")
    try:
        from torch.onnx._internal.torchscript_exporter import \
            onnx_proto_utils
    except ImportError:
        pytest.skip("torch exporter internals moved")
    monkeypatch.setattr(onnx_proto_utils, "_add_onnxscript_fn",
                        lambda b, c: b)

    class Net(torch.nn.Module):
        def forward(self, t):
            return torch.nn.functional.interpolate(
                t, scale_factor=2.0, mode="bilinear", align_corners=False,
                recompute_scale_factor=False)

    net = Net().eval()
    tx = torch.randn(1, 2, 3, 4)
    with torch.no_grad():
        want = net(tx).numpy()
    path = str(tmp_path / "resize_hp.onnx")
    torch.onnx.export(net, (tx,), path, dynamo=False, opset_version=13,
                      do_constant_folding=True)
    blk = mxonnx.import_to_gluon(path)
    got = blk(nd.array(tx.numpy())).asnumpy()
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
