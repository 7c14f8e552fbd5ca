"""Testing helpers (ref: python/mxnet/test_utils.py)."""
from __future__ import annotations

import numpy as np

from .context import current_context
from .ndarray import NDArray, array


def default_context():
    return current_context()


def set_default_context(ctx):
    """(ref: test_utils.py:set_default_context) — make ``ctx`` the ambient
    default for factory calls outside explicit Context scopes."""
    from . import context as _ctx_mod

    _ctx_mod._default = ctx


def list_gpus():
    """(ref: test_utils.py:list_gpus) — TPU ordinals of this process.
    mx.gpu() is the accelerator alias here, so the standard upstream gate
    ``mx.gpu() if list_gpus() else mx.cpu()`` keeps selecting the TPU on TPU
    hosts and cpu elsewhere."""
    from .context import _accel_devices

    return list(range(len(_accel_devices())))


def _np(x):
    return x.asnumpy() if isinstance(x, NDArray) else np.asarray(x)


def assert_almost_equal(a, b, rtol=1e-5, atol=1e-8, names=("a", "b")):
    np.testing.assert_allclose(_np(a), _np(b), rtol=rtol, atol=atol)


def almost_equal(a, b, rtol=1e-5, atol=1e-8):
    return np.allclose(_np(a), _np(b), rtol=rtol, atol=atol)


def same(a, b):
    return np.array_equal(_np(a), _np(b))


def rand_ndarray(shape, dtype=np.float32, ctx=None):
    return array(np.random.randn(*shape).astype(dtype), ctx=ctx)


def rand_shape_nd(ndim, dim=10):
    return tuple(np.random.randint(1, dim + 1, size=ndim))


def check_numeric_gradient(fn, inputs, eps=1e-3, rtol=1e-2, atol=1e-3,
                           n_checks=5):
    """Finite-difference check of autograd gradients of scalar fn(*inputs)."""
    from . import autograd

    arrs = [array(_np(x)) for x in inputs]
    for a in arrs:
        a.attach_grad()
    with autograd.record():
        out = fn(*arrs)
    out.backward()

    vals = [_np(a).copy() for a in arrs]

    def eval_at(vs):
        return float(_np(fn(*[array(v) for v in vs])).sum())

    for k, a in enumerate(arrs):
        g = a.grad.asnumpy().ravel()
        flat = vals[k].ravel()
        rng = np.random.RandomState(0)
        for i in rng.choice(flat.size, size=min(n_checks, flat.size), replace=False):
            orig = flat[i]
            flat[i] = orig + eps
            fp = eval_at(vals)
            flat[i] = orig - eps
            fm = eval_at(vals)
            flat[i] = orig
            fd = (fp - fm) / (2 * eps)
            if not np.isclose(g[i], fd, rtol=rtol, atol=atol):
                raise AssertionError(
                    "gradient mismatch at input %d elem %d: autograd %g vs fd %g"
                    % (k, i, g[i], fd))
    return True


def assert_exception(f, exception_type, *args, **kwargs):
    """Assert f(*args, **kwargs) raises exception_type (ref:
    test_utils.py:assert_exception)."""
    try:
        f(*args, **kwargs)
    except exception_type:
        return
    raise AssertionError("%r did not raise %s" % (f, exception_type.__name__))


def check_symbolic_forward(sym, inputs, expected, rtol=1e-5, atol=1e-8):
    """Bind ``sym`` with positional input arrays (matched to
    list_arguments order) and compare outputs against ``expected``
    (ref: test_utils.py:check_symbolic_forward)."""
    names = sym.list_arguments()
    args = {n: array(_np(v)) for n, v in zip(names, inputs)}
    ex = sym.bind(args=args)
    outs = ex.forward()
    if not isinstance(expected, (list, tuple)):
        expected = [expected]
    assert len(outs) == len(expected), (
        "%d outputs vs %d expected values" % (len(outs), len(expected)))
    for o, e in zip(outs, expected):
        np.testing.assert_allclose(_np(o), _np(e), rtol=rtol, atol=atol)
    return outs


def check_symbolic_backward(sym, inputs, out_grads, expected_grads,
                            rtol=1e-5, atol=1e-8, grad_req="write"):
    """Forward+backward ``sym`` and compare input gradients (ref:
    test_utils.py:check_symbolic_backward)."""
    names = sym.list_arguments()
    args = {n: array(_np(v)) for n, v in zip(names, inputs)}
    grads = {n: array(np.zeros_like(_np(v))) for n, v in zip(names, inputs)}
    ex = sym.bind(args=args, args_grad=grads, grad_req=grad_req)
    ex.forward(is_train=True)
    ex.backward([array(_np(g)) for g in out_grads]
                if isinstance(out_grads, (list, tuple))
                else array(_np(out_grads)))
    if isinstance(expected_grads, dict):
        items = expected_grads.items()
    else:
        assert len(names) == len(expected_grads), (
            "%d arguments vs %d expected gradients"
            % (len(names), len(expected_grads)))
        items = zip(names, expected_grads)
    for n, e in items:
        np.testing.assert_allclose(_np(ex.grad_dict[n]), _np(e),
                                   rtol=rtol, atol=atol)
    return ex.grad_dict
