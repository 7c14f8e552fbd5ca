"""gluon.contrib.estimator: fit loop + event-handler family
(ref: upstream tests/python/unittest/test_gluon_estimator.py,
test_gluon_event_handler.py)."""
import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.gluon import nn, loss as gloss
from mxnet_tpu.gluon.contrib.estimator import (
    CheckpointHandler, EarlyStoppingHandler, Estimator, LoggingHandler,
    MetricHandler, StoppingHandler, ValidationHandler)


def _toy_data(n=32, d=8, classes=3, batch=8, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, d)).astype(np.float32)
    y = rng.integers(0, classes, n)
    return [(nd.array(x[i:i + batch]), nd.array(y[i:i + batch]))
            for i in range(0, n, batch)]


def _toy_net(classes=3):
    net = nn.Sequential()
    net.add(nn.Dense(16, activation="relu"), nn.Dense(classes))
    net.initialize()
    return net


def _estimator(**kw):
    net = _toy_net()
    return Estimator(net, gloss.SoftmaxCrossEntropyLoss(),
                     train_metrics=mx.metric.Accuracy(), **kw), net


def test_fit_runs_and_tracks_metrics():
    est, _ = _estimator()
    out = est.fit(_toy_data(), epochs=2)
    (name, acc), = out
    assert name == "accuracy" and 0.0 <= acc <= 1.0
    assert est.current_epoch == 1


def test_loss_decreases_over_epochs():
    est, _ = _estimator()
    data = _toy_data(n=64)
    first = est.evaluate(data, metrics=mx.metric.Loss())
    est.fit(data, epochs=8)
    last = est.evaluate(data, metrics=mx.metric.Loss())
    assert last[0][1] < first[0][1]


def test_validation_handler_epoch_period(capsys):
    est, _ = _estimator()
    calls = []
    vh = ValidationHandler(_toy_data(seed=1),
                           lambda d: calls.append(est.evaluate(d)),
                           epoch_period=2)
    est.fit(_toy_data(), epochs=4, event_handlers=[vh])
    assert len(calls) == 2  # epochs 1 and 3


def test_validation_handler_batch_period():
    est, _ = _estimator()
    calls = []
    vh = ValidationHandler(_toy_data(seed=1),
                           lambda d: calls.append(1),
                           epoch_period=None, batch_period=3)
    est.fit(_toy_data(), epochs=1, event_handlers=[vh])  # 4 batches
    assert len(calls) == 1


def test_default_validation_handler_populates_val_metrics():
    est, _ = _estimator()
    est.fit(_toy_data(), val_data=_toy_data(seed=1), epochs=1)
    assert est.val_metrics \
        and est.val_metrics[0].get()[0] == "validation accuracy"
    assert est.val_metrics[0].num_inst > 0


def test_stopping_handler_max_batch():
    est, _ = _estimator()
    seen = []

    class Counter:
        def batch_end(self, estimator, batch=None):
            seen.append(estimator.current_batch)

    est.fit(_toy_data(), epochs=100, event_handlers=[Counter()], batches=6)
    assert len(seen) == 6


def test_early_stopping_patience(tmp_path):
    est, _ = _estimator()

    class Worsen(MetricHandler):
        """Overwrite the monitored metric with a worsening series."""

        def __init__(self):
            pass

        def epoch_begin(self, estimator):
            pass

        def batch_end(self, estimator, batch=None):
            m = estimator.train_metrics[0]
            m.reset()
            m.sum_metric = -float(estimator.current_epoch)
            m.num_inst = 1

    h = EarlyStoppingHandler(monitor="accuracy", patience=2, mode="max")
    est.fit(_toy_data(), epochs=50, event_handlers=[Worsen(), h])
    # epoch 0 sets best=0; epochs 1,2 worsen -> stop at epoch 2
    assert est.current_epoch == 2
    assert h.stopped_epoch == 2


def test_early_stopping_min_delta():
    est, _ = _estimator()

    class Flat(MetricHandler):
        def __init__(self):
            pass

        def epoch_begin(self, estimator):
            pass

        def batch_end(self, estimator, batch=None):
            m = estimator.train_metrics[0]
            m.reset()
            # tiny improvements below min_delta must not reset patience
            m.sum_metric = 1.0 + 1e-6 * estimator.current_epoch
            m.num_inst = 1

    h = EarlyStoppingHandler(monitor="accuracy", patience=3, mode="max",
                             min_delta=0.01)
    est.fit(_toy_data(), epochs=50, event_handlers=[Flat(), h])
    assert est.current_epoch == 3


def test_checkpoint_handler_rotation_and_best(tmp_path):
    import os
    est, net = _estimator()
    ch = CheckpointHandler(str(tmp_path), model_prefix="m", save_best=True,
                           monitor="accuracy", mode="max", max_checkpoints=2)
    est.fit(_toy_data(), epochs=5, event_handlers=[ch])
    files = sorted(os.listdir(tmp_path))
    epochs = [f for f in files if "epoch" in f and f.endswith(".params")]
    assert len(epochs) == 2  # rotated down to max_checkpoints
    assert "m-best.params" in files


def test_checkpoint_resume(tmp_path):
    est, net = _estimator()
    ch = CheckpointHandler(str(tmp_path), model_prefix="m")
    est.fit(_toy_data(), epochs=1, event_handlers=[ch])
    # structural keys ('0.weight') are instance-independent — the whole
    # point of _collect_params_with_prefix save format
    ref = {k: v.data().asnumpy()
           for k, v in net._collect_params_with_prefix().items()}

    est2, net2 = _estimator()
    ch2 = CheckpointHandler(str(tmp_path), model_prefix="m",
                            resume_from_checkpoint=True)
    # zero-epoch fit still fires train_begin -> load
    est2.fit(_toy_data(), epochs=0, event_handlers=[ch2])
    for k, v in net2._collect_params_with_prefix().items():
        np.testing.assert_allclose(v.data().asnumpy(), ref[k], rtol=1e-6)


def test_validation_runs_before_user_handlers_each_epoch():
    """EarlyStopping monitoring 'validation accuracy' must see THIS epoch's
    validation value (no NaN poisoning at epoch 0)."""
    est, _ = _estimator()
    h = EarlyStoppingHandler(monitor="validation accuracy", patience=3,
                             mode="max")
    est.fit(_toy_data(), val_data=_toy_data(seed=1), epochs=4,
            event_handlers=[h])
    assert h.best is not None and h.best == h.best  # a real number, not NaN


def test_checkpoint_resume_numeric_epoch_sort(tmp_path):
    import os
    est, net = _estimator()
    ch = CheckpointHandler(str(tmp_path), model_prefix="m",
                           max_checkpoints=20)
    est.fit(_toy_data(), epochs=12, event_handlers=[ch])
    assert os.path.exists(tmp_path / "m-epoch11.params")
    ref = {k: v.data().asnumpy()
           for k, v in net._collect_params_with_prefix().items()}

    est2, net2 = _estimator()
    ch2 = CheckpointHandler(str(tmp_path), model_prefix="m",
                            resume_from_checkpoint=True)
    est2.fit(_toy_data(), epochs=0, event_handlers=[ch2])
    # must have loaded epoch11 (the newest), not lexicographic epoch9
    for k, v in net2._collect_params_with_prefix().items():
        np.testing.assert_allclose(v.data().asnumpy(), ref[k], rtol=1e-6)


def test_batch_period_checkpoints_rotate(tmp_path):
    import os
    est, _ = _estimator()
    ch = CheckpointHandler(str(tmp_path), model_prefix="m", epoch_period=None,
                           batch_period=1, max_checkpoints=3)
    est.fit(_toy_data(), epochs=3, event_handlers=[ch])  # 12 batch saves
    files = [f for f in os.listdir(tmp_path) if f.endswith(".params")]
    assert len(files) == 3


def test_save_parameters_deduplicate_shared_params(tmp_path):
    """deduplicate=True writes a shared Parameter once; load restores it to
    every alias."""
    from mxnet_tpu.gluon import nn as gnn
    d1 = gnn.Dense(6, in_units=6)
    d2 = gnn.Dense(6, in_units=6, params=d1.params)
    net = gnn.HybridSequential()
    net.add(d1, d2)
    net.initialize()
    f = str(tmp_path / "w.params")
    net.save_parameters(f, deduplicate=True)
    saved = np.load(f)
    assert len(saved.files) == 2  # one weight + one bias, not four

    d1b = gnn.Dense(6, in_units=6)
    d2b = gnn.Dense(6, in_units=6, params=d1b.params)
    net2 = gnn.HybridSequential()
    net2.add(d1b, d2b)
    net2.initialize()
    net2.load_parameters(f)
    x = _toy_data(n=2, d=6, batch=2)[0][0]
    np.testing.assert_allclose(net(x).asnumpy(), net2(x).asnumpy(), rtol=1e-6)


def test_checkpoint_resume_continues_epoch_numbering(tmp_path):
    """A resumed run's saves must sort after the run it resumed from, and
    resume must restore trainer (optimizer) state, not just params."""
    import os
    est, net = _estimator()
    ch = CheckpointHandler(str(tmp_path), model_prefix="m", max_checkpoints=20)
    est.fit(_toy_data(), epochs=3, event_handlers=[ch])  # epoch0..2

    est2, net2 = _estimator()
    ch2 = CheckpointHandler(str(tmp_path), model_prefix="m",
                            max_checkpoints=20, resume_from_checkpoint=True)
    est2.fit(_toy_data(), epochs=2, event_handlers=[ch2])
    files = sorted(os.listdir(tmp_path))
    # run 2's two epochs saved as epoch3/epoch4, not epoch0/epoch1 again
    assert "m-epoch3.params" in files and "m-epoch4.params" in files
    assert "m-epoch2.params" in files  # run 1's newest still present
    # trainer states were restored: adam's update counter advanced past 0
    assert est2.trainer._optimizer.num_update > len(_toy_data()) * 2


def test_fit_empty_loader_stops(recwarn):
    est, _ = _estimator()
    import warnings as _w
    with _w.catch_warnings(record=True) as rec:
        _w.simplefilter("always")
        est.fit([], batches=10)  # 2^30-epoch sentinel must not spin
    assert any("no batches" in str(w.message) for w in rec)


def test_load_parameters_cast_dtype_saved(tmp_path):
    """cast_dtype with dtype_source='saved' casts the NET to the file's
    dtype (upstream semantics)."""
    net = _toy_net()
    net(nd.array(np.zeros((1, 8), np.float32)))  # materialize deferred shapes
    net.cast("float16")
    f = str(tmp_path / "w.params")
    net.save_parameters(f)

    net2 = _toy_net()  # float32
    net2(nd.array(np.zeros((1, 8), np.float32)))
    net2.load_parameters(f, cast_dtype=True, dtype_source="saved")
    for p in net2.collect_params().values():
        assert p.data().dtype == np.float16


def test_logging_handler_prints(capsys):
    est, _ = _estimator()
    est.fit(_toy_data(), epochs=1,
            event_handlers=[LoggingHandler(log_interval=2)])
    out = capsys.readouterr().out
    assert "samples/s" in out and "epoch 0 done" in out


def test_logging_epoch_only(capsys):
    est, _ = _estimator()
    est.fit(_toy_data(), epochs=1,
            event_handlers=[LoggingHandler(log_interval="epoch")])
    out = capsys.readouterr().out
    assert "samples/s" not in out and "epoch 0 done" in out

def test_default_monitor_prefers_validation_metric():
    """monitor=None must track a VALIDATION metric when one has a value:
    save-best/early-stop on a train metric rewards overfitting."""
    from mxnet_tpu.gluon.contrib.estimator import _monitored_value

    est, _ = _estimator()
    # train acc deliberately 0.0 so val (1.0) is distinguishable below
    est.train_metrics[0].update(nd.array([1, 1]), nd.array(np.eye(3)[[0, 0]]))
    # no validation configured at all -> train metric is the only candidate
    name, _ = _monitored_value(est, None, "test")
    assert name == est.train_metrics[0].get()[0]

    # validation configured but not yet run (NaN) -> train stands in,
    # loudly (one-time warning), never silently for the whole run
    import warnings as _w
    est.val_metrics = [mx.metric.Accuracy()]
    with _w.catch_warnings(record=True) as rec:
        _w.simplefilter("always")
        name, val = _monitored_value(est, None, "test")
    assert name == est.train_metrics[0].get()[0] and val == 0.0
    assert any("TRAIN metric" in str(r.message) for r in rec)

    est.val_metrics[0].update(nd.array([1, 2]), nd.array(np.eye(3)[[1, 2]]))
    name, val = _monitored_value(est, None, "test")
    assert name == est.val_metrics[0].get()[0]
    assert val == 1.0

    # explicit monitor still finds train metrics
    tname = est.train_metrics[0].get()[0]
    name, val = _monitored_value(est, tname, "test")
    assert name == tname
