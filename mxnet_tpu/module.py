"""Module API adapter (ref: python/mxnet/module/module.py).

The legacy Module trains a Symbol graph. Here Module binds the Symbol to a
jitted executor; SoftmaxOutput heads get their MXNet training semantics
(backward = softmax - one_hot(label)) by constructing the cross-entropy loss
over the head's logits.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import initializer as init_mod
from . import metric as metric_mod
from . import optimizer as opt_mod
from .context import current_context
from .ndarray import NDArray
from .symbol import Symbol

__all__ = ["Module", "BucketingModule", "SequentialModule"]


class Module:
    def __init__(self, symbol, data_names=("data",), label_names=("softmax_label",),
                 context=None, logger=None):
        self._symbol = symbol
        self._data_names = list(data_names)
        self._label_names = list(label_names or [])
        self._ctx = context or current_context()
        self._exec = None
        self._arg_params = {}
        self._optimizer = None
        self._opt_states = {}
        self._n_main_outputs = 1
        self._aux_update_names = []
        self._pred_pool = None
        self.binded = False
        self.params_initialized = False

    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, **kwargs):
        if self.binded and not force_rebind:
            return
        shapes = {}
        for name, shape in data_shapes:
            shapes[name] = tuple(shape)
        for name, shape in (label_shapes or []):
            shapes[name] = tuple(shape)
        self._data_shapes = shapes
        self._for_training = for_training
        self._inputs_need_grad = inputs_need_grad
        self._exec = None
        self._pred_pool = None  # rebind invalidates the inference pool
        self.binded = True

    def init_params(self, initializer=None, arg_params=None, aux_params=None,
                    allow_missing=False, force_init=False, allow_extra=False):
        assert self.binded
        if arg_params is None and getattr(self, "_preloaded_params", None):
            # Module.load stashed the checkpoint's params for bind time
            pre_arg, pre_aux = self._preloaded_params
            arg_params = dict(pre_arg)
            arg_params.update(pre_aux or {})
        initializer = initializer or init_mod.Uniform(0.01)
        arg_names = self._symbol.list_arguments()
        # infer parameter shapes from data shapes via eval_shape with zeros
        inferred = self._infer_param_shapes()
        for n in arg_names:
            if n in self._data_names or n in self._label_names:
                continue
            if arg_params and n in arg_params:
                self._arg_params[n] = arg_params[n]
                continue
            arr = NDArray(jnp.zeros(inferred[n], jnp.float32))
            initializer(init_mod.InitDesc(n), arr)
            self._arg_params[n] = arr
            arr.attach_grad()
        self._pred_pool = None  # pool captures param objects; re-resolve
        self.params_initialized = True

    def _infer_param_shapes(self):
        """Infer every argument's shape from the bound data/label shapes —
        graph shape inference (ref: src/executor/graph_executor.cc infer
        pass), so params need no declared shape= on their variables."""
        from .shape_inference import format_infer_errors, infer_shapes_partial

        known = dict(self._data_shapes)
        var_shapes, _, errors = infer_shapes_partial(self._symbol, known)
        missing = [n for n, s in var_shapes.items() if s is None]
        if missing:
            raise ValueError(
                "shape inference could not determine %s from data shapes %s; "
                "declare shape= on those variables%s"
                % (missing, known, format_infer_errors(errors)))
        return var_shapes

    def forward(self, data_batch, is_train=None):
        if is_train is None:
            is_train = getattr(self, "_for_training", True)
        feed = {}
        for name, arr in zip(self._data_names, data_batch.data):
            feed[name] = arr
        if data_batch.label:
            for name, arr in zip(self._label_names, data_batch.label):
                feed[name] = arr
        self._last_feed = feed
        if self._exec is None:
            args = dict(self._arg_params)
            for n in self._data_names + self._label_names:
                if n in feed:
                    args[n] = feed[n]
            grads = {n: NDArray(jnp.zeros_like(a._data))
                     for n, a in self._arg_params.items()}
            if getattr(self, "_inputs_need_grad", False):
                for n in self._data_names:
                    a = feed[n]
                    d = a._data if isinstance(a, NDArray) else jnp.asarray(a)
                    grads[n] = NDArray(jnp.zeros_like(d))
            self._exec = self._bn_aux_symbol().bind(self._ctx, args, grads)
        self._exec.forward(is_train=bool(is_train), **feed)
        outs = self._exec.outputs
        n_main = self._n_main_outputs
        if is_train and len(outs) > n_main:
            # BatchNorm aux write-back (upstream: executor aux_states are
            # copied back after each training forward): the hidden
            # new-moving-mean/var outputs land in the bound moving vars
            # IN PLACE, so the next forward (and eval mode) sees them
            for name, new in zip(self._aux_update_names, outs[n_main:]):
                self._arg_params[name]._data = new._data
        return outs[:n_main]

    def _bn_aux_symbol(self):
        """Wrap the bound symbol so each BatchNorm's hidden updated-stat
        outputs are fetched alongside the main outputs (ref:
        src/executor/graph_executor.cc aux-state write-back)."""
        from .symbol import Group, Symbol, _attr_symbols

        self._aux_update_names = []
        # a _group's head count is its input list (Symbol._n_outputs stays
        # at the constructor default for groups)
        self._n_main_outputs = len(self._symbol._inputs) \
            if self._symbol._op == "_group" else 1
        items, seen, stack = [], set(), [self._symbol]
        while stack:
            s = stack.pop()
            if id(s) in seen or not isinstance(s, Symbol):
                continue
            seen.add(id(s))
            if (s._op == "BatchNorm" and len(s._inputs) >= 5
                    and s._inputs[3].is_var() and s._inputs[4].is_var()):
                items.append(Symbol("_item", [s], {"index": 1},
                                    name=s.name + "_mm_upd"))
                items.append(Symbol("_item", [s], {"index": 2},
                                    name=s.name + "_mv_upd"))
                self._aux_update_names += [s._inputs[3].name,
                                           s._inputs[4].name]
            stack.extend(s._inputs)
            stack.extend(_attr_symbols(s._attrs))
        if not items:
            return self._symbol
        mains = ([self._symbol[i] for i in range(self._n_main_outputs)]
                 if self._symbol._op == "_group" else [self._symbol])
        return Group(mains + items)

    def backward(self, out_grads=None):
        if out_grads is None and self._symbol._op == "SoftmaxOutput":
            # MXNet semantics: d(logits) = softmax - one_hot(label). The
            # probs are a mandated output of the head, so the grad from them
            # is already a single elementwise pass — the same one-pass
            # backward the fused pallas xent kernel (ops/pallas/softmax_xent)
            # achieves by reconstructing p from its saved lse. one_hot via
            # iota-compare, NOT .at[].set(): scatter is a serialized op on
            # TPU, the compare fuses into the subtract.
            prob = self._exec.outputs[0]._data
            label = self._last_feed[self._label_names[0]]
            label = label._data if isinstance(label, NDArray) else jnp.asarray(label)
            cols = jax.lax.broadcasted_iota(jnp.int32, prob.shape, prob.ndim - 1)
            onehot = (cols == label.astype(jnp.int32)[:, None]).astype(prob.dtype)
            grad = (prob - onehot) / prob.shape[0]
            out_grads = [NDArray(grad)]
        elif out_grads is None:
            out_grads = [NDArray(jnp.ones(o.shape, o.dtype))
                         for o in self._exec.outputs[:self._n_main_outputs]]
        elif isinstance(out_grads, NDArray):
            out_grads = [out_grads]
        out_grads = list(out_grads)
        if len(out_grads) < self._n_main_outputs:
            raise ValueError("backward needs %d output gradients, got %d"
                             % (self._n_main_outputs, len(out_grads)))
        # aux stat fetches are NOT differentiated through (upstream treats
        # aux states as non-gradient): zero cotangents for the tail ONLY
        out_grads += [NDArray(jnp.zeros(o.shape, o.dtype))
                      for o in self._exec.outputs[len(out_grads):]]
        self._exec.backward(out_grads)

    def get_outputs(self):
        return self._exec.outputs[:self._n_main_outputs]

    def get_input_grads(self):
        """(ref: module/base_module.py:get_input_grads) — requires
        bind(inputs_need_grad=True)."""
        assert getattr(self, "_inputs_need_grad", False), \
            "bind with inputs_need_grad=True"
        return [self._exec.grad_dict[n] for n in self._data_names]

    def init_optimizer(self, kvstore="local", optimizer="sgd", optimizer_params=None,
                       force_init=False):
        optimizer_params = optimizer_params or {"learning_rate": 0.01}
        self._optimizer = (optimizer if isinstance(optimizer, opt_mod.Optimizer)
                           else opt_mod.create(optimizer, **optimizer_params))

    def update(self):
        aux = set(self._aux_update_names)
        for i, (n, p) in enumerate(sorted(self._arg_params.items())):
            # aux states (BN moving stats) are written back by forward, not
            # optimized — an optimizer step (esp. weight decay) would erode
            # the statistics (upstream excludes aux from updates)
            if n in aux:
                continue
            g = self._exec.grad_dict.get(n)
            if g is None:
                continue
            if i not in self._opt_states:
                self._opt_states[i] = self._optimizer.create_state(i, p)
            self._opt_states[i] = self._optimizer.update(i, p, g, self._opt_states[i])

    def fit(self, train_data, eval_data=None, eval_metric="accuracy",
            num_epoch=1, optimizer="sgd", optimizer_params=None,
            initializer=None, batch_end_callback=None, **kwargs):
        """(ref: module/base_module.py:fit)"""
        if not self.binded:
            first = next(iter(train_data))
            train_data.reset()
            self.bind([(n, tuple(a.shape)) for n, a in zip(self._data_names, first.data)],
                      [(n, tuple(a.shape)) for n, a in zip(self._label_names, first.label or [])])
        if not self.params_initialized:
            self.init_params(initializer)
        self.init_optimizer(optimizer=optimizer, optimizer_params=optimizer_params)
        em = metric_mod.create(eval_metric)
        for epoch in range(num_epoch):
            em.reset()
            train_data.reset()
            for batch in train_data:
                self.forward_backward(batch)
                self.update()
                # pad-aware like score: the SAME metric over the SAME data
                # must agree between the fit loop and score()
                outs, labels = self._strip_pad(batch, self.get_outputs(),
                                               list(batch.label or []))
                em.update(labels, outs)
        return em.get()

    # -- BaseModule conveniences (ref: module/base_module.py) ---------------

    @property
    def symbol(self):
        return self._symbol

    @property
    def data_names(self):
        return list(self._data_names)

    @property
    def output_names(self):
        return self._symbol.list_outputs()

    @property
    def data_shapes(self):
        from .io import DataDesc
        return [DataDesc(n, self._data_shapes[n]) for n in self._data_names
                if n in getattr(self, "_data_shapes", {})]

    @property
    def label_shapes(self):
        from .io import DataDesc
        return [DataDesc(n, self._data_shapes[n]) for n in self._label_names
                if n in getattr(self, "_data_shapes", {})]

    @property
    def output_shapes(self):
        _, outs, _ = self._symbol.infer_shape(
            **{n: s for n, s in getattr(self, "_data_shapes", {}).items()})
        return list(zip(self.output_names, outs))

    def forward_backward(self, data_batch):
        """(ref: base_module.py:forward_backward)"""
        self.forward(data_batch, is_train=True)
        self.backward()

    def update_metric(self, eval_metric, labels, pre_sliced=False):
        """(ref: base_module.py:update_metric). All labels pair with all
        main outputs (EvalMetric.update zips lists); pre_sliced flattens
        upstream's per-device label slices."""
        if not isinstance(labels, (list, tuple)):
            labels = [labels]
        elif pre_sliced:
            labels = [l for sl in labels for l in
                      (sl if isinstance(sl, (list, tuple)) else [sl])]
        eval_metric.update(list(labels), self.get_outputs())

    @staticmethod
    def _strip_pad(batch, outs, labels):
        """Drop an iterator's wrap-around rows so metrics don't
        double-count them (predict strips identically)."""
        pad = getattr(batch, "pad", 0) or 0
        if not pad:
            return outs, labels
        outs = [NDArray(o._data[:o.shape[0] - pad]) for o in outs]
        labels = [NDArray(l._data[:l.shape[0] - pad]) for l in labels]
        return outs, labels

    def _predict_pool(self):
        """Shared bucketed inference executor (serve.executor_pool) for
        predict/score-style eval: ONE compiled program at the bound-batch
        bucket serves every batch — including the iterator's final padded
        partial batch, which the bound executor used to retrace at its
        smaller shape. Returns (pool, input_names), or (None, None) when
        the graph isn't poolable (stochastic eval graph, missing params) —
        the per-batch forward path serves those."""
        if self._pred_pool is not None:
            return self._pred_pool
        from .serve.executor_pool import BucketedExecutor, symbol_infer_fn

        self._pred_pool = (None, None)
        shapes = getattr(self, "_data_shapes", None)
        if shapes and self.params_initialized:
            arg_names = set(self._symbol.list_arguments())
            input_names = [n for n in self._data_names + self._label_names
                           if n in arg_names and n in shapes]
            fn, pnames = symbol_infer_fn([self._symbol], input_names)
            if fn is not None and all(n in self._arg_params for n in pnames):
                plist = [self._arg_params[n] for n in pnames]

                def params_fn():
                    return [p._data for p in plist]

                bucket = shapes[self._data_names[0]][0]
                self._pred_pool = (
                    BucketedExecutor(fn, params_fn, buckets=(bucket,),
                                     name="module.predict"), input_names)
        return self._pred_pool

    def _pool_batch_inputs(self, batch, input_names, rows):
        """Assemble predict-pool inputs from a DataBatch; absent labels
        (predict on unlabeled iterators) feed zeros at the bound shape —
        eval outputs can't depend on them row-wise."""
        feed = dict(zip(self._data_names, batch.data))
        if batch.label:
            feed.update(zip(self._label_names, batch.label))
        ins = []
        for n in input_names:
            a = feed.get(n)
            if a is None:
                ins.append(np.zeros((rows,) + tuple(self._data_shapes[n][1:]),
                                    np.float32))
            else:
                ins.append(a.asnumpy() if isinstance(a, NDArray)
                           else np.asarray(a))
        return ins

    def predict(self, eval_data, num_batch=None, merge_batches=True,
                reset=True, always_output_list=False):
        """(ref: base_module.py:predict) — run inference over an iterator,
        concatenating per-batch outputs along axis 0. Deterministic graphs
        route through the shared bucketed executor pool (one compiled
        program for all batches, partial final batch padded); others fall
        back to the per-batch bound-executor forward."""
        if reset and hasattr(eval_data, "reset"):
            eval_data.reset()
        pool, input_names = self._predict_pool()
        per_batch = []  # list over batches of the (pad-stripped) output list
        for i, batch in enumerate(eval_data):
            if num_batch is not None and i >= num_batch:
                break
            pad = getattr(batch, "pad", 0) or 0
            if pool is not None:
                from .serve.executor_pool import PoolError

                rows = batch.data[0].shape[0]
                try:
                    ins = self._pool_batch_inputs(batch, input_names, rows)
                    outs = pool.run(ins, n_real=rows - pad)
                except PoolError:  # e.g. a batch wider than the bound bucket
                    outs = None
                if outs is not None and pool.row_aligned:
                    per_batch.append([NDArray(o) for o in outs])
                    continue
                # outputs don't carry the batch on axis 0 (or the batch
                # doesn't fit the bucket): padding is not sliceable —
                # disable the pool and recompute via forward
                pool = None
                self._pred_pool = (None, None)
            self.forward(batch, is_train=False)
            outs = self.get_outputs()
            if pad:
                outs = [NDArray(o._data[:o.shape[0] - pad]) for o in outs]
            per_batch.append(outs)
        if not per_batch:
            return []
        if not merge_batches:
            # upstream contract: a list over batches (each a list of outputs)
            return per_batch
        merged = [NDArray(jnp.concatenate([outs[j]._data
                                           for outs in per_batch], axis=0))
                  for j in range(len(per_batch[0]))]
        if len(merged) == 1 and not always_output_list:
            return merged[0]
        return merged

    def score(self, eval_data, eval_metric, num_batch=None, reset=True):
        """(ref: base_module.py:score)"""
        em = metric_mod.create(eval_metric)
        em.reset()
        if reset and hasattr(eval_data, "reset"):
            eval_data.reset()
        for i, batch in enumerate(eval_data):
            if num_batch is not None and i >= num_batch:
                break
            self.forward(batch, is_train=False)
            outs, labels = self._strip_pad(batch, self.get_outputs(),
                                           list(batch.label or []))
            em.update(labels, outs)
        return em.get_name_value()

    _AUX_SUFFIXES = ("moving_mean", "moving_var", "running_mean",
                     "running_var")

    def _is_aux(self, name):
        return name in getattr(self, "_aux_update_names", ()) \
            or name.endswith(self._AUX_SUFFIXES)

    def get_params(self):
        """(arg_params, aux_params) with BN moving stats in the AUX dict —
        the upstream split (executors store them as aux_states); internally
        they live in _arg_params for the forward write-back."""
        args = {n: v for n, v in self._arg_params.items()
                if not self._is_aux(n)}
        aux = {n: v for n, v in self._arg_params.items() if self._is_aux(n)}
        return args, aux

    def set_params(self, arg_params, aux_params=None, allow_missing=False,
                   force_init=True, allow_extra=False):
        """Write values IN PLACE: the bound executor's arg_dict holds the
        same NDArray objects as _arg_params (bind shares, forward reads
        arg_dict), so replacing dict entries after bind would be a silent
        no-op for subsequent forwards — upstream set_params writes through
        to the executors (ref: module/module.py:set_params).

        ``allow_extra=False`` rejects names the module doesn't know (a typo
        would otherwise land in a dead dict entry no executor reads);
        ``allow_missing=False`` requires every module parameter present."""
        given = dict(arg_params or {})
        given.update(aux_params or {})
        known = set(self._arg_params)  # snapshot BEFORE mutating in the loop
        if not known:
            # pre-bind there is nothing to validate names against, so a
            # typo'd name cannot be caught and would become a dead dict
            # entry — warn LOUDLY while keeping the documented
            # pre-bind flow (values apply at bind time)
            import warnings

            warnings.warn(
                "set_params before bind/init_params: parameter names cannot "
                "be validated against the module — a misspelled name would "
                "be silently unused; prefer binding first")
            for n, v in given.items():
                self._arg_params[n] = v if isinstance(v, NDArray) \
                    else NDArray(jnp.asarray(v))
            return
        extra = sorted(set(given) - known)
        if extra and not allow_extra:
            raise ValueError(
                "set_params: unknown parameter(s) %s (module has %s...); "
                "pass allow_extra=True to ignore"
                % (extra[:5], sorted(known)[:5]))
        missing = sorted(known - set(given))
        if missing and not allow_missing:
            raise ValueError(
                "set_params: missing parameter(s) %s; pass "
                "allow_missing=True to keep current values"
                % (missing[:5],))
        kept = []
        for n, v in given.items():
            if n not in known:
                continue  # allow_extra: ignored, like upstream
            new = v._data if isinstance(v, NDArray) else jnp.asarray(v)
            cur = self._arg_params[n]
            if tuple(new.shape) != tuple(cur._data.shape):
                raise ValueError(
                    "set_params: %r has shape %s; module expects %s"
                    % (n, tuple(new.shape), tuple(cur._data.shape)))
            if not force_init:
                kept.append(n)
            else:
                cur._data = new.astype(cur._data.dtype)
        if kept:
            import warnings
            warnings.warn("set_params: force_init=False kept %d already-"
                          "initialized parameter(s) (e.g. %r)"
                          % (len(kept), kept[0]))

    def save_checkpoint(self, prefix, epoch):
        """prefix-symbol.json + prefix-NNNN.params, the mx.model layout
        (ref: module/module.py:save_checkpoint)."""
        from . import model as _model
        arg, aux = self.get_params()
        _model.save_checkpoint(prefix, epoch, self._symbol, arg, aux)

    @staticmethod
    def load(prefix, epoch, data_names=("data",), label_names=("softmax_label",),
             context=None, **kwargs):
        """Rebuild a Module from a save_checkpoint layout
        (ref: module/module.py:Module.load). Params apply at bind time."""
        from . import model as _model
        sym, arg, aux = _model.load_checkpoint(prefix, epoch)
        mod = Module(sym, data_names, label_names, context, **kwargs)
        mod._preloaded_params = (arg, aux)
        return mod


class BucketingModule(Module):
    """(ref: module/bucketing_module.py) — per-bucket executors; each bucket is
    one jit cache entry keyed by its shapes, so XLA recompiles per bucket
    exactly like MXNet rebinds per bucket."""

    def __init__(self, sym_gen, default_bucket_key=None, context=None, **kwargs):
        self._sym_gen = sym_gen
        self._default_key = default_bucket_key
        sym, data_names, label_names = sym_gen(default_bucket_key)
        super().__init__(sym, data_names, label_names, context)
        self._buckets = {}
        self._curr_module = None

    def switch_bucket(self, bucket_key, data_shapes=None):
        if bucket_key not in self._buckets:
            sym, data_names, label_names = self._sym_gen(bucket_key)
            m = Module(sym, data_names, label_names, self._ctx)
            # buckets share weights, optimizer, and optimizer state — one
            # model, several compiled shapes (ref: bucketing_module.py:
            # shared_module binding)
            m._arg_params = self._arg_params
            m._opt_states = self._opt_states
            self._buckets[bucket_key] = m
        m = self._buckets[bucket_key]
        m._optimizer = getattr(self, "_optimizer", None)
        self._curr_module = m
        return m

    def forward(self, data_batch, is_train=None):
        """Route by the batch's bucket_key; each bucket is a cached compiled
        executor (ref: bucketing_module.py:forward)."""
        key = getattr(data_batch, "bucket_key", None)
        key = self._default_key if key is None else key
        m = self.switch_bucket(key)
        return m.forward(data_batch, is_train)

    def _predict_pool(self):
        # bucketing modules pick their graph per batch (bucket_key), so a
        # single pooled program can't serve predict — per-bucket executors
        # already are the bucketed cache here
        return None, None

    def backward(self, out_grads=None):
        self._curr_module.backward(out_grads)

    def update(self):
        self._curr_module.update()

    def get_outputs(self):
        return self._curr_module.get_outputs()

    @property
    def _exec(self):
        # fit()/metrics read outputs via self._exec — route to the bucket
        # module currently bound (base __init__'s write lands in __dict__
        # via the setter below, used only before the first forward)
        if getattr(self, "_curr_module", None) is not None:
            return self._curr_module._exec
        return self.__dict__.get("_exec_base")

    @_exec.setter
    def _exec(self, v):
        self.__dict__["_exec_base"] = v


class SequentialModule:
    """Chain of Modules where module i's outputs feed module i+1's data
    (ref: python/mxnet/module/sequential_module.py). Intermediate modules
    bind with ``inputs_need_grad=True`` so the backward pass hands each
    stage's input grads to the stage before it as ``out_grads``."""

    def __init__(self, logger=None):
        self._modules = []
        self._take_labels = []
        self.binded = False
        self.params_initialized = False

    def add(self, module, take_labels=False):
        if self.binded:
            raise RuntimeError("add() after bind()")
        self._modules.append(module)
        self._take_labels.append(bool(take_labels))
        return self

    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, **kwargs):
        if self.binded and not force_rebind:
            return
        assert self._modules, "add() at least one module before bind()"
        cur = [(n, tuple(s)) for n, s in data_shapes]
        for i, m in enumerate(self._modules):
            lab = label_shapes if self._take_labels[i] else None
            need = inputs_need_grad if i == 0 else for_training
            m.bind(cur, lab, for_training=for_training,
                   inputs_need_grad=need, force_rebind=force_rebind)
            # next stage's data shapes = this stage's inferred output shapes
            feed = dict(cur)
            if lab:
                feed.update({n: tuple(s) for n, s in lab})
            if i + 1 < len(self._modules):
                _, out_shapes, _ = m._symbol.infer_shape(**feed)
                nxt = self._modules[i + 1]
                if len(nxt._data_names) > len(out_shapes):
                    raise ValueError(
                        "module %d expects %d inputs but module %d emits %d "
                        "outputs" % (i + 1, len(nxt._data_names), i,
                                     len(out_shapes)))
                cur = list(zip(nxt._data_names, out_shapes))
        self._for_training = for_training
        self._inputs_need_grad = inputs_need_grad
        self.binded = True

    def init_params(self, initializer=None, arg_params=None, aux_params=None,
                    **kwargs):
        assert self.binded
        for m in self._modules:
            m.init_params(initializer=initializer, arg_params=arg_params,
                          aux_params=aux_params, allow_missing=True,
                          allow_extra=True, **{k: v for k, v in kwargs.items()
                                               if k not in ("allow_missing",
                                                            "allow_extra")})
        self.params_initialized = True

    def init_optimizer(self, **kwargs):
        for m in self._modules:
            m.init_optimizer(**kwargs)

    def forward(self, data_batch, is_train=None):
        from .io import DataBatch

        batch = data_batch
        for i, m in enumerate(self._modules):
            label = (data_batch.label
                     if self._take_labels[i] else [])
            batch = DataBatch(data=list(batch.data if i == 0
                                        else self._modules[i - 1]
                                        .get_outputs()),
                              label=label)
            m.forward(batch, is_train=is_train)
        return self._modules[-1].get_outputs()

    def backward(self, out_grads=None):
        grads = out_grads
        for i in reversed(range(len(self._modules))):
            m = self._modules[i]
            m.backward(grads)
            if i > 0:
                grads = m.get_input_grads()

    def update(self):
        for m in self._modules:
            m.update()

    def get_outputs(self):
        return self._modules[-1].get_outputs()

    def get_input_grads(self):
        assert self._inputs_need_grad
        return self._modules[0].get_input_grads()

    def get_params(self):
        arg, aux = {}, {}
        for m in self._modules:
            a, x = m.get_params()
            arg.update(a)
            aux.update(x)
        return arg, aux
