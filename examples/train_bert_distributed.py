#!/usr/bin/env python
"""BERT pretraining with the compiled distributed train step (dp × tp mesh).

Demonstrates the performance path described in SURVEY.md §3.4-3.5: the whole
step (forward, backward, gradient psum over 'dp' riding ICI, Adam update) is
one donated-buffer XLA program; parameters shard over 'tp' via the
TRANSFORMER_RULES name-pattern specs.

Run on N virtual devices:
  JAX_PLATFORMS=cpu \
  XLA_FLAGS=--xla_force_host_platform_device_count=8 python examples/train_bert_distributed.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

import mxnet_tpu as mx
from mxnet_tpu import _trace, parallel
from mxnet_tpu.models.bert import BERTModel
from mxnet_tpu.parallel import P
from mxnet_tpu.parallel.tensor_parallel import TRANSFORMER_RULES, spec_for


def main(steps=10):
    n = len(jax.devices())
    tp = 2 if n % 2 == 0 else 1
    mesh = parallel.make_mesh({"dp": -1, "tp": tp})
    print("mesh:", dict(mesh.shape))

    bert = BERTModel(vocab_size=1024, units=128, hidden_size=512, num_layers=2,
                     num_heads=4, max_length=64, dropout=0.1)
    bert.initialize()
    plist = list(bert.collect_params().values())
    specs = [spec_for(p.name, p.shape, TRANSFORMER_RULES, mesh) for p in plist]
    params = [jax.device_put(p.data()._data, NamedSharding(mesh, s))
              for p, s in zip(plist, specs)]

    opt = mx.optimizer.Adam(learning_rate=1e-3)
    init_states, apply_opt = parallel.tree_optimizer_step(opt)
    states = init_states(params)

    def loss_fn(param_arrays, batch, key):
        tok, mp, mlm_y = batch
        with _trace.trace_scope(key, True) as t:
            t.param_store = {id(p): a for p, a in zip(plist, param_arrays)}
            _, _, _, mlm = bert._call_traced(tok, None, None, mp)
        lp = jax.nn.log_softmax(mlm.astype(jnp.float32), axis=-1)
        return jnp.mean(-jnp.take_along_axis(lp, mlm_y[..., None], axis=-1))

    @jax.jit
    def step(params, states, t, key, batch):
        # traced under the mesh, as parallel.build_train_step does: the ops
        # see what the program is compiled for (a Mosaic kernel cannot be
        # partitioned, so they take their XLA formulations)
        with parallel.use_mesh(mesh):
            loss, grads = jax.value_and_grad(loss_fn)(params, batch, key)
        new_p, new_s = apply_opt(params, grads, states, jnp.float32(1e-3),
                                 jnp.float32(0.0), t)
        return new_p, new_s, loss

    rng = np.random.default_rng(0)
    B = 4 * mesh.shape["dp"]
    for i in range(steps):
        batch = (
            jax.device_put(jnp.asarray(rng.integers(0, 1024, (B, 64)), jnp.int32),
                           NamedSharding(mesh, P("dp"))),
            jax.device_put(jnp.asarray(rng.integers(0, 64, (B, 8)), jnp.int32),
                           NamedSharding(mesh, P("dp"))),
            jax.device_put(jnp.asarray(rng.integers(0, 1024, (B, 8)), jnp.int32),
                           NamedSharding(mesh, P("dp"))),
        )
        params, states, loss = step(params, states, jnp.int32(i + 1),
                                    jax.random.PRNGKey(i), batch)
        print("step %d loss %.4f" % (i, float(loss)))


if __name__ == "__main__":
    main()
