#!/usr/bin/env python
"""Long-context causal-LM training with sequence parallelism.

A transformer whose attention runs SHARDED OVER THE SEQUENCE on the 'sp'
mesh axis — the context no single chip could hold. Two interchangeable
strategies (pick with --sp-strategy):

  ring     parallel.ring_attention — K/V shards rotate via lax.ppermute,
           n ICI hops, O(T/n · T/n) score memory, no head-count constraint
  ulysses  parallel.ulysses_attention — two all_to_alls re-lay sequence
           shards as head shards, exact dense attention per head group;
           fewer hops, needs heads % sp == 0

Everything else (embeddings, MLPs, loss, Adam update) operates on the
sequence-sharded activations directly; the whole step compiles to ONE
donated-buffer XLA program.

Run on 8 virtual devices:
  JAX_PLATFORMS=cpu \
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  python examples/train_long_context.py --sp-strategy ring
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

import mxnet_tpu as mx
from mxnet_tpu import parallel
from mxnet_tpu.parallel import P


def init_params(key, vocab, d, heads, layers, scale=0.02):
    ks = jax.random.split(key, 2 + 4 * layers)
    p = {"embed": jax.random.normal(ks[0], (vocab, d)) * scale,
         "unembed": jax.random.normal(ks[1], (d, vocab)) * scale,
         "layers": []}
    for i in range(layers):
        k0, k1, k2, k3 = ks[2 + 4 * i: 6 + 4 * i]
        p["layers"].append({
            "qkv": jax.random.normal(k0, (d, 3 * d)) * scale,
            "proj": jax.random.normal(k1, (d, d)) * scale,
            "up": jax.random.normal(k2, (d, 4 * d)) * scale,
            "down": jax.random.normal(k3, (4 * d, d)) * scale,
        })
    return p


def build_forward(mesh, heads, attn_fn):
    def fwd(params, tok):
        # tok (B, T) sharded over T; embedding lookup is local per shard
        x = jnp.take(params["embed"], tok, axis=0)        # (B, T, D)
        B, T, D = x.shape
        hd = D // heads
        for lp in params["layers"]:
            h = x - x.mean(-1, keepdims=True)
            h = h / jnp.sqrt((h * h).mean(-1, keepdims=True) + 1e-5)
            qkv = h @ lp["qkv"]                           # (B, T, 3D)
            q, k, v = jnp.split(qkv, 3, axis=-1)
            def heads_first(t):
                return t.reshape(B, T, heads, hd).transpose(0, 2, 1, 3)
            a = attn_fn(heads_first(q), heads_first(k), heads_first(v),
                        mesh, causal=True)                # (B, H, T, hd)
            a = a.transpose(0, 2, 1, 3).reshape(B, T, D)
            x = x + a @ lp["proj"]
            h = x - x.mean(-1, keepdims=True)
            h = h / jnp.sqrt((h * h).mean(-1, keepdims=True) + 1e-5)
            x = x + jax.nn.gelu(h @ lp["up"]) @ lp["down"]
        return x @ params["unembed"]                      # (B, T, V)
    return fwd


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sp-strategy", choices=["ring", "ulysses"],
                    default="ring")
    ap.add_argument("--seq", type=int, default=0,
                    help="context length (default 256 per sp shard)")
    ap.add_argument("--steps", type=int, default=10)
    args = ap.parse_args()

    n = len(jax.devices())
    mesh = parallel.make_mesh({"sp": n})
    # scale width with the mesh so heads==sp divides both d and the ulysses
    # head requirement for ANY device count (12, 6, ... included)
    heads = n
    d = max(128, 16 * heads)
    d += (-d) % heads  # round up to a multiple of heads (e.g. n=6 → d=132)
    vocab, layers = 512, 2
    T = args.seq or 256 * n
    B = 2
    print("mesh sp=%d  context T=%d  strategy=%s" % (n, T, args.sp_strategy))

    attn = (parallel.ring_attention if args.sp_strategy == "ring"
            else parallel.ulysses_attention)
    fwd = build_forward(mesh, heads, attn)

    key = jax.random.PRNGKey(0)
    params = init_params(key, vocab, d, heads, layers)
    opt = mx.optimizer.Adam(learning_rate=3e-4)
    init_states, apply_opt = parallel.tree_optimizer_step(opt)

    flat, tree = jax.tree_util.tree_flatten(params)
    states = init_states(flat)

    seq_sharding = NamedSharding(mesh, P(None, "sp"))

    def loss_fn(flat_params, tok, target):
        p = jax.tree_util.tree_unflatten(tree, flat_params)
        logits = fwd(p, tok).astype(jnp.float32)
        lp = jax.nn.log_softmax(logits, -1)
        nll = -jnp.take_along_axis(lp, target[..., None], -1)
        return nll.mean()

    import functools

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(flat_params, states, t, tok, target):
        loss, grads = jax.value_and_grad(loss_fn)(flat_params, tok, target)
        new_p, new_s = apply_opt(flat_params, grads, states,
                                 jnp.float32(3e-4), jnp.float32(0.0), t)
        return new_p, new_s, loss

    rng = np.random.default_rng(0)
    data = rng.integers(0, vocab, (B, T + 1))
    tok = jax.device_put(jnp.asarray(data[:, :-1], jnp.int32), seq_sharding)
    tgt = jax.device_put(jnp.asarray(data[:, 1:], jnp.int32), seq_sharding)

    t0 = time.perf_counter()
    for i in range(args.steps):
        flat, states, loss = step(flat, states, jnp.int32(i + 1), tok, tgt)
    loss = float(loss)
    dt = time.perf_counter() - t0
    print("%d steps, final loss %.4f, %.1f tok/s"
          % (args.steps, loss, args.steps * B * T / dt))
    assert np.isfinite(loss)


if __name__ == "__main__":
    main()
