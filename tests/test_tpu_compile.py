"""The main path's Pallas kernels, compiled for a described TPU v5e at their
real widths — no chip attached, nothing runs. Interpret mode
(tests/test_kernels.py) proves the math; this proves that Mosaic accepts the
blocks, the tiling and the fast-memory use, which interpret mode cannot see.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU's library, and every xdist worker imports
every test file. All of these tests stay in this one file for the same
reason, and compile in the test's own process.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    had_log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache out of it
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", cache_was_on)
    cc.reset_cache()
    if had_log_dir is None:
        os.environ.pop("TPU_LOG_DIR", None)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled.as_text()


def _sum32(x):
    return jnp.sum(x.astype(jnp.float32))


@pytest.mark.parametrize("rows,dtype", [(8192, jnp.bfloat16),
                                        (8, jnp.bfloat16),
                                        (600, jnp.float32)])
def test_layernorm_768_compiles_fwd_bwd(one_chip, rows, dtype):
    """LayerNorm at width 768: the BERT/GPT activations (8192 rows), the
    8-slot decode step, and a 600-token eager prompt (row block 8)."""
    from mxnet_tpu.ops.pallas import layernorm as ln

    assert ln.tiles(rows, 768)
    x = jax.ShapeDtypeStruct((rows, 768), dtype, sharding=one_chip)
    g = jax.ShapeDtypeStruct((768,), dtype, sharding=one_chip)
    text = _compile(jax.value_and_grad(
        lambda x, g, b: _sum32(ln.layernorm(x, g, b, 1e-5)),
        argnums=(0, 1, 2)), x, g, g)
    assert "tpu_custom_call" in text and "layernorm_fwd" in text


def test_gates_refuse_rows_that_do_not_tile():
    """Row counts with no block that is a multiple of 8 take the jnp branch
    (601 rows of LayerNorm: Mosaic refuses a 1-row block)."""
    from mxnet_tpu.ops.pallas import layernorm as ln
    from mxnet_tpu.ops.pallas import softmax_xent as sx

    assert not ln.tiles(601, 768) and not ln.tiles(8192, 100)
    assert ln.tiles(1, 768) and ln.tiles(256, 768)
    assert sx.tiles(1280, 30522) and sx.tiles(8, 2)
    assert not sx.tiles(1281, 30522)


@pytest.mark.parametrize("rows,vocab,dtype", [(1280, 30522, jnp.bfloat16),
                                              (8192, 50257, jnp.float32)])
def test_softmax_xent_compiles_fwd_bwd(one_chip, rows, vocab, dtype):
    """The MLM loss of the BERT step (64 x 20 rows, vocab 30522) and an LM
    loss at GPT-2's vocab."""
    from mxnet_tpu.ops.pallas import softmax_xent as sx

    assert sx.tiles(rows, vocab)
    logits = jax.ShapeDtypeStruct((rows, vocab), dtype, sharding=one_chip)
    labels = jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=one_chip)
    text = _compile(lambda l, y: jax.value_and_grad(
        lambda l: jnp.sum(sx.softmax_xent(l, y)))(l), logits, labels)
    assert "softmax_xent_fwd" in text and "softmax_xent_bwd" in text


@pytest.mark.parametrize("seq,dim,dtype", [(1024, 64, jnp.bfloat16),
                                           (2048, 128, jnp.float32)])
def test_flash_causal_compiles_fwd_bwd(one_chip, seq, dim, dtype):
    """Causal flash attention at GPT-2's head width (the server's T=1024
    prefill) and at head width 128, forward and both backward kernels."""
    from mxnet_tpu.ops.pallas.flash_attention import flash_attention

    qkv = jax.ShapeDtypeStruct((8, 12, seq, dim), dtype, sharding=one_chip)
    text = _compile(jax.value_and_grad(
        lambda q, k, v: _sum32(flash_attention(q, k, v, causal=True)),
        argnums=(0, 1, 2)), qkv, qkv, qkv)
    assert all(n in text for n in ("flash_fwd", "flash_dq", "flash_dkv"))


def test_flash_valid_len_compiles_fwd_bwd(one_chip):
    """The key-padding path (BERT-style valid lengths) at (8, 12, 1024, 64)."""
    from mxnet_tpu.ops.pallas.flash_attention import flash_attention

    qkv = jax.ShapeDtypeStruct((8, 12, 1024, 64), jnp.bfloat16,
                               sharding=one_chip)
    vl = jax.ShapeDtypeStruct((8,), jnp.int32, sharding=one_chip)
    text = _compile(lambda q, k, v, vl: jax.value_and_grad(
        lambda q, k, v: _sum32(flash_attention(q, k, v, kv_valid_len=vl)),
        argnums=(0, 1, 2))(q, k, v), qkv, qkv, qkv, vl)
    assert all(n in text for n in ("flash_fwd", "flash_dq", "flash_dkv"))


@pytest.mark.parametrize("told", [False, True],
                         ids=["every_slot", "told_the_live_slots"])
@pytest.mark.parametrize("shape,dtype", [
    ((8, 12, 1024, 64), jnp.bfloat16),     # GPT-2 small, the smoke's server
    ((32, 20, 1024, 64), jnp.bfloat16),    # gpt2-large.chat-decode's pages
    ((32, 25, 1024, 64), jnp.float32),     # GPT-2 XL's heads, fp32 pages
    ((160, 4, 128, 16), jnp.bfloat16),     # more slots than lanes
    ((32, 8, 4096, 128), jnp.bfloat16),    # head width 128: a window ring
    ((32, 8, 8192, 128), jnp.float32)])    # ... and a full page, fp32
def test_kv_cache_write_compiles_in_place(one_chip, shape, dtype, told):
    """The K/V column write alone, told which slots are live or not: Mosaic
    takes the kernel's own DMA of a dynamic 128-lane window out of the
    buffer left in HBM and the lane rotate (of 16-bit values too; at head
    width 128 a window of one sublane tile of rows and the sublane select),
    the donated buffer is the result and nothing cache-sized stands beside
    it, and no ``while`` (the scatter's loop over the slots) is left."""
    import re

    from mxnet_tpu.ops.pallas import kv_write

    S, H, C, D = shape
    assert kv_write.tiles(shape, (S, H, 1, D), dtype)
    live = (jax.ShapeDtypeStruct((S,), jnp.int32, sharding=one_chip),) * told
    compiled = jax.jit(kv_write.kv_cache_write, donate_argnums=(0,)).lower(
        jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip),
        jax.ShapeDtypeStruct((S, H, 1, D), dtype, sharding=one_chip),
        jax.ShapeDtypeStruct((S,), jnp.int32, sharding=one_chip),
        *live).compile()
    assert "kv_cache_write" in compiled.as_text()
    assert not re.search(r" while\(", compiled.as_text())
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == S * H * C * D * jnp.dtype(dtype).itemsize
    assert mem.temp_size_in_bytes < 1 << 20


def test_retention_step_compiles_in_place_at_the_published_sizes(one_chip):
    """The step's kernel at Brumby-14B's sizes (32 slots, 40 query and 8 K/V
    heads of width 128: 65 rows of phi a head, 1.09 GB of state a layer):
    Mosaic takes the walk's DMA of the blocks of S and of a head's z out of
    buffers left in HBM, the lane rotations that build phi, the transposed
    float32 contraction on the matrix unit; S and z are the results
    (aliased) and nothing of their size stands beside them."""
    from mxnet_tpu.ops.pallas import retention_step as K

    slots, H, Hkv, D, rows = 32, 40, 8, 128, 65
    sd = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                   sharding=one_chip)
    assert K.tiles((slots, H, 1, D), (slots, Hkv, 1, D))
    compiled = jax.jit(K.retention_step, donate_argnums=(4, 5)).lower(
        sd((slots, H, 1, D), jnp.bfloat16),
        sd((slots, Hkv, 1, D), jnp.bfloat16),
        sd((slots, Hkv, 1, D), jnp.bfloat16),
        sd((slots, Hkv), jnp.float32),
        sd((slots, Hkv, rows * D, D), jnp.float32),
        sd((slots, Hkv, rows, D), jnp.float32),
        sd((slots,), jnp.int32)).compile()
    assert "retention_step" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == slots * Hkv * rows * D * (D + 1) * 4
    assert mem.temp_size_in_bytes < 8 << 20


@pytest.mark.parametrize("heads,shape,dtype", [
    (12, (8, 12, 1024, 64), jnp.bfloat16),     # GPT-2 small: the smoke's
    (25, (32, 25, 1024, 64), jnp.float32),     # GPT-2 XL's heads, fp32 pages
    (4, (160, 4, 128, 16), jnp.bfloat16),      # more slots than lanes
    (16, (4, 1, 1024, 256), jnp.bfloat16)])    # head width 256, one K/V head
def test_decode_attention_compiles(one_chip, heads, shape, dtype):
    """The decode attention alone at other served widths: Mosaic takes the
    blocks, the kernel's own DMA of a dynamic 128-lane window (a 512-row
    window at head widths of whole lane tiles) and the reductions down the
    sublanes and along the lanes; the buffers are read where they lie."""
    from mxnet_tpu.ops.pallas import decode_attention as K

    S, _, C, D = shape
    assert K.tiles((S, heads, 1, D), shape, dtype)
    on = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                   sharding=one_chip)
    compiled = jax.jit(K.decode_attention).lower(
        on((S, heads, 1, D), dtype), on(shape, dtype), on(shape, dtype),
        on((S,), jnp.int32)).compile()
    assert "decode_attention" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


@pytest.mark.parametrize("seq,window", [(1024, 4096), (8192, 4096),
                                        (8192, None)])
def test_flash_grouped_window_compiles(one_chip, seq, window):
    """The forward kernel with 128 query heads on 8 K/V heads of width 128,
    with and without the 4096 window, at the served model's prefill
    buckets."""
    from mxnet_tpu.ops.pallas.flash_attention import flash_attention

    q = jax.ShapeDtypeStruct((1, 128, seq, 128), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 8, seq, 128), jnp.bfloat16,
                              sharding=one_chip)
    text = _compile(lambda q, k, v: flash_attention(
        q, k, v, causal=True, window=window), q, kv, kv)
    assert "flash_fwd" in text


@pytest.mark.parametrize("rows,tile", [(512, 16), (20480, 256)])
def test_moe_ffn_compiles_at_the_served_widths(one_chip, rows, tile):
    """The grouped expert FFN at width 4096 x 4096, 16 experts held: a
    decode step's 16-row tiles and a prefill chunk's 256-row tiles. Mosaic
    takes the blocks and the raised VMEM limit; nothing expert-sized is
    allocated beside the operands."""
    from mxnet_tpu.ops.pallas import moe_ffn as K

    assert K.tiles(tile, 4096, 4096, jnp.bfloat16)
    on = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                   sharding=one_chip)
    w = on((16, 4096, 4096), jnp.bfloat16)
    compiled = jax.jit(
        lambda x, te, tv, wg, wu, wd: K.moe_ffn(x, te, tv, wg, wu, wd, tile)
    ).lower(on((rows, 4096), jnp.bfloat16), on((rows // tile,), jnp.int32),
            on((rows // tile,), jnp.int32), w, w, w).compile()
    assert "moe_ffn" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


@pytest.mark.parametrize("S,H,Hkv,C,D", [
    (32, 20, 20, 1024, 64),       # gpt2-large's pages: the column paths
    (32, 128, 8, 4096, 128),      # command-a-plus's rings: the row paths
    (32, 128, 8, 8192, 128)])     # ... and its full page
def test_decode_step_reads_and_writes_kv_with_the_kernels(
        one_chip, monkeypatch, S, H, Hkv, C, D):
    """A decode step in small (two layers: QKV projection, ``cache_write``
    of K and V at per-slot positions, ``cached_attention`` over per-slot
    lengths, 0 for a free slot, which the write is told too) over the
    serving cells' buffers, donated.
    What interpret mode cannot see: Mosaic takes both kernels at these
    widths; the view a kernel takes of a buffer (``(S,H,D,C)`` for head
    widths under 128) is a bitcast of the layout the device holds it in, so
    no cache-shaped array is copied, transposed, carried through a ``while``
    (the scatter's serial loop) or read by a fusion (the dense attention
    over slots x capacity); every buffer is updated where it lies, and
    nothing cache-sized is allocated beside them."""
    import re

    from mxnet_tpu.ops import attention as A

    monkeypatch.setattr(A, "is_tpu_backend", lambda: True)
    # the row path is shut at the gate (ops/attention.py says why): opened
    # here, so that what it compiles to stays held until it opens
    monkeypatch.setattr(A, "_DECODE_ROW_PATH", True)
    layers, U = 2, 1024

    def heads(y, n):
        return jnp.transpose(y.reshape(S, 1, n, D), (0, 2, 1, 3))

    def step(x, ws, caches, pos, active):
        lengths = jnp.minimum(pos + 1, C) * active
        out = []
        for (w, wo), (kc, vc) in zip(ws, caches):
            qkv = jnp.dot(x, w)
            q = heads(qkv[:, :H * D], H)
            k = heads(qkv[:, H * D:(H + Hkv) * D], Hkv)
            v = heads(qkv[:, (H + Hkv) * D:], Hkv)
            kc = A.cache_write(kc, k, pos % C, lengths)
            vc = A.cache_write(vc, v, pos % C, lengths)
            o = A.cached_attention(q, kc, vc, lengths)
            x = x + jnp.dot(jnp.transpose(o, (0, 2, 1, 3)).reshape(S, H * D),
                            wo)
            out.append((kc, vc))
        return x, out

    def on(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    cache = on((S, Hkv, C, D), jnp.bfloat16)
    w = (on((U, (H + 2 * Hkv) * D), jnp.bfloat16),
         on((H * D, U), jnp.bfloat16))
    compiled = jax.jit(step, donate_argnums=(2,)).lower(
        on((S, U), jnp.bfloat16), [w] * layers, [(cache, cache)] * layers,
        on((S,), jnp.int32), on((S,), jnp.int32)).compile()
    text = compiled.as_text()
    # a custom call's result carries its kernel's name
    calls = lambda name: len(re.findall(
        r"%%%s[.\d]* = \S+ custom-call\(" % name, text))
    assert calls("kv_cache_write") == 2 * layers
    assert calls("decode_attention") == layers
    assert not re.search(r" while\(", text)
    # async copy-start/-done pairs are the compiler's own prefetch of a
    # buffer into fast memory; the parent's program has them too
    made = re.findall(
        r"= bf16\[%d,%d,(?:%d,%d|%d,%d)\]\S* ([\w-]+)\("
        % (S, Hkv, C, D, D, C), text)
    assert made and set(made) <= {"parameter", "bitcast", "custom-call",
                                  "copy-done"}, sorted(set(made))
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == 2 * layers * S * Hkv * C * D * 2
    assert mem.temp_size_in_bytes < 8 << 20


def test_train_step_over_a_mesh_compiles_without_mosaic(topo, monkeypatch):
    """A Mosaic kernel cannot be partitioned by the SPMD partitioner. With
    the TPU branch of the gates forced open, ``build_train_step`` over a
    four-chip ``dp`` mesh must still compile: traced under the mesh, the ops
    take their XLA formulations and the partitioner adds the all-reduce."""
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import chip_smoke
    from mxnet_tpu.models.bert import BERTModel
    from mxnet_tpu.ops import functional as OF

    monkeypatch.setattr(OF, "is_tpu_backend", lambda: True)
    mesh = Mesh(np.array(topo.devices).reshape(4), ("dp",))

    def tiny():
        return BERTModel(vocab_size=512, units=128, hidden_size=256,
                         num_layers=1, num_heads=2, max_length=32)

    _net, _plist, step, params, states = chip_smoke.build_bert_step(
        tiny, seed=0, mesh=mesh)
    batch = chip_smoke.make_bert_batch(0, 512, 8, 32, 4)

    def on(tree, spec):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=NamedSharding(mesh, spec)), tree)

    text = step.lower(
        on(params, P()), on(states, P()), on(jnp.int32(1), P()),
        on(jax.random.PRNGKey(0), P()), on(batch, P("dp"))
    ).compile().as_text()
    assert "tpu_custom_call" not in text and "all-reduce" in text


def test_latent_attention_compiles_at_the_published_sizes(one_chip):
    """The absorbed decode read at A.X-K1's sizes (32 slots, 64 heads, a
    latent of 512 and a rotated part of 64, 16,384 positions): Mosaic takes
    the walk's DMA of a 512-row block of latents and of the same positions'
    64 x 512 window of the rotated keys (the capacity on the lanes: the
    ``swapaxes`` is a bitcast, no copy of a buffer), the two contractions on
    the matrix unit, and the queries of every slot whole in VMEM."""
    from mxnet_tpu.ops.pallas import latent_attention as K

    S, H, R, P, C = 32, 64, 512, 64, 16384
    sd = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)
    shapes = ((S, H, 1, R), (S, H, 1, P), (S, 1, C, R), (S, 1, C, P))
    assert K.tiles(*shapes, jnp.bfloat16)
    compiled = jax.jit(
        lambda ql, qp, c, pe, n: K.latent_attention(ql, qp, c, pe, n,
                                                    0.130861)).lower(
        *(sd(s) for s in shapes), sd((S,), jnp.int32)).compile()
    text = compiled.as_text()
    assert "latent_attention" in text and "tpu_custom_call" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 8 << 20


@pytest.mark.parametrize("width", [512, 64], ids=["c_kv", "k_pe"])
def test_latent_page_write_compiles_in_place(one_chip, width):
    """The step's write of a latent page's two buffers through
    ``kv_cache_write``: the latent rows (one "head" of 512) on the row
    path, the shared rotated key (one "head" of 64) on the column path,
    each aliased to its result with nothing of its size beside it."""
    from mxnet_tpu.ops.pallas import kv_write

    S, C = 32, 16384
    sd = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)
    assert kv_write.tiles((S, 1, C, width), (S, 1, 1, width), jnp.bfloat16)
    compiled = jax.jit(kv_write.kv_cache_write, donate_argnums=(0,)).lower(
        sd((S, 1, C, width)), sd((S, 1, 1, width)), sd((S,), jnp.int32),
        sd((S,), jnp.int32)).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == S * C * width * 2
    assert mem.temp_size_in_bytes < 1 << 20


def test_flash_forward_compiles_at_two_widths(one_chip):
    """The prefill's expanded attention of a group of 16 heads at 4,096
    tokens: keys of 192 (128 + 64), values of 128."""
    from mxnet_tpu.ops.pallas.flash_attention import flash_attention

    sd = lambda shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                                            sharding=one_chip)
    compiled = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, causal=True, scale=0.130861, block_q=512,
        block_k=512)).lower(
        sd((1, 16, 4096, 192)), sd((1, 16, 4096, 192)),
        sd((1, 16, 4096, 128))).compile()
    assert "flash_fwd" in compiled.as_text()
