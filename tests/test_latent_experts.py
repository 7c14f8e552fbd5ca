"""The expert layer's second form (``ops/moe.py``, ``ops/pallas/moe_ffn.py``):
non-gated relu^2 experts, a selection bias in the router, and experts that
work in a latent narrower than the model, as Nemotron-3-Super's LatentMoE
has them, against the plain reference
(``benchmark/reference/nemotron_3_super.py: moe``), on the CPU at a small
size. The SwiGLU tests of ``tests/test_latent_moe.py`` and
``tests/test_cohere_moe.py`` hold the first form unchanged."""
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu.ops import moe as M
from mxnet_tpu.ops.pallas import moe_ffn as K

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _reference():
    spec = importlib.util.spec_from_file_location(
        "reference_nemotron_3_super_experts",
        os.path.join(ROOT, "benchmark", "reference", "nemotron_3_super.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _reference()


def _layer(seed, d=64, latent=32, f=48, E=16, fs=40):
    rs = np.random.RandomState(seed)
    g = lambda *s, sd=0.1: jnp.asarray(rs.normal(0, sd, s), jnp.float32)
    return {"moe_router_weight": g(E, d, sd=0.3),
            "moe_router_bias": g(E, sd=0.05),
            "moe_fc1_weight": g(latent, d), "moe_fc2_weight": g(d, latent),
            "moe_experts_up_weight": g(E, f, latent),
            "moe_experts_down_weight": g(E, f, latent),
            "moe_shared_up_weight": g(fs, d),
            "moe_shared_down_weight": g(d, fs)}


def test_the_four_shares_add_up_to_the_uncut_latent_layer():
    """The routed parts of the 4 shares (``first_expert`` 0, 4, 8, 12 of 16:
    the deployment's 0, 128, 256, 384 of 512 in proportion), each in the
    latent and taken through W_fc2, plus the shared expert counted once, are
    the reference's uncut layer; every pick is computed exactly once."""
    lp = _layer(3)
    h = jnp.asarray(np.random.RandomState(3).normal(0, 1, (21, 64)),
                    jnp.float32)
    latent = h @ lp["moe_fc1_weight"].T
    routed, picks = 0.0, 0
    for first in (0, 4, 8, 12):
        part, load = M.expert_ffn(
            h, lp["moe_router_weight"], None,
            lp["moe_experts_up_weight"][first:first + 4],
            lp["moe_experts_down_weight"][first:first + 4],
            first_expert=first, top_k=3, routed_scale=5.0, act="relu2",
            bias=lp["moe_router_bias"], rows=latent)
        assert part.shape == (21, 32) and int(load.sum()) == 21 * 3
        routed = routed + part
        picks += int(load[:4].sum())
    assert picks == 21 * 3
    total = routed @ lp["moe_fc2_weight"].T + M.relu2_ffn(
        h, lp["moe_shared_up_weight"], lp["moe_shared_down_weight"])
    cfg = dict(experts_per_token=3, routed_scaling_factor=5.0,
               first_expert=0, experts_held=16)
    want = ref.moe(cfg, h, lp, "float32")
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=2e-5)
    one = part @ lp["moe_fc2_weight"].T       # one share alone is not it
    assert float(jnp.abs(one - want).max()) > 0.05


def test_the_selection_bias_picks_and_never_weighs():
    """A bias that lifts one expert into every token's choice changes the
    choice; the weights stay the normalised UNBIASED scores of the chosen,
    as the reference has them, and a zero bias is no bias."""
    rs = np.random.RandomState(4)
    h = jnp.asarray(rs.normal(0, 1, (9, 64)), jnp.float32)
    w = jnp.asarray(rs.normal(0, 0.3, (16, 64)), jnp.float32)
    plain_w, plain_e = M.route(h, w, 3, 5.0)
    zero_w, zero_e = M.route(h, w, 3, 5.0, jnp.zeros(16))
    assert np.array_equal(np.asarray(plain_e), np.asarray(zero_e))
    np.testing.assert_allclose(np.asarray(zero_w), np.asarray(plain_w),
                               rtol=1e-6)
    bias = jnp.zeros(16).at[7].set(10.0)
    bw, be = M.route(h, w, 3, 5.0, bias)
    assert (np.asarray(be) == 7).any(axis=1).all()
    assert not np.array_equal(np.asarray(be), np.asarray(plain_e))
    s = jax.nn.sigmoid(h @ w.T)
    chosen = jnp.take_along_axis(s, be, axis=1)
    np.testing.assert_allclose(
        np.asarray(bw), np.asarray(5.0 * chosen / chosen.sum(1, keepdims=True)),
        rtol=1e-6)
    r_w, r_e = ref.route(dict(experts_per_token=3, routed_scaling_factor=5.0),
                         h, w, bias)
    assert np.array_equal(np.asarray(r_e), np.asarray(be))
    np.testing.assert_allclose(np.asarray(r_w), np.asarray(bw), rtol=1e-6)


@pytest.mark.parametrize("split", [False, True], ids=["whole", "blocks"])
@pytest.mark.parametrize("tm,f", [(16, 256), (16, 384)])
def test_relu2_is_its_three_line_form(tm, f, split, monkeypatch):
    """The grouped relu^2 FFN, kernel (interpret mode) and XLA, against
    ``relu(x Wu^T)^2 Wd`` written out for every tile: two matrices an
    expert, no gate; a tile that is not valid is zeros. The kernel runs
    with the whole inner width as one block, and (a VMEM budget lowered
    to two double-buffered blocks of 128) in several."""
    if split:
        monkeypatch.setattr(K, "_VMEM_LIMIT", 2 * (2 * 2 * 128 * 128 * 4))
    assert K._block_f(f, 128, 4, mats=2) == (128 if split else f)
    rs = np.random.RandomState(f)
    E, d, tiles = 3, 128, 4
    x = jnp.asarray(rs.normal(0, 1, (tiles * tm, d)), jnp.float32)
    wu = jnp.asarray(rs.normal(0, 0.1, (E, f, d)), jnp.float32)
    wd = jnp.asarray(rs.normal(0, 0.1, (E, f, d)), jnp.float32)
    te = jnp.asarray([2, 0, 1, 1], jnp.int32)
    tv = jnp.asarray([1, 1, 1, 0], jnp.int32)
    want = []
    for t in range(tiles):
        xt = np.asarray(x[t * tm:(t + 1) * tm], np.float64)
        u = xt @ np.asarray(wu[te[t]], np.float64).T
        y = np.square(np.maximum(u, 0)) @ np.asarray(wd[te[t]], np.float64)
        want.append(y * float(tv[t]))
    want = np.concatenate(want)
    got_k = K.moe_ffn(x, te, tv, None, wu, wd, tm, interpret=True,
                      act="relu2")
    got_x = M._grouped_ffn_xla(x, te, tv, None, wu, wd, tm, "relu2")
    for got in (got_k, got_x):
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4,
                                   atol=1e-4)
    assert not np.asarray(got_k[3 * tm:]).any()


def test_the_inner_blocks_divide_the_published_widths():
    """Nemotron-3-Super's 2688 x 1024 relu^2 experts fit the VMEM budget
    whole (2 x 2 x 2688 x 1024 x 2 B = 22 MB of the 48): one block of
    2688, so a tile is one grid step (the old rule took blocks of 384, 7 a
    tile); the SwiGLU widths of command-a-plus (4096 x 4096: 201 MB
    whole) and ax-k1 (2048 x 7168: 176 MB) keep 512, and a width no
    multiple of 128 divides keeps the halving."""
    assert K._block_f(2688, 1024, 2, mats=2) == 2688
    assert K._block_f(2688, 1024, 2, mats=3) == 2688
    assert K._block_f(4096, 4096, 2) == 512
    assert K._block_f(2048, 7168, 2) == 512
    assert K._block_f(32, 64, 4) == 32 and K._block_f(96, 64, 4) == 96
    assert K._block_f(1000, 64, 4) == 8
    assert K._block_f(1000, 64, 4, mats=2) == 8
    assert K.tiles(16, 1024, 2688, jnp.bfloat16, "relu2")
    assert K.tiles(256, 1024, 2688, jnp.bfloat16, "relu2")


@pytest.mark.parametrize("f,d,act,held,top_k,n_tiles,blocks", [
    (2688, 1024, "relu2", 128, 22, 172, 1),     # nemotron-3-super
    (4096, 4096, "swiglu", 16, 8, 32, 8),       # command-a-plus
    (2048, 7168, "swiglu", 12, 8, 28, 4)])      # ax-k1
def test_the_grid_counters_read_the_blocks_a_tile_walks(
        f, d, act, held, top_k, n_tiles, blocks):
    """Tracing a decode step's call (32 slots, 16-row tiles: the static
    grid of ``ops/moe.py``) bumps ``moe_ffn_grid_tiles`` by its tiles and
    ``moe_ffn_grid_steps`` by its grid steps: their ratio is the blocks a
    tile walks."""
    from mxnet_tpu.observability import registry

    assert n_tiles == -(-32 * top_k // 16) + held
    tiles = registry.counter("moe_ffn_grid_tiles").value
    steps = registry.counter("moe_ffn_grid_steps").value
    sd = jax.ShapeDtypeStruct
    w = sd((held, f, d), jnp.bfloat16)
    out = jax.eval_shape(
        lambda x, te, tv, wu, wd, *wg: K.moe_ffn(
            x, te, tv, wg[0] if wg else None, wu, wd, 16, interpret=True,
            act=act),
        sd((n_tiles * 16, d), jnp.bfloat16), sd((n_tiles,), jnp.int32),
        sd((n_tiles,), jnp.int32), w, w, *([w] if act == "swiglu" else []))
    assert out.shape == (n_tiles * 16, d)
    dt = registry.counter("moe_ffn_grid_tiles").value - tiles
    ds = registry.counter("moe_ffn_grid_steps").value - steps
    assert (dt, ds) == (n_tiles, n_tiles * blocks)
