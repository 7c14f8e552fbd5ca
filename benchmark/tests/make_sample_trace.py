"""Records the small device trace kept beside the tests (``sample.xplane.pb``).

Run on the chip, once, by hand: ``python benchmark/tests/make_sample_trace.py
<out_dir>``. Drives a two-layer BERT-shaped train step and a two-layer GPT
under ``serve.GenerativeServer`` for a fraction of a second under the
profiler, writes the ``.xplane.pb`` to ``<out_dir>`` and prints what planes,
lines and event names it holds. The benchmark's runs never call this.
"""
import glob
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(out_dir):
    import jax
    import jax.numpy as jnp
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import _trace, amp, parallel, profiler, serve
    from mxnet_tpu.models.bert import BERTModel
    from mxnet_tpu.models.gpt import GPTModel
    from mxnet_tpu.ops.functional import softmax_xent_rows

    print("device", jax.devices())
    mx.random.seed(0)
    net = BERTModel(vocab_size=1024, units=256, hidden_size=1024,
                    num_layers=2, num_heads=4, dropout=0.1, max_length=128)
    net.initialize()
    amp.convert_hybrid_block(net, "bfloat16")
    plist = list(net.collect_params().values())
    opt = mx.optimizer.Adam(learning_rate=1e-4, multi_precision=True)

    def loss_fn(param_arrays, batch, key):
        tok, tt, vl, mp, mlm_y, nsp_y = batch
        with _trace.trace_scope(key, True) as t:
            t.param_store = {id(p): a for p, a in zip(plist, param_arrays)}
            _s, _p, nsp_logits, mlm_logits = net._call_traced(tok, tt, vl, mp)
        nsp_lp = jax.nn.log_softmax(nsp_logits.astype(jnp.float32), axis=-1)
        nsp_nll = -jnp.take_along_axis(nsp_lp, nsp_y[:, None], axis=-1)
        return (jnp.mean(softmax_xent_rows(mlm_logits, mlm_y))
                + jnp.mean(nsp_nll))

    step = parallel.build_train_step(loss_fn, opt)
    params = [p.data()._data for p in plist]
    states = parallel.tree_optimizer_step(opt)[0](params)
    rng = np.random.default_rng(0)
    B, T, P, V = 8, 128, 8, 1024
    batch = (jnp.asarray(rng.integers(0, V, (B, T)), jnp.int32),
             jnp.zeros((B, T), jnp.int32), jnp.full((B,), T, jnp.float32),
             jnp.asarray(rng.integers(0, T, (B, P)), jnp.int32),
             jnp.asarray(rng.integers(0, V, (B, P)), jnp.int32),
             jnp.asarray(rng.integers(0, 2, (B,)), jnp.int32))
    key = jax.random.PRNGKey(0)
    for i in range(2):
        params, states, loss = step(params, states, jnp.int32(i + 1), key,
                                    batch)
    print("warm loss", float(loss))

    model = GPTModel(vocab_size=1024, units=256, num_layers=2, num_heads=4,
                     max_length=1024, dropout=0.0)
    model.initialize()
    model.cast("bfloat16")
    model.hybridize()
    srv = serve.GenerativeServer(model, slots=4, timeout_ms=600000.0)
    srv.warmup(prompt_buckets=[16, 1000], max_tokens=1024)
    prompts = [rng.integers(0, V, (n,)).astype(np.int32)
               for n in (12, 16, 700, 10, 900, 14)]

    trace_dir = os.path.join(out_dir, "raw")
    shutil.rmtree(trace_dir, ignore_errors=True)
    os.makedirs(trace_dir)
    profiler.set_config(filename=os.path.join(trace_dir, "profile.json"))
    with srv:
        srv.submit(prompts[0], max_new_tokens=4).result(timeout_s=600)
        profiler.set_state("run")
        t0 = time.perf_counter()
        for i in range(3):
            with jax.profiler.TraceAnnotation("bench[train_step]"):
                params, states, loss = step(params, states, jnp.int32(i + 3),
                                            key, batch)
        print("traced loss", float(loss))
        time.sleep(0.02)
        streams = [srv.submit(p, max_new_tokens=6) for p in prompts]
        for s in streams:
            s.result(timeout_s=600)
        print("traced seconds", time.perf_counter() - t0)
        profiler.set_state("stop")
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    print("files", [(f, os.path.getsize(f)) for f in found])
    dst = os.path.join(out_dir, "sample.xplane.pb")
    shutil.copy(found[0], dst)
    shutil.rmtree(trace_dir, ignore_errors=True)

    pd = jax.profiler.ProfileData.from_file(dst)
    for plane in pd.planes:
        print("PLANE", repr(plane.name))
        try:
            print("  stats", list(plane.stats)[:20])
        except Exception as e:
            print("  (no plane stats: %s)" % e)
        for line in plane.lines:
            evs = list(line.events)
            print("  LINE", repr(line.name), len(evs))
            seen = {}
            for ev in evs:
                seen.setdefault(ev.name, ev)
            for name, ev in list(seen.items())[:40]:
                try:
                    st = dict(ev.stats)
                except Exception:
                    st = {}
                keys = {k: (str(v)[:60]) for k, v in list(st.items())[:12]}
                print("     EV", repr(name[:100]), ev.start_ns,
                      ev.duration_ns, keys)


if __name__ == "__main__":
    main(sys.argv[1] if len(sys.argv) > 1 else "chiprun_out/sample_trace")
