"""Shared set-up steps of the drivers: the model from its configuration file,
with the benchmark's own weights in it."""
import importlib
import re


def construct(config):
    """The model object of ``config["constructor"]`` built with
    ``config["sizes"]``."""
    ctor = config["constructor"]
    mod = importlib.import_module(ctor["module"])
    return getattr(mod, ctor["name"])(**config["sizes"])


def reference_names(config, program_names):
    """Program parameter name -> the reference's name, by the ordered
    ``param_rename`` rules (regular expression, replacement) of the
    configuration file."""
    out = {}
    for name in program_names:
        ref = name
        for pattern, repl in config["param_rename"]:
            ref = re.sub(pattern, repl, ref)
        out[name] = ref
    return out


def install_weights(config, plist, weights):
    """Hands each of the model's parameters its array out of ``weights``
    (reference name -> array), cast to the parameter's own type. Every
    reference name has to be used once and every parameter served."""
    import jax.numpy as jnp

    from mxnet_tpu.ndarray import NDArray

    names = reference_names(config, [p.name for p in plist])
    want = sorted(names.values())
    if want != sorted(weights):
        raise SystemExit(
            "benchmark: the model's parameters and the reference's do not "
            "match: only in the model %s, only in the reference %s"
            % (sorted(set(want) - set(weights))[:5],
               sorted(set(weights) - set(want))[:5]))
    for p in plist:
        w = weights[names[p.name]]
        if tuple(w.shape) != tuple(p.shape):
            raise SystemExit("benchmark: %s is %s in the model and %s in the "
                             "reference" % (p.name, p.shape, w.shape))
        p.set_data(NDArray(jnp.asarray(w, p.dtype)))
    return names
