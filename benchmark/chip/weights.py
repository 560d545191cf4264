"""Seeded weights, made on the device in one jitted call, in the type each
leaf is served in. They are the benchmark's: the program receives them via
``Parameter.set_data`` and the plain reference receives the same dict."""
from __future__ import annotations


def seed_key(seed):
    """A PRNG key from any whole-number seed (the driver's exceed 2**31)."""
    import jax

    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def make_weights(model_mod, cfg, seed, device=None):
    """{name: array} for every leaf of ``model_mod.param_specs(cfg)``."""
    import jax
    import jax.numpy as jnp

    specs = model_mod.param_specs(cfg)
    stds = [model_mod.init_std(cfg, n, s) if kind == "normal" else None
            for n, s, _, kind in specs]

    def make(key):
        out = {}
        for i, ((name, shape, dtype, kind), std) in enumerate(zip(specs, stds)):
            dt = jnp.dtype(dtype)
            if kind == "normal":
                k = jax.random.fold_in(key, i)
                out[name] = (jax.random.normal(k, shape, jnp.float32)
                             * std).astype(dt)
            else:   # a constant: "ones", "zeros" or the number itself
                value = {"ones": 1.0, "zeros": 0.0}.get(kind, kind)
                out[name] = jnp.full(shape, value, dt)
        return out

    fn = jax.jit(make)
    if device is not None:
        with jax.default_device(device):
            return fn(seed_key(seed))
    return fn(seed_key(seed))


def load_into(params, weights, prefix, ctxs):
    """Hand the seeded weights to the program's parameters, by name; every
    program parameter must get one and every weight must be taken."""
    import jax

    taken = set()
    for pname, p in params.items():
        if not pname.startswith(prefix):
            raise KeyError(f"parameter {pname!r} lacks the prefix {prefix!r}")
        name = pname[len(prefix):]
        if name not in weights:
            raise KeyError(f"no seeded weight for program parameter {pname!r}")
        w = weights[name]
        p.shape = tuple(w.shape)
        if p._data is None:
            p._finish_deferred_init()
        if str(p.dtype) != str(w.dtype):
            raise TypeError(f"{pname}: program holds {p.dtype}, weight is {w.dtype}")
        for ctx, arr in p._data.items():
            arr._set_data(jax.device_put(w, ctx.jax_device))
        taken.add(name)
    missing = set(weights) - taken
    if missing:
        raise KeyError(f"seeded weights the program has no parameter for: {sorted(missing)[:5]}")
