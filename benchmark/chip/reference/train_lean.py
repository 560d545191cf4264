"""``reference/train.py``'s ``follow`` for a model whose float32 copies do
not fit the chip six times over: the same rules (imported from there, not
rewritten), the same readings, but the float32 master weights, the
optimizer's moments and the starting point live in host memory and pass
through the jitted rule leaf by leaf. On the device at any time: the
weights as served (float32 values rounded to the served type), one running
sum of gradients, one block's gradients, and one leaf of optimizer state."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from reference import train


def staged_value_and_grad(ref_mod, cfg, lin):
    """``train.value_and_grad_fn``'s (summed loss, gradients) for a reference
    that gives its ``stages``: forward piece by piece keeping each piece's
    input, then each piece's own vjp from the last to the first, every piece
    a program of its own that is handed its own weights only. The whole
    model's backward in one program reserves 10.3 GB at 8,192 tokens and
    float32 ``highest`` (compiled for a v5e, PR 27): more than is left beside
    the weights and their gradients."""
    pieces = ref_mod.stages(cfg, lin)
    fwd = [jax.jit(fn) for _, fn in pieces]

    def pull(fn):
        def back(w, x, batch, ct):
            if x is None:
                _, vjp = jax.vjp(lambda w_: fn(w_, None, batch), w)
                return vjp(ct)[0], None
            _, vjp = jax.vjp(lambda w_, x_: fn(w_, x_, batch), w, x)
            return vjp(ct)
        return jax.jit(back)

    bwd = [pull(fn) for _, fn in pieces]

    def vg(tr, fixed, batch):
        w = {**tr, **fixed}
        own = [{k: v for k, v in w.items() if k.startswith(prefixes)}
               for prefixes, _ in pieces]
        carries, x = [], None
        for f, w_i in zip(fwd, own):
            carries.append(x)
            x = f(w_i, x, batch)
        loss, ct, grads = x, jnp.ones((), x.dtype), {}
        for b, w_i, x_i in zip(bwd[::-1], own[::-1], carries[::-1]):
            g_i, ct = b(w_i, x_i, batch, ct)
            grads.update({k: v for k, v in g_i.items() if k in tr})
        return loss, grads

    return vg


def _block_grads(vg, rows_independent, served, batch, rows_per_block, keep_rows):
    """(summed loss, summed gradients) over ``batch`` in blocks of rows; the
    running sum is donated to each addition, so two gradient trees are live
    at most."""
    if keep_rows is not None:
        batch = tuple(a[keep_rows] for a in batch)
    tr = {k: v for k, v in served.items() if "running_" not in k}
    fixed = {k: v for k, v in served.items() if "running_" in k}
    n = batch[0].shape[0]
    if not rows_independent or rows_per_block >= n:
        rows_per_block = n
    add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b),
                  donate_argnums=0)
    loss, g = 0.0, None
    for lo in range(0, n, rows_per_block):
        b = tuple(jnp.asarray(a[lo:lo + rows_per_block]) for a in batch)
        l_, g_ = vg(tr, fixed, b)
        loss = loss + float(l_)
        g = g_ if g is None else add(g, g_)
        del g_
    return loss, g


def follow(ref_mod, cfg, specs, weights, batches, denom, opt, lin,
           rows_per_block=16, keep_rows=None):
    """The readings of ``train.follow`` (``losses``, ``grad1``, ``change``),
    from the same arguments."""
    dtypes = {n: d for n, _, d, _ in specs}
    mp = bool(opt["params"].get("multi_precision"))
    name = opt["name"]
    host = {k: np.asarray(v.astype(jnp.float32)) for k, v in weights.items()}
    del weights
    start = {k: v.copy() for k, v in host.items()}
    names = train.trainable(specs)
    if name == "adamw":
        state = {k: (np.zeros_like(host[k]), np.zeros_like(host[k])) for k in names}
        rule = jax.jit(lambda t, w, g, s: train._adamw(opt, t, w, g, s), static_argnums=0)
    elif name == "sgd":
        state = {k: np.zeros_like(host[k]) for k in names}
        rule = jax.jit(lambda t, w, g, s: train._sgd(opt, t, w, g, s), static_argnums=0)
    else:
        raise KeyError(f"the reference has no rule for optimizer {name!r}")
    sq = jax.jit(lambda a: jnp.sum(jnp.square(a)))
    losses, grad1 = [], None
    with jax.default_matmul_precision("highest"):
        vg = staged_value_and_grad(ref_mod, cfg, lin) \
            if hasattr(ref_mod, "stages") else train.value_and_grad_fn(ref_mod, cfg, lin)
        for t, batch in enumerate(batches, 1):
            served = {k: train._round_to(jnp.asarray(v), dtypes[k])
                      for k, v in host.items()}
            frac = 1.0
            if keep_rows is not None:
                n_all = batch[0].shape[0]
                frac = batch[0][keep_rows].shape[0] / n_all
            loss, g = _block_grads(vg, ref_mod.ROWS_INDEPENDENT, served, batch,
                                   rows_per_block, keep_rows)
            del served
            step_denom = denom * frac
            losses.append(loss / step_denom)
            if t == 1:
                grad1 = {}
            for k in names:
                gk = g.pop(k) / step_denom
                if t == 1:
                    g1 = gk + opt["params"]["wd"] * jnp.asarray(host[k]) \
                        if name == "sgd" else gk
                    grad1[k] = float(jnp.sqrt(sq(g1)))
                new_w, new_s = rule(t, jnp.asarray(host[k]), gk,
                                    jax.tree_util.tree_map(jnp.asarray, state[k]))
                if not mp:          # state lives in the served type
                    new_w = train._round_to(new_w, dtypes[k])
                    new_s = jax.tree_util.tree_map(
                        lambda a, d=dtypes[k]: train._round_to(a, d), new_s)
                host[k] = np.asarray(new_w)
                state[k] = jax.tree_util.tree_map(np.asarray, new_s)
            del g
    change = {k: float(np.sqrt(np.sum(np.square(
        host[k].astype(np.float64) - start[k].astype(np.float64))))) for k in names}
    return {"losses": losses, "grad1": grad1, "change": change}
