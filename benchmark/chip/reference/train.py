"""The reference's training steps: loss and gradients of the plain model in
float32 at ``highest`` matmul precision, and the optimizer rules as their
papers give them, written out here (nothing of the program's optimizer).

What the configuration states about storage is kept: a leaf served in
bfloat16 without a float32 master copy (``multi_precision`` off) is rounded
to bfloat16 after each update, as is its momentum; with a master copy the
master stays float32 and the forward reads its bfloat16 rounding."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _round_to(x, dtype):
    return x.astype(jnp.dtype(dtype)).astype(jnp.float32)


def trainable(specs):
    return [n for n, _, _, _ in specs if "running_" not in n]


def value_and_grad_fn(ref_mod, cfg, lin):
    """The compiled (summed loss, gradients) of the plain model; the leaves
    that are not trained (BatchNorm's running statistics) are an argument,
    never a closed-over constant."""
    def f(tr, fixed, b):
        return ref_mod.loss_sum(cfg, {**tr, **fixed}, b, lin)

    return jax.jit(jax.value_and_grad(f))


def grads_of_mean_loss(vg, rows_independent, w, batch, denom, rows_per_block,
                       keep_rows=None):
    """(mean loss, {leaf: gradient}) over ``batch`` by ``vg``, in blocks of
    rows where rows are independent. ``keep_rows`` (a slice) plants the
    half-batch fault: only those rows, the mean over them."""
    if keep_rows is not None:
        n_all = batch[0].shape[0]
        batch = tuple(a[keep_rows] for a in batch)
        denom = denom * batch[0].shape[0] / n_all
    tr = {k: v for k, v in w.items() if "running_" not in k}
    fixed = {k: v for k, v in w.items() if "running_" in k}
    n = batch[0].shape[0]
    if not rows_independent or rows_per_block >= n:
        rows_per_block = n
    loss, g = 0.0, None
    for lo in range(0, n, rows_per_block):
        b = tuple(jnp.asarray(a[lo:lo + rows_per_block]) for a in batch)
        l_, g_ = vg(tr, fixed, b)
        loss = loss + l_
        g = g_ if g is None else jax.tree_util.tree_map(jnp.add, g, g_)
    return loss / denom, {k: v / denom for k, v in g.items()}


@jax.jit
def _norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


def _adamw(opt, t, w32, g, state):
    """Loshchilov & Hutter 2019, with the bias correction folded into the
    rate (as Kingma & Ba 2015, section 2, and MXNet, do)."""
    p = opt["params"]
    b1, b2, eps = p["beta1"], p["beta2"], p["epsilon"]
    lr = float(p["learning_rate"] * np.sqrt(1.0 - b2 ** t) / (1.0 - b1 ** t))
    m, v = state
    m = b1 * m + (1.0 - b1) * g
    v = b2 * v + (1.0 - b2) * jnp.square(g)
    return w32 - lr * (m / (jnp.sqrt(v) + eps) + p["wd"] * w32), (m, v)


def _sgd(opt, t, w32, g, state):
    """SGD with momentum and L2 weight decay (Sutskever et al. 2013; the
    decay is added to the gradient, as He et al. 2015 train)."""
    p = opt["params"]
    mom = p["momentum"] * state - p["learning_rate"] * (g + p["wd"] * w32)
    return w32 + mom, mom


def follow(ref_mod, cfg, specs, weights, batches, denom, opt, lin,
           rows_per_block=16, keep_rows=None):
    """Follow ``len(batches)`` steps from ``weights``. Returns the readings
    the comparison takes: ``losses`` (mean per step), ``grad1`` (per-leaf
    norm of the first gradient as the optimizer gets it) and ``change``
    (per-leaf norm of the parameters' change after the last step)."""
    dtypes = {n: d for n, _, d, _ in specs}
    mp = bool(opt["params"].get("multi_precision"))
    name = opt["name"]
    w32 = {k: v.astype(jnp.float32) for k, v in weights.items()}
    start = dict(w32)
    names = trainable(specs)
    if name == "adamw":
        state = {k: (jnp.zeros_like(w32[k]), jnp.zeros_like(w32[k])) for k in names}
        rule = jax.jit(lambda t, w, g, s: _adamw(opt, t, w, g, s), static_argnums=0)
    elif name == "sgd":
        state = {k: jnp.zeros_like(w32[k]) for k in names}
        rule = jax.jit(lambda t, w, g, s: _sgd(opt, t, w, g, s), static_argnums=0)
    else:
        raise KeyError(f"the reference has no rule for optimizer {name!r}")
    losses, grad1 = [], None
    with jax.default_matmul_precision("highest"):
        vg = value_and_grad_fn(ref_mod, cfg, lin)
        for t, batch in enumerate(batches, 1):
            served = {k: _round_to(v, dtypes[k]) for k, v in w32.items()}
            loss, g = grads_of_mean_loss(vg, ref_mod.ROWS_INDEPENDENT, served,
                                         batch, denom, rows_per_block, keep_rows)
            losses.append(float(loss))
            if t == 1:
                if name == "sgd":   # the optimizer's gradient carries the decay
                    g1 = {k: g[k] + opt["params"]["wd"] * w32[k] for k in names}
                else:
                    g1 = g
                grad1 = {k: float(v) for k, v in _norms(g1).items()}
            for k in names:
                new_w, new_s = rule(t, w32[k], g[k], state[k])
                if not mp:          # state lives in the served type
                    new_w = _round_to(new_w, dtypes[k])
                    new_s = jax.tree_util.tree_map(
                        lambda a, d=dtypes[k]: _round_to(a, d), new_s)
                w32[k], state[k] = new_w, new_s
            del g
        change = _norms({k: w32[k] - start[k] for k in names})
    return {"losses": losses, "grad1": grad1,
            "change": {k: float(v) for k, v in change.items()}}
