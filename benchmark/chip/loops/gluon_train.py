"""The training loop: ``with autograd.record(): loss = model(batch)`` →
``loss.backward()`` → ``trainer.step(n)`` on the framework's own Gluon
blocks, one step after the other (a trainer's closed loop).

Set-up builds ONE model + Trainer, hands it the seeded weights, drives it
through ``check_steps`` steps (which compile, or load from the cache, and
whose losses, first gradient and parameter change the reference follows),
then hands the same objects to the window. The contexts come from the
cell's ``chips``; nothing here knows a configuration's or a cell's name.
"""
from __future__ import annotations

import collections
import functools
import gc
import importlib
import time

import numpy as np

SPAN_NAMES = ("data", "fwd", "bwd", "update")   # the spans `step` opens


@functools.lru_cache(maxsize=None)
def _norms_fn():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def norms(tree):
        return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
                for k, v in tree.items()}

    @jax.jit
    def change_norms(now, start):
        return {k: jnp.sqrt(jnp.sum(jnp.square(
            now[k].astype(jnp.float32) - start[k].astype(jnp.float32))))
            for k in now}

    return norms, change_norms


class Run:
    def __init__(self, cfg, traffic, shape, chips, seed, spans, rehearse=False):
        self.cfg, self.traffic, self.shape = cfg, traffic, dict(shape)
        self.chips, self.seed, self.spans = chips, seed, spans
        self.rehearse = rehearse
        self.model_mod = importlib.import_module(f"models.{cfg['model']}")
        self.shape["batch"] = shape["batch_per_chip"] * chips
        self.samples_per_step, self.denom = \
            self.model_mod.samples_and_denominator(cfg, self.shape)
        self.readings = None

    # ---- the harness's calls ---------------------------------------------------
    def setup(self):
        self.build()
        self.check_steps()

    def measure(self, seconds):
        steps, wall = self.window(seconds)
        samples = steps * self.samples_per_step
        flops = samples * self.model_mod.flops_per_sample(self.cfg, self.shape)
        finite = np.isfinite(self.last_loss)
        return {"end_to_end": {"train_samples_per_s": samples / wall},
                "attempted": steps, "failed": 0 if finite else steps,
                "steps": steps, "seconds": wall, "samples": samples,
                "flops": flops}

    def reader_context(self):
        return {"model_mod": self.model_mod, "shape": self.shape}

    def verify(self):
        import compare

        nums, where = compare.numbers(self.readings, self.reference())
        ok, table = compare.judge(nums, self.cfg.get("limits", {}))
        for k, leaf in where.items():
            table[k]["leaf"] = leaf
        return ok, table

    # ---- set-up ------------------------------------------------------------
    def build(self):
        import mxnet_tpu as mx

        make_ctx = mx.cpu if self.rehearse else mx.tpu
        self.ctxs = [make_ctx(i) for i in range(self.chips)]
        self.devices = [c.jax_device for c in self.ctxs]
        self.block, self.forward = self.model_mod.build(self.cfg, self.ctxs)
        self.params = self.block.collect_params()
        self.in_dtypes = self.model_mod.input_dtypes(self.cfg)
        self.reseed(self.seed)

    def reseed(self, seed):
        """Everything that comes from the seed: the weights (handed to the
        program's parameters), a new Trainer with fresh optimizer state, and
        the pool of host batches. ``calibrate.py`` calls it again to read
        many seeds through one compiled model."""
        from mxnet_tpu import gluon

        import weights as W

        self.seed = seed
        self.weights = W.make_weights(self.model_mod, self.cfg, seed,
                                      self.devices[0])
        W.load_into(self.params, self.weights, self.block.prefix, self.ctxs)
        opt = self.cfg["optimizer"]
        self.trainer = gluon.Trainer(self.params, opt["name"],
                                     dict(opt["params"]))
        rng = np.random.default_rng(seed)
        self.pool = [self.model_mod.host_batch(self.cfg, self.shape, rng)
                     for _ in range(self.traffic["pool_batches"])]

    # ---- one step, as the window calls it ------------------------------------
    def load(self, host_batch):
        """Put one host batch on the device(s), as ``split_and_load`` does."""
        from mxnet_tpu.gluon.utils import split_and_load

        parts = []
        for a, dt in zip(host_batch, self.in_dtypes):
            per = split_and_load(a, self.ctxs)
            parts.append([p if str(p.dtype) == dt else p.astype(dt) for p in per])
        return [tuple(p[i] for p in parts) for i in range(len(self.ctxs))]

    def forward_backward(self, per_ctx):
        from mxnet_tpu import autograd

        with autograd.record():
            with self.spans("fwd"):
                outs = [self.forward(*inputs) for inputs in per_ctx]
        with self.spans("bwd"):
            for o in outs:
                o.backward()
        return outs

    def update(self):
        self.trainer.step(self.denom)

    def step(self, host_batch):
        with self.spans("data"):
            per_ctx = self.load(host_batch)
        outs = self.forward_backward(per_ctx)
        with self.spans("update"):
            self.update()
        return outs

    def mean_loss(self, outs):
        total = sum(float(o.asnumpy().astype(np.float32).sum()) for o in outs)
        return total / self.denom

    # ---- the program's side of the comparison --------------------------------
    def _views(self):
        """Per leaf: (first-moment array, factor to the gradient, the array
        that carries the parameter as the optimizer keeps it)."""
        opt = self.cfg["optimizer"]
        p = opt["params"]
        prefix = self.block.prefix
        states = self.trainer._updaters[0].states
        out = {}
        for i, param in enumerate(self.trainer._params):
            if param.grad_req == "null" or i not in states:
                continue
            st, weight = states[i], param.list_data()[0]
            master = None
            if p.get("multi_precision") and isinstance(st, tuple) \
                    and len(st) == 2 and str(weight.dtype) != "float32":
                st, master = st
            if opt["name"] in ("adam", "adamw"):
                moment, factor = st[0], 1.0 / (1.0 - p["beta1"])
            elif opt["name"] == "sgd" and p.get("momentum"):
                moment, factor = st, 1.0 / p["learning_rate"]
            else:
                raise KeyError(f"no first-gradient rule for {opt['name']!r}")
            out[param.name[len(prefix):]] = (
                moment._data, factor, (master if master is not None else weight)._data)
        return out

    def check_steps(self):
        """The first steps, through the window's own call and feed."""
        norms, change_norms = _norms_fn()
        n = self.traffic["check_steps"]
        losses, grad1 = [], None
        for k in range(n):
            outs = self.step(self.pool[k % len(self.pool)])
            losses.append(self.mean_loss(outs))
            if k == 0:
                views = self._views()
                raw = norms({name: v[0] for name, v in views.items()})
                grad1 = {name: float(raw[name]) * views[name][1] for name in raw}
        views = self._views()
        change = change_norms({name: v[2] for name, v in views.items()},
                              {name: self.weights[name] for name in views})
        self.readings = {"losses": losses, "grad1": grad1,
                         "change": {k: float(v) for k, v in change.items()}}
        self.weights = None

    # ---- the window ------------------------------------------------------------
    def window(self, seconds):
        """Steps until ``seconds`` have passed; ends when the last step's
        loss and updated parameters are ready on the device. Returns
        (steps, window seconds)."""
        import jax

        depth = self.traffic["max_steps_in_flight"]
        in_flight = collections.deque()
        first = self.traffic["check_steps"]
        steps = 0
        t0 = time.perf_counter()
        while True:
            outs = self.step(self.pool[(first + steps) % len(self.pool)])
            steps += 1
            in_flight.append(outs)
            if len(in_flight) > depth:
                for o in in_flight.popleft():
                    o.wait_to_read()
            if time.perf_counter() - t0 >= seconds:
                break
        ready = [o._data for o in outs]
        for param in self.trainer._params:
            ready += [d._data for d in param.list_data()]
        jax.block_until_ready(ready)
        wall = time.perf_counter() - t0
        self.last_loss = self.mean_loss(outs)
        return steps, wall

    def release(self):
        """Drop the program's state, so that the reference has the chip."""
        for name in ("trainer", "params", "block", "forward", "weights"):
            setattr(self, name, None)
        gc.collect()

    # ---- the reference's side ----------------------------------------------------
    def reference(self, precision="exact", **faults):
        import jax

        import weights as W
        from reference import lowp, train

        ref_mod = importlib.import_module(f"reference.{self.cfg['model']}")
        w = W.make_weights(self.model_mod, self.cfg, self.seed, self.devices[0])
        n = self.traffic["check_steps"]
        batches = [self.pool[k % len(self.pool)] for k in range(n)]
        with jax.default_device(self.devices[0]):
            return train.follow(
                ref_mod, self.cfg, self.model_mod.param_specs(self.cfg), w,
                batches, self.denom, self.cfg["optimizer"],
                lowp.PRECISIONS[precision],
                rows_per_block=self.cfg.get("reference_rows_per_block", 16),
                **faults)
