"""``loops/gluon_train.py``'s loop for a model whose training state fills
most of the chip: the same ``Run`` (build, check steps, window), with the
reference followed by ``reference/train_lean.py`` (master weights and
moments in host memory) instead of ``reference/train.py``.

Where the program's state is still on the device when the reference is
asked for (``calibrate.py`` reads many seeds through one compiled model and
never releases it), it is parked in host memory for the reference's run
and put back after. The program's own counters (``mx.profiler.counters()``:
the expert layers' device tallies) are read once after set-up and once
after the window, never inside a step, and handed to the readers."""
from __future__ import annotations

import importlib

from loops import gluon_train

SPAN_NAMES = gluon_train.SPAN_NAMES


def _program_counters():
    import mxnet_tpu as mx

    return mx.profiler.counters()


class Run(gluon_train.Run):
    def reseed(self, seed):
        """As the parent's; the benchmark's own copy of the seeded weights
        (the start the parameters' change is measured from) then waits in host
        memory, not beside the training state."""
        import numpy as np

        super().reseed(seed)
        self.weights = {k: np.asarray(v) for k, v in self.weights.items()}

    def setup(self):
        super().setup()
        self.counters_after_setup = _program_counters()

    def reader_context(self):
        ctx = super().reader_context()
        ctx["program_counters"] = {"setup": self.counters_after_setup,
                                   "window": _program_counters()}
        return ctx

    # ---- the program's state, out of the reference's way -----------------------
    def _state_arrays(self):
        """Every NDArray that carries the program's weights, gradients and
        optimizer state."""
        if getattr(self, "trainer", None) is None:
            return []
        out = []

        def walk(node):
            if node is None:
                return
            if isinstance(node, (tuple, list)):
                for n in node:
                    walk(n)
            elif hasattr(node, "_set_data"):
                out.append(node)

        for param in self.trainer._params:
            walk(param.list_data())
            if param.grad_req != "null":
                walk(param.list_grad())
        for updater in self.trainer._updaters:
            walk(list(updater.states.values()))
        return out

    def _parked(self):
        import numpy as np

        arrays = self._state_arrays()
        kept = [(a, np.asarray(a._data), a._data.sharding) for a in arrays]
        for a, host, _ in kept:
            a._data = host          # the device buffer goes; the host copy stands in
        return kept

    def _unpark(self, kept):
        import jax

        for a, host, sharding in kept:
            a._set_data(jax.device_put(host, sharding))

    def _report_memory(self, where):
        """One line on standard error: what the device holds as the reference
        starts (a reference that cannot load is a memory question first)."""
        import sys

        import jax

        stats = self.devices[0].memory_stats() or {}
        live = [a for a in jax.live_arrays()
                if self.devices[0] in a.devices()]
        top = sorted(live, key=lambda a: -a.nbytes)[:4]
        print(f"device memory {where}: in use {stats.get('bytes_in_use')}, "
              f"reserved {stats.get('bytes_reserved')}, limit "
              f"{stats.get('bytes_limit')}; {len(live)} live arrays of "
              f"{sum(a.nbytes for a in live)} bytes, largest "
              f"{[(tuple(a.shape), str(a.dtype)) for a in top]}",
              file=sys.stderr, flush=True)

    # ---- the reference's side ----------------------------------------------------
    def reference(self, precision="exact", **faults):
        import jax

        import weights as W
        from reference import lowp, train_lean

        ref_mod = importlib.import_module(f"reference.{self.cfg['model']}")
        kept = self._parked()
        self._report_memory("before the reference")
        try:
            import numpy as np

            w = W.make_weights(self.model_mod, self.cfg, self.seed, self.devices[0])
            w = {k: np.asarray(v) for k, v in w.items()}   # off the device
            n = self.traffic["check_steps"]
            batches = [self.pool[k % len(self.pool)] for k in range(n)]
            with jax.default_device(self.devices[0]):
                return train_lean.follow(
                    ref_mod, self.cfg, self.model_mod.param_specs(self.cfg), w,
                    batches, self.denom, self.cfg["optimizer"],
                    lowp.PRECISIONS[precision],
                    rows_per_block=self.cfg.get("reference_rows_per_block", 16),
                    **faults)
        finally:
            self._unpark(kept)
