#!/usr/bin/env python3
"""Record the small trace the harness's unit test keeps: three 'steps' of a
named matmul under the benchmark's spans, with a host sleep in ``data``.
Run once on the chip; the file goes to chiprun_out/small_trace/."""
import glob
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def main(out="chiprun_out/small_trace"):
    import jax
    import jax.numpy as jnp

    from spans import Spans

    assert jax.devices()[0].platform == "tpu"
    spans = Spans(True)

    @jax.jit
    def bench_small_matmul(a):
        return jnp.tanh(a @ a)

    a = jnp.ones((2048, 2048), jnp.bfloat16)
    bench_small_matmul(a).block_until_ready()
    (a * 2).block_until_ready()
    tmp = "chiprun_out/_small_tmp"
    shutil.rmtree(tmp, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(tmp, profiler_options=opts)
    for _ in range(3):
        with spans("data"):
            time.sleep(0.004)
        with spans("fwd"):
            y = bench_small_matmul(a)
        with spans("update"):
            z = y * 2
            time.sleep(0.002)
    jax.block_until_ready((y, z))
    jax.profiler.stop_trace()
    os.makedirs(out, exist_ok=True)
    src = glob.glob(os.path.join(tmp, "plugins", "profile", "*", "*.xplane.pb"))[0]
    shutil.copy(src, os.path.join(out, "small.xplane.pb"))
    print("small trace", os.path.getsize(src))


if __name__ == "__main__":
    main()
