#!/usr/bin/env python3
"""Record the small trace the reducer's tests read (chip only): three
"steps" of a jitted matmul chain plus one Pallas flash-attention call, with
host sleeps between them, under the harness's own spans. Writes
``chiprun_out/small_trace.xplane.pb``; copy it to
``perfbench/tests/data/small_trace.xplane.pb``."""

import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from perfbench.harness import tracing, xplane  # noqa: E402


def main():
    from paddle_tpu.ops.pallas.flash_attention import flash_attention
    assert jax.devices()[0].platform == "tpu"

    @jax.jit
    def step(x, q):
        y = jnp.tanh(x @ x)
        o = flash_attention(q, q, q, True, None)
        return y, o

    x = jnp.ones((1024, 1024), jnp.bfloat16) * 0.01
    q = jnp.ones((1, 512, 4, 64), jnp.bfloat16)
    jax.block_until_ready(step(x, q))
    tr = tracing.Tracer("perfbench/.trace_small")
    tr.start()
    for _ in range(3):
        with jax.profiler.TraceAnnotation("bench/engine.step"):
            jax.block_until_ready(step(x, q))
        with jax.profiler.TraceAnnotation("bench/stamp"):
            time.sleep(0.002)
    tr.stop()
    src = xplane.find_xplane(tr.dir)
    os.makedirs("chiprun_out", exist_ok=True)
    shutil.copy(src, "chiprun_out/small_trace.xplane.pb")
    print(os.path.getsize(src), "bytes")
    tr.discard()


if __name__ == "__main__":
    main()
