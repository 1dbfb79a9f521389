#!/usr/bin/env python3
"""Record the small device trace that test_xplane.py reduces.

    python3 benchmark/tests/record_tiny_trace.py <out.xplane.pb>

Three rounds of two jitted steps with a pause between rounds, traced with
the JAX profiler on whatever device JAX finds (the kept file was recorded
on the chip). Prints what the reduction must find.
"""

import glob
import json
import os
import shutil
import sys
import tempfile
import time


def main() -> int:
    import jax
    import jax.numpy as jnp

    @jax.jit
    def tiny_matmul_step(x, w):
        return jnp.tanh(x @ w)

    @jax.jit
    def tiny_reduce_step(x):
        return (x * 2.0).sum()

    x = jnp.ones((512, 512), jnp.bfloat16)
    w = jnp.ones((512, 512), jnp.bfloat16) * 0.01
    tiny_reduce_step(tiny_matmul_step(x, w)).block_until_ready()
    tmp = tempfile.mkdtemp(prefix="tiny-trace-")
    jax.profiler.start_trace(tmp)
    for _ in range(3):
        tiny_reduce_step(tiny_matmul_step(x, w)).block_until_ready()
        time.sleep(0.02)
    jax.profiler.stop_trace()
    found = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)
    shutil.copy(found[0], sys.argv[1])
    shutil.rmtree(tmp, ignore_errors=True)
    dev = jax.devices()[0]
    print(json.dumps({"platform": dev.platform, "kind": dev.device_kind,
                      "bytes": os.path.getsize(sys.argv[1]),
                      "rounds": 3,
                      "modules": ["jit_tiny_matmul_step",
                                  "jit_tiny_reduce_step"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
