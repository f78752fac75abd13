#!/usr/bin/env python3
"""Record the small trace ``tests/test_trace.py`` reads (run on the chip):
one jitted matmul chain, 20 calls, under a ``bench:window`` annotation.

    python3 benchmarks/tools/record_small_trace.py chiprun_out/small_trace
"""

import glob
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(out_dir: str) -> None:
    import jax
    import jax.numpy as jnp

    from harness import common

    @jax.jit
    def small_chain(x, w):
        for _ in range(4):
            x = jnp.tanh(x @ w)
        return x

    x, w = jnp.ones((512, 512)), jnp.ones((512, 512)) * 0.01
    small_chain(x, w).block_until_ready()
    window = common.TracedWindow(out_dir + ".tmp")
    window.start()
    with common.annotate("window"):
        for _ in range(20):
            small_chain(x, w).block_until_ready()
            time.sleep(0.002)
    window.stop()
    os.makedirs(out_dir, exist_ok=True)
    for path in glob.glob(os.path.join(out_dir + ".tmp", "plugins", "profile", "*", "*.xplane.pb")):
        shutil.copy(path, os.path.join(out_dir, "small_chain.xplane.pb"))
        print(path, os.path.getsize(path), "bytes")
    shutil.rmtree(out_dir + ".tmp", ignore_errors=True)


if __name__ == "__main__":
    main(sys.argv[1])
