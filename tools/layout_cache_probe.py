#!/usr/bin/env python3
"""Is ``jax.device_put(x, Format(Layout(...), sharding))`` safe with the
persistent compilation cache on this installation? (Run on the chip.)

    python3 tools/layout_cache_probe.py

Three fresh processes share one new, empty cache directory and place the
same arrays with a layout that is not the device's default, then hand
them to a jitted consumer. With JAX 0.9.0 on a TPU v5e the first process
is fine and every later one fails: the placing program (an identity with
a result layout) then comes from the cache, the array it returns reports
the DEFAULT layout and size while its buffer has the layout asked for,
and the consumer is compiled for the one and refused the other
(``INVALID_ARGUMENT: Executable(...) expected parameter 0 of size ...``).
That is why ``server/bank.py`` asks for no layout and stores its stacks in
shapes whose default layout is the one it needs (``_stored_shape``).
"""

import os
import subprocess
import sys
import tempfile

SHAPES = [((4096, 200), (0, 1)), ((512, 300, 250), (0, 1, 2)), ((512, 250, 300), (0, 2, 1))]


def child() -> None:
    import jax
    import numpy as np
    from jax.experimental.layout import Format, Layout
    from jax.sharding import SingleDeviceSharding

    # cache every program, however quickly it compiled
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    home = SingleDeviceSharding(jax.devices()[0])
    pick = jax.jit(lambda a, i: jax.lax.dynamic_index_in_dim(a, i, 0))
    for shape, order in SHAPES:
        x = np.arange(np.prod(shape), dtype=np.float32).reshape(shape)
        a = jax.device_put(x, Format(Layout(order), home))
        line = (f"{shape}: asked {order}, reports {a.format.layout.major_to_minor} "
                f"({a.on_device_size_in_bytes()} bytes)")
        try:
            line += f"; consumer ok, equal={np.array_equal(np.asarray(pick(a, 3))[0], x[3])}"
        except Exception as exc:
            line += f"; consumer FAILED: {str(exc)[:160]}"
        print(line, flush=True)


if __name__ == "__main__":
    if sys.argv[1:] == ["child"]:
        child()
    else:
        cache = tempfile.mkdtemp(prefix="layout-probe-cache-")
        for k in (1, 2, 3):
            print(f"== process {k}, cache {cache}", flush=True)
            subprocess.run(
                [sys.executable, __file__, "child"],
                env=dict(os.environ, JAX_COMPILATION_CACHE_DIR=cache),
            )
