#!/usr/bin/env python
"""Does a layout-changing program survive the persistent compilation cache?

Run it TWICE in a row on the chip with the same (fresh)
``JAX_COMPILATION_CACHE_DIR``. The first process compiles
``jax.device_put(x, Format(layout))`` and gets the layout it asked for; the
second is served the same entries from the cache and — on jax 0.9.0 / libtpu
0.0.34, `TPU v5 lite` (my chip run, PR 22) — gets the DEFAULT layout back for
every layout it asks for. ``runtime/model_wrapper._relayout`` exists because
of this; when this probe prints OK on both runs, that workaround can go.
"""

import ml_dtypes
import numpy as np

import jax
from jax.experimental.layout import Format, Layout
from jax.sharding import SingleDeviceSharding

jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

dev = jax.devices()[0]
print("device:", dev.platform, dev.device_kind)
sharding = SingleDeviceSharding(dev)
x = jax.device_put(np.zeros((16, 1024, 8, 64), ml_dtypes.bfloat16), sharding)
print("default layout:", x.format.layout.major_to_minor)
for order in [(0, 1, 2, 3), (0, 2, 3, 1), (0, 1, 3, 2)]:
    fmt = Format(Layout(major_to_minor=order, tiling=((8, 128), (2, 1))), sharding)
    got = jax.device_put(x, fmt).format.layout.major_to_minor
    print("asked", order, "got", got, "OK" if got == order else "WRONG")
