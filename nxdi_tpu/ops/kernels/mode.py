"""How the Pallas kernels of this package are lowered: compiled by Mosaic on
a TPU backend, run by the Pallas interpreter everywhere else (the CPU test
suite). THE one place that asks the backend — every kernel module calls
``mode.interpret()`` so a test can steer all of them with one monkeypatch."""

import jax


def interpret() -> bool:
    return jax.devices()[0].platform != "tpu"
