"""tests/tpu/ runs on an attached chip: ``NXDI_TPU_HW_TESTS=1 python -m pytest
tests/tpu/ -q`` (one process — a chip belongs to one process at a time).
Everywhere else the ``tpu`` fixture skips; the backend is asked inside the
fixture, never while a module is imported."""

import pytest


@pytest.fixture(scope="session")
def tpu():
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        pytest.skip("needs TPU hardware")
    return dev
