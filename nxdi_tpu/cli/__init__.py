"""Command-line entry points. Serving and generation CLIs run on the backend
JAX attaches by default (the TPU where there is one) and on the CPU when asked
(``--on-cpu``, or ``JAX_PLATFORMS=cpu`` in the environment, which the tests
set). Only the analysis CLIs that cost or audit a program for a *named* part
from abstract structs (``lint``, ``costs``) pick the CPU themselves."""


def add_on_cpu_flag(parser) -> None:
    parser.add_argument(
        "--on-cpu", action="store_true",
        help="run on the CPU backend (8 virtual devices) instead of the "
             "attached accelerator",
    )


def use_cpu_backend(n_devices: int = 8) -> None:
    """Hold this process to the CPU backend with ``n_devices`` virtual
    devices. Must run before the first JAX computation."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", n_devices)
