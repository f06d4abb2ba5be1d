"""The serving benchmark of nxdi_tpu: ``python benchmark/run.py --workload ...``.

Everything that decides a number lives here (traffic generation, the window,
the arithmetic, the trace reducer, the peaks table, the references); the
program under ``nxdi_tpu/`` is only the system under test. See README.md.
"""
