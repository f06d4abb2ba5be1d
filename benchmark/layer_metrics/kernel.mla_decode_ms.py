"""Kernels: device time of the absorbed latent-attention decode kernel per
token-generation execution: the ``XLA Ops`` events whose instruction name is
``mla_paged_decode`` or ``mla_paged_decode.<n>`` (the kernel's ``name=``),
first chip, inside the executions of ``jit_token_generation_model*``, summed
(one launch a layer) and divided by the number of those executions. ms.
Nothing to read where the trace has no such module or instruction (a program
without the kernel: every dense configuration, and the parent of PR 30)."""

from benchmark import program_trace

KERNEL = "mla_paged_decode"


def read(run):
    planes = program_trace.of(run)
    if planes is None:
        return None
    v = program_trace.kernel_s_per_execution(planes, KERNEL)
    return None if v is None else v * 1e3
