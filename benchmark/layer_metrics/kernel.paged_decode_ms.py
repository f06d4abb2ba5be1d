"""Kernels: device time of the paged decode attention kernel per
token-generation execution: the ``XLA Ops`` events whose instruction name is
``paged_attention_decode`` or ``paged_attention_decode.<n>`` (the kernel's
``name=``, which the trace carries in the instruction name, not in a stat),
first chip, inside the executions of ``jit_token_generation_model*``, summed
and divided by the number of those executions. ms. Nothing to read where the
trace has no such module or instruction."""

from benchmark import program_trace

KERNEL = "paged_attention_decode"


def read(run):
    planes = program_trace.of(run)
    if planes is None:
        return None
    v = program_trace.kernel_s_per_execution(planes, KERNEL)
    return None if v is None else v * 1e3
