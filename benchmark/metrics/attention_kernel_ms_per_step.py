"""Device milliseconds a step inside flash attention's three kernels
(``flash_attention_fwd``, ``flash_attention_dq``,
``flash_attention_dkv``)."""

from benchmark.metrics._program import kernel_ms_per_step


def read(run):
    return kernel_ms_per_step(run, ("flash_attention_",))
