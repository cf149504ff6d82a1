"""Device milliseconds a step inside flash attention's BACKWARD kernels
(``flash_attention_dq`` and ``flash_attention_dkv``, the two-pass form
that 16,384 positions take, or the one-pass ``flash_attention_bwd``):
the own time of their events."""

from benchmark.metrics._program import kernel_ms_per_step

KERNELS = ("flash_attention_dq", "flash_attention_dkv",
           "flash_attention_bwd")


def read(run):
    return kernel_ms_per_step(run, KERNELS)
