"""Device milliseconds a step inside flash attention's kernels under the
global layer's causal mask and the windowed layers' band
(``flash_attention_fwd`` and the backward: ``flash_attention_dq`` +
``_dkv`` past the one-pass budget, as at 16,384 positions, else
``flash_attention_bwd``): the own time of their events."""

from benchmark.metrics._program import kernel_ms_per_step

KERNELS = ("flash_attention_",)


def read(run):
    return kernel_ms_per_step(run, KERNELS)
