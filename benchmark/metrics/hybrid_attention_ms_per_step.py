"""Device milliseconds a step inside flash attention's three kernels
(``flash_attention_fwd``, ``flash_attention_dq``,
``flash_attention_dkv``) forming the differential pair's two maps under
the window, the causal and the cross layers' masks
(``attention_kernel_ms_per_step``'s reading, declared for this cell)."""

from benchmark.metrics.attention_kernel_ms_per_step import read  # noqa: F401
