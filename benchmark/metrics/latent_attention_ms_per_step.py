"""Device milliseconds a step inside latent flash attention's three
kernels (``flash_attention_latent_fwd``, ``flash_attention_latent_dq``,
``flash_attention_latent_dkv``): the own time of their events."""

from benchmark.metrics._program import kernel_ms_per_step

KERNELS = ("flash_attention_latent_",)


def read(run):
    return kernel_ms_per_step(run, KERNELS)
