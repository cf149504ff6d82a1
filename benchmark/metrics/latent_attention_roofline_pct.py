"""Latent flash attention's share of its roofline: the FLOPs and bytes
that attention over the causal pairs requires
(``flops.attention_per_step``: QK^T at 192, PV at 128, ``k_pe`` read
once) over the own time of the ``flash_attention_latent_*`` events."""

from benchmark.metrics._sparse import roofline_pct
from benchmark.metrics.latent_attention_ms_per_step import KERNELS


def read(run):
    return roofline_pct(run, KERNELS,
                        run["flops"].attention_per_step(run["cfg"]))
