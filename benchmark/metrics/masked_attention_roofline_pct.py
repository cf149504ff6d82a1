"""Masked flash attention's share of its roofline: the FLOPs and bytes
that attention over the pairs the block-diffusion mask allows requires
(``flops.attention_per_step``) over the own time of the
``flash_attention_*`` events."""

from benchmark.metrics._sparse import roofline_pct


def read(run):
    return roofline_pct(run, ("flash_attention_",),
                        run["flops"].attention_per_step(run["cfg"]))
