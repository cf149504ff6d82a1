"""Differential flash attention's share of its roofline: the FLOPs and
bytes that two maps a pair over the pairs the three layers' masks allow
require (``flops.attention_per_step``: QK^T at 64, PV at 128) over the
own time of the ``flash_attention_*`` events."""

from benchmark.metrics._sparse import roofline_pct


def read(run):
    return roofline_pct(run, ("flash_attention_",),
                        run["flops"].attention_per_step(run["cfg"]))
