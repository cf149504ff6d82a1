"""What the metrics of the ``sdar-30b-a3b-chat`` cell share: the rows
the program's expert layers routed in the window
(``moe_rows_routed_total{layer, held}``, published by
``observability/moe_stats.py`` at the syncs the train loop has), and a
kernel family's share of its roofline.  A program without the counters
or the kernels reads ``None``, never an error."""

from benchmark.harness import counter_delta
from benchmark.metrics._program import kernel_ms_per_step


def routed_rows(run, held="1"):
    """Assignments routed in the window to the experts held here
    (``held="1"``: the rows the grouped products processed), all layers
    together; ``None`` when the counter did not move."""
    moved = [v for label, v in counter_delta(
        run["after"], run["before"], "moe_rows_routed_total").items()
        if 'held="%s"' % held in label]
    return sum(moved) if moved else None


def roofline_pct(run, names, need):
    """``need = (FLOPs, bytes)`` a step requires of the kernels in
    ``names``: the least time the chip could take for them over the own
    time of their events, as a percentage."""
    ms = kernel_ms_per_step(run, names)
    if ms is None or run["peaks"] is None:
        return None
    flops, nbytes = need
    least = max(flops / run["peaks"]["bf16_flops_per_s"],
                nbytes / run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / (1e-3 * ms)
