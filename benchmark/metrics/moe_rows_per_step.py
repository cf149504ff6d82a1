"""Rows a step routed to the experts held on this chip, all layers
together: ``moe_rows_routed_total{held="1"}`` between the window's
snapshots over its steps.  These are the rows the grouped products
processed; an even router gives ``2 L x top_k x held / published`` a
layer."""

from benchmark.metrics._sparse import routed_rows


def read(run):
    rows = routed_rows(run)
    if rows is None or not run["steps"]:
        return None
    return rows / run["steps"]
