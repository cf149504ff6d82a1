"""Rows a step routed to the experts held on this chip, all sparse
layers together (``moe_rows_per_step``'s reading, declared for this
cell): an even router gives ``T x top_k x held / published`` a layer."""

from benchmark.metrics.moe_rows_per_step import read  # noqa: F401
