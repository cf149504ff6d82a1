"""The ReGLU experts' grouped matrix products' share of their roofline
in the windowed cell (``grouped_matmul_roofline_pct``'s reading,
declared for this cell): the FLOPs and bytes the rows really routed to
the held experts require over the own time of the ``grouped_matmul_*``
events."""

from benchmark.metrics.grouped_matmul_roofline_pct import read  # noqa: F401
