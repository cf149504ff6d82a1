"""The busiest held expert's rows over the mean of the held experts,
worst layer, under the sigmoid router and its selection bias
(``expert_load_max_over_mean``'s reading, declared for this cell)."""

from benchmark.metrics.expert_load_max_over_mean import read  # noqa: F401
