"""The busiest held expert's rows over the mean of the held experts,
worst layer: the program's ``moe_expert_load_max_over_mean{layer}`` as
it stood at the window's end (each layer's value is of its last
interval between two reads).  1.0 is an even split."""


def read(run):
    values = [v for k, v in run["after"].get("gauges", {}).items()
              if k.startswith("moe_expert_load_max_over_mean")]
    return max(values) if values else None
