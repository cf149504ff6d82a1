"""The whole step's share of the chip's bf16 peak in the sparse cell:
the FLOPs the model requires for a record (``flops/<config>.py``: the
experts' from the rows the router really sent to the held experts,
attention's from the pairs the mask allows, nothing recomputed) times
the records a second of the traced window, over the published peak."""

from benchmark.metrics._sparse import routed_rows


def read(run):
    rows = routed_rows(run)
    if run["peaks"] is None or not run["records"] or rows is None:
        return None
    need = run["flops"].train_flops_per_record(
        run["cfg"], rows / run["records"])
    return 100.0 * need * run["records"] / run["window_s"] / (
        run["peaks"]["bf16_flops_per_s"] * run["device"]["count"])
