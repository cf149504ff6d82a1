"""The whole step's share of the chip's bf16 peak in the hybrid cell:
the FLOPs the model requires for a record (``flops/<config>.py``: the
matrix products, attention's two maps over the pairs its masks allow,
the scan's operations; nothing recomputed) times the records a second of
the traced window, over the published peak."""


def read(run):
    if run["peaks"] is None or not run["records"]:
        return None
    need = run["flops"].train_flops_per_record(run["cfg"])
    return 100.0 * need * run["records"] / run["window_s"] / (
        run["peaks"]["bf16_flops_per_s"] * run["device"]["count"])
