"""The whole step's share of the chip's bf16 peak: the FLOPs the model
requires for a record (``flops/<config>.py``, forward and backward,
nothing recomputed) times the records a second of the traced window,
over the published peak.  Only on a device that peaks.json knows."""


def read(run):
    if run["peaks"] is None or not run["records"]:
        return None
    rate = run["records"] / run["window_s"]
    need = run["flops"].train_flops_per_record(run["cfg"]) * rate
    return 100.0 * need / (run["peaks"]["bf16_flops_per_s"]
                           * run["device"]["count"])
