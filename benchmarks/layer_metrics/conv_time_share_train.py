"""conv_time_share.train: the share of the device's op time spent in
convolution operations, by the names the compiler gives them."""

LAYER = "model"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "train_clips_per_s_per_chip"


def read(run):
    if run.trace is None or not run.trace.busy_s:
        return None
    conv = sum(s for name, s in run.trace.op_seconds.items()
               if is_convolution(name, run.trace.op_text.get(name, "")))
    return 100.0 * conv / sum(run.trace.op_seconds.values()) if conv else None


def is_convolution(name: str, text: str) -> bool:
    """By the names the compiler gives: an instruction or a fusion that it
    names after a convolution (``%convolution_add_fusion.3``), a bare
    ``convolution(`` instruction, or an output fusion (``kind=kOutput``:
    on the TPU the fusions rooted in a convolution; a matrix product is
    a convolution to this compiler too)."""
    return ("convolution" in name or " convolution(" in text
            or "kind=kOutput" in text)
