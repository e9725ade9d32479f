"""Model FLOP/s utilization of an untraced window, in percent.

Layer: the model step (``repro.models.resnet``).  Inputs completed over
the window, times the FLOPs one input needs (the adapter's
``flops_per_input``: conv and fc multiply-adds, twice), over the chip's
peak at the configuration's precision (``spec.Bench.flops_peak``: for
float32 at ``highest``, the bf16 peak over its six bfloat16 passes).
"""


def read(run):
    if run.window.images == 0:
        return None
    per_s = run.window.images / run.window.seconds
    return 100.0 * per_s * run.adapter.flops_per_input(run.cfg) / run.flops_peak
