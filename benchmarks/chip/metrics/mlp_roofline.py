"""The blocks' MLPs' share of their roofline, in percent.

Layer: the XLA operations on the device whose ``op_name`` holds a block's
``mlp`` scope (``kinds.py``): LayerNorm, the expansion, GELU, the
projection and the layer scale, with what XLA fused into them.  The least
time the chip could take for all of them (the larger of their FLOPs over
the peak at the configuration's precision and their minimal bytes over
HBM bandwidth: the adapter's ``kind_work``; ConvNeXt-T's are bound by
FLOPs), for every query of the traced window, over their device time.
"""

import kinds


def read(run):
    return kinds.kind_roofline(run, "mlp")
