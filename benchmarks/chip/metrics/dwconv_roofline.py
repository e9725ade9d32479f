"""The depthwise convs' share of their roofline, in percent.

Layer: the XLA operations on the device whose ``op_name`` holds a block's
``dwconv`` scope (``kinds.py``): every depthwise conv and its bias, with
what XLA fused into them.  The least time the chip could take for all of
them (the larger of their FLOPs over the peak at the configuration's
precision and their minimal bytes, each map read and written once, over
HBM bandwidth: the adapter's ``kind_work``; ConvNeXt-T's are bound by
bytes), for every query of the traced window, over their device time.
"""

import kinds


def read(run):
    return kinds.kind_roofline(run, "dwconv")
