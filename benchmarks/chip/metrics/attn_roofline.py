"""The blocks' window attention's share of its roofline, in percent.

Layer: the XLA operations on the device whose ``op_name`` holds a block's
``attn`` scope (``kinds.py``): LayerNorm, the cyclic shift, the window
partition, qkv, the logits with bias and mask, softmax, AV, proj, the
window reverse, the shift back and the residual add, with what XLA fused
into them.  The least time the chip could take for all of them (the
larger of their FLOPs over the peak at the configuration's precision and
their minimal bytes, each block's map read and written once with its
weights, over HBM bandwidth: the adapter's ``kind_work``; Swin-T's are
bound by FLOPs), for every query of the traced window, over their device
time.
"""

import kinds


def read(run):
    return kinds.kind_roofline(run, "attn")
