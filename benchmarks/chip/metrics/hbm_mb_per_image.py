"""HBM bytes the device moved per image, in MB (1e6 bytes).

Layer: the XLA operations on the device.  The paper's own metric is the
data that layer-by-layer dataflow moves between layers; on a TPU that is
HBM traffic.  Every operation of the traced window is counted from the
operand and result types in its trace event (``scopes.hbm_bytes``:
buffers in HBM only, logical bytes, an async transfer once), summed, and
divided by the images the window completed.
"""

import scopes


def read(run):
    total = scopes.window_hbm_bytes(run)
    if not total or run.traced.images == 0:
        return None
    return total / run.traced.images / 1e6
