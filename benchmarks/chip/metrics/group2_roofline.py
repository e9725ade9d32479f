"""Fused group ``group2``'s share of its roofline, in percent.

Layer: the XLA operations on the device that compute stage 2, found by
the layer scopes the program puts in their ``op_name`` (``scopes.py``).
The least time the chip could take for the group's work (the larger of
its FLOPs over the peak at the configuration's precision and its minimal
bytes over HBM bandwidth, the adapter's ``group_work``), for every query
of the traced window, over the device time of the group's operations.
"""

import scopes

GROUP = "group2"


def read(run):
    return scopes.group_roofline(run, GROUP)
