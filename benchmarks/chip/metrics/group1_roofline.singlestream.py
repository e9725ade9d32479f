"""Fused group 1's share of its roofline for one image per query, in
percent.

Layer: the XLA operations on the device that compute the stem conv, its
max-pool and stage 1, found by the layer scopes the program puts in their
``op_name`` (``scopes.py``).  At batch 1 XLA reshapes the maps (the stem
conv's output is ``[112,8,16,64]``), so the output-height rule of
``group1_roofline.py`` cannot find them.  The least time the chip could
take for the group's work on one image (``group_work`` at the traffic's
batch), for every query of the traced window, over the device time of
the group's operations.
"""

import scopes

GROUP = "group1"


def read(run):
    return scopes.group_roofline(run, GROUP)
