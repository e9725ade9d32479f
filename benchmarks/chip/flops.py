"""Operations and bytes of a CNN's layers, computed from their shapes.

The benchmark's own arithmetic: an adapter lists its model's layers from a
configuration file's sizes (``models/<model>.py``: ``layers(cfg)``) and
nothing of the program.  A layer is a conv (with its batch-norm), a pool,
a residual add or the classifier; each belongs to one named group of the
configuration (``groups``), so a metric can ask for one group's work.

FLOPs count multiply-adds twice, for the convs and the classifier only:
the element-wise work (batch-norm, ReLU, adds, pools) is under 1% of it
and does not run on the matrix unit.  Minimal bytes are what any
implementation of a group must move through HBM: the group's input map,
its weights (conv taps and four batch-norm vectors per output channel)
and its output map, each once.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Layer:
    name: str
    kind: str            # "conv", "maxpool", "add", "avgpool" or "fc"
    group: str
    cin: int
    cout: int
    in_hw: int
    out_hw: int
    k: int = 1
    stride: int = 1
    padding: int = 0

    @property
    def macs(self) -> int:
        """Multiply-adds per image."""
        if self.kind == "conv":
            return self.out_hw * self.out_hw * self.cout * self.cin * self.k * self.k
        if self.kind == "fc":
            return self.cin * self.cout
        return 0

    @property
    def weights(self) -> int:
        """Parameters: conv taps and batch-norm vectors, or fc weights."""
        if self.kind == "conv":
            return self.k * self.k * self.cin * self.cout + 4 * self.cout
        if self.kind == "fc":
            return self.cin * self.cout + self.cout
        return 0

    def in_elems(self) -> int:
        return self.in_hw * self.in_hw * self.cin

    def out_elems(self) -> int:
        return self.out_hw * self.out_hw * self.cout


def out_hw(hw: int, k: int, s: int, p: int) -> int:
    return (hw + 2 * p - k) // s + 1


def group_layers(layers: list[Layer], group: str | None) -> list[Layer]:
    """The layers of one group, in order (all of them for ``None``)."""
    ls = [lyr for lyr in layers if group is None or lyr.group == group]
    if not ls:
        raise KeyError(f"no layer in group {group!r}")
    return ls


def flops(layers: list[Layer], group: str | None = None) -> int:
    """FLOPs per image of a group (or of the whole model)."""
    return 2 * sum(lyr.macs for lyr in group_layers(layers, group))


def min_bytes(layers: list[Layer], group: str, batch: int,
              bytes_per_elem: int) -> int:
    """Input map + weights + output map of a group, each moved once."""
    ls = group_layers(layers, group)
    maps = batch * (ls[0].in_elems() + ls[-1].out_elems())
    return bytes_per_elem * (maps + sum(lyr.weights for lyr in ls))


def out_rows(layers: list[Layer], group: str) -> set[int]:
    """Output heights of a group's layers: how its ops are told apart."""
    return {lyr.out_hw for lyr in group_layers(layers, group)}
