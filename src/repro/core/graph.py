"""CNN layer-graph IR for the PIMfused dataflow planner.

The paper (§IV, Fig. 3a) treats a CNN as a sequence of *macro layers* where
element-wise post-ops (BN, ReLU) are folded into their producer by default:
``CONV_BN_RELU`` is one layer.  The IR here captures exactly the properties
the dataflow mapper and tiling engine need:

* spatial geometry (kernel, stride, padding) for receptive-field math,
* channel geometry (cin, cout) for weight/activation footprints,
* op kind, which decides where it may execute (PIMcore vs GBcore) and which
  `PIMcore_CMP` / `GBcore_CMP` execution flag it uses (Table I),
* residual edges (ADD_RELU consumes a second, earlier tensor).

Shapes follow the paper's notation: feature maps are (C, OY, OX); batch is
always 1 for the inference workloads evaluated (ResNet18, §V).
"""

from __future__ import annotations

import dataclasses
import enum
from typing import NamedTuple


class OpKind(enum.Enum):
    """Macro-layer kinds; mirror the execution flags of Table I."""

    CONV_BN = "CONV_BN"          # conv + batch-norm (no activation)
    CONV_BN_RELU = "CONV_BN_RELU"
    POOL_MAX = "POOL_MAX"
    POOL_AVG = "POOL_AVG"
    ADD_RELU = "ADD_RELU"        # residual add + relu
    FC = "FC"                    # final classifier (GEMV on PIM)

    @property
    def is_conv(self) -> bool:
        return self in (OpKind.CONV_BN, OpKind.CONV_BN_RELU)

    @property
    def is_pool(self) -> bool:
        return self in (OpKind.POOL_MAX, OpKind.POOL_AVG)

    @property
    def is_spatial(self) -> bool:
        """True if the op slides a window over (oy, ox)."""
        return self.is_conv or self.is_pool

    @property
    def pimcore_flag(self) -> str | None:
        """PIMcore_CMP execution flag (Table I), if PIMcore-executable."""
        return {
            OpKind.CONV_BN: "CONV_BN",
            OpKind.CONV_BN_RELU: "CONV_BN_RELU",
            OpKind.POOL_MAX: "POOL",
            OpKind.POOL_AVG: "POOL",
            OpKind.ADD_RELU: "ADD_RELU",
            OpKind.FC: "CONV_BN",  # FC lowers to a 1x1 MAC op on PIMcores
        }[self]

    @property
    def gbcore_flag(self) -> str | None:
        """GBcore_CMP execution flag (Table I): POOL / ADD_RELU only."""
        if self.is_pool:
            return "POOL"
        if self is OpKind.ADD_RELU:
            return "ADD_RELU"
        return None


@dataclasses.dataclass(frozen=True)
class Layer:
    """One macro layer of the CNN graph."""

    name: str
    kind: OpKind
    cin: int
    cout: int
    # input spatial extent (iy, ix) and output extent (oy, ox)
    iy: int
    ix: int
    oy: int
    ox: int
    kh: int = 1
    kw: int = 1
    stride: int = 1
    padding: int = 0
    # channel groups for grouped/depthwise convolution (MobileNet-style
    # depthwise = groups == cin == cout); each output channel only sees
    # cin // groups input channels.
    groups: int = 1
    # name of the layer producing the PRIMARY input; None = previous layer in
    # list order (or the graph input for the first layer).  Shortcut convs
    # (e.g. ResNet down-sample 1x1) read the block input, not their list
    # predecessor, so they set this explicitly.
    input_of: str | None = None
    # name of the layer whose OUTPUT is the residual operand, for ADD_RELU
    residual_of: str | None = None

    def __post_init__(self) -> None:
        if self.groups < 1 or self.cin % self.groups or self.cout % self.groups:
            raise ValueError(
                f"{self.name}: groups={self.groups} must divide "
                f"cin={self.cin} and cout={self.cout}")

    # ---- footprint helpers (element counts; dtype handled by caller) ----
    @property
    def cin_per_group(self) -> int:
        return self.cin // self.groups

    @property
    def weight_elems(self) -> int:
        if self.kind.is_conv:
            return (self.cout * self.cin_per_group * self.kh * self.kw
                    + 2 * self.cout)  # +BN scale/shift
        if self.kind is OpKind.FC:
            return self.cout * self.cin + self.cout
        return 0

    @property
    def in_elems(self) -> int:
        return self.cin * self.iy * self.ix

    @property
    def out_elems(self) -> int:
        return self.cout * self.oy * self.ox

    @property
    def macs_per_position(self) -> int:
        """MACs per (oy, ox) output position across all output channels —
        the unit fused tiling scales by a tile's computed positions."""
        if self.kind.is_conv:
            return self.cout * self.cin_per_group * self.kh * self.kw
        return 0

    @property
    def macs(self) -> int:
        """Multiply-accumulate count for the whole layer."""
        if self.kind.is_conv:
            return self.oy * self.ox * self.macs_per_position
        if self.kind is OpKind.FC:
            return self.cout * self.cin
        return 0

    @property
    def alu_ops(self) -> int:
        """Non-MAC element ops (pool compares/adds, residual adds, relu)."""
        if self.kind.is_pool:
            return self.out_elems * self.kh * self.kw
        if self.kind is OpKind.ADD_RELU:
            return 2 * self.out_elems
        # BN+ReLU folded into conv epilogue
        return 0

    def out_extent_for(self, in_y: int, in_x: int) -> tuple[int, int]:
        """Output extent produced by this layer from a given input extent."""
        if not self.kind.is_spatial:
            return in_y, in_x
        oy = (in_y + 2 * self.padding - self.kh) // self.stride + 1
        ox = (in_x + 2 * self.padding - self.kw) // self.stride + 1
        return oy, ox

    def in_extent_for(self, out_y: int, out_x: int) -> tuple[int, int]:
        """Input extent REQUIRED to produce an output tile of (out_y, out_x).

        This is the receptive-field step used by fused-layer tiling (Fig. 1b):
        required_input = (out - 1) * stride + kernel   (before padding clip).
        """
        if not self.kind.is_spatial:
            return out_y, out_x
        ry = (out_y - 1) * self.stride + self.kh
        rx = (out_x - 1) * self.stride + self.kw
        return ry, rx


@dataclasses.dataclass
class Graph:
    """A linear chain of macro layers with optional residual side-edges.

    ResNet-style graphs are chains once ADD_RELU layers record which earlier
    layer output they re-consume; this matches the paper's Fig. 3(a) drawing.
    """

    name: str
    layers: list[Layer]

    def __post_init__(self) -> None:
        by_name = {lyr.name: lyr for lyr in self.layers}
        if len(by_name) != len(self.layers):
            raise ValueError(f"duplicate layer names in graph {self.name}")
        # refs to layers not in this graph are EXTERNAL: they denote the
        # graph/group input (sliced fused groups reference the group input
        # by the name of the producing layer outside the slice).
        self.external_refs = {
            ref for lyr in self.layers for ref in (lyr.residual_of, lyr.input_of)
            if ref is not None and ref not in by_name
        }
        self._index = {lyr.name: i for i, lyr in enumerate(self.layers)}

    def __len__(self) -> int:
        return len(self.layers)

    def __iter__(self):
        return iter(self.layers)

    def __getitem__(self, i):
        return self.layers[i]

    def index_of(self, name: str) -> int:
        return self._index[name]

    @property
    def total_macs(self) -> int:
        return sum(lyr.macs for lyr in self.layers)

    @property
    def total_weight_elems(self) -> int:
        return sum(lyr.weight_elems for lyr in self.layers)

    def slice(self, start: int, stop: int, name: str | None = None) -> "Graph":
        return Graph(name or f"{self.name}[{start}:{stop}]", self.layers[start:stop])


# ---------------------------------------------------------------------------
# ResNet18 builder (the paper's benchmark, §V).
# ---------------------------------------------------------------------------

def _conv(name: str, cin: int, cout: int, iy: int, ix: int, k: int, s: int,
          p: int, relu: bool = True, input_of: str | None = None,
          groups: int = 1) -> Layer:
    oy = (iy + 2 * p - k) // s + 1
    ox = (ix + 2 * p - k) // s + 1
    return Layer(name=name, kind=OpKind.CONV_BN_RELU if relu else OpKind.CONV_BN,
                 cin=cin, cout=cout, iy=iy, ix=ix, oy=oy, ox=ox,
                 kh=k, kw=k, stride=s, padding=p, input_of=input_of,
                 groups=groups)


def build_resnet18(input_hw: int = 224, num_classes: int = 1000) -> Graph:
    """ResNet18 as a macro-layer chain (CONV_BN_RELU folding per the paper).

    Layer counting follows the paper: CONV_BN_RELU / POOL / ADD_RELU / FC are
    each ONE layer.  The first 8 layers are

        L0 conv7x7/2, L1 maxpool/2,
        L2..L5 stage-1 convs, plus ADD_RELU after each pair (L4', L7')...

    The paper's fused-kernel splits ("first 8 layers", "next 7") are applied
    by the fusion planner over this list, so the list order is what matters.
    """
    L: list[Layer] = []
    hw = input_hw
    # Stem
    L.append(_conv("conv1", 3, 64, hw, hw, k=7, s=2, p=3))
    hw = L[-1].oy
    pool_oy = (hw + 2 * 1 - 3) // 2 + 1
    L.append(Layer("maxpool", OpKind.POOL_MAX, 64, 64, hw, hw, pool_oy, pool_oy,
                   kh=3, kw=3, stride=2, padding=1))
    hw = pool_oy

    stage_channels = [64, 128, 256, 512]
    cin = 64
    for si, cout in enumerate(stage_channels):
        for bi in range(2):  # two BasicBlocks per stage
            stride = 2 if (si > 0 and bi == 0) else 1
            blk = f"s{si + 1}b{bi + 1}"
            in_name = L[-1].name
            L.append(_conv(f"{blk}_conv1", cin, cout, hw, hw, k=3, s=stride, p=1))
            mid_hw = L[-1].oy
            L.append(_conv(f"{blk}_conv2", cout, cout, mid_hw, mid_hw, k=3, s=1,
                           p=1, relu=False))
            shortcut_name = in_name
            if stride != 1 or cin != cout:
                # Shortcut conv reads the BLOCK input, not its list predecessor.
                L.append(_conv(f"{blk}_down", cin, cout, hw, hw, k=1, s=stride,
                               p=0, relu=False, input_of=in_name))
                shortcut_name = L[-1].name
            # ADD consumes conv2's output as primary input (the down conv, if
            # present, sits between them in list order, so wire explicitly).
            L.append(Layer(f"{blk}_add", OpKind.ADD_RELU, cout, cout,
                           mid_hw, mid_hw, mid_hw, mid_hw,
                           input_of=f"{blk}_conv2", residual_of=shortcut_name))
            hw = mid_hw
            cin = cout

    # Global average pool + FC
    L.append(Layer("avgpool", OpKind.POOL_AVG, 512, 512, hw, hw, 1, 1,
                   kh=hw, kw=hw, stride=hw, padding=0))
    L.append(Layer("fc", OpKind.FC, 512, num_classes, 1, 1, 1, 1))
    return Graph("resnet18", L)


class ConvGeometry(NamedTuple):
    """One ResNet18 conv as the fused CONV_BN[+ADD][+RELU] op sees it."""
    hw: int            # input height = width
    cin: int
    cout: int
    k: int
    stride: int
    padding: int
    relu: bool
    residual: bool     # ADD of the block's shortcut before the ReLU

    @property
    def name(self) -> str:
        flags = "+ADD_RELU" if self.residual else "+RELU" if self.relu else ""
        return (f"{self.k}x{self.k}/{self.stride} "
                f"{self.hw}x{self.cin}->{self.cout}{flags}")


def conv_geometries(image: int = 224) -> list[ConvGeometry]:
    """The distinct conv geometries of ResNet18 on image×image inputs, in
    network order, read off ``build_resnet18``: each conv layer, with a
    block's conv2 fused with the ADD_RELU that consumes it."""
    layers = build_resnet18(image).layers
    fused_add = {lyr.input_of for lyr in layers
                 if lyr.kind is OpKind.ADD_RELU}
    geoms = [ConvGeometry(lyr.iy, lyr.cin, lyr.cout, lyr.kh, lyr.stride,
                          lyr.padding,
                          relu=(lyr.kind is OpKind.CONV_BN_RELU
                                or lyr.name in fused_add),
                          residual=lyr.name in fused_add)
             for lyr in layers if lyr.kind.is_conv]
    return list(dict.fromkeys(geoms))


def first_n_layers(g: Graph, n: int) -> Graph:
    """Workload slice, e.g. the paper's ResNet18_First8Layers (§V-2)."""
    return g.slice(0, n, name=f"{g.name}_first{n}")


# ---------------------------------------------------------------------------
# Additional CNN workloads (beyond the paper's ResNet18 benchmark): a plain
# VGG-style chain and a MobileNet-style depthwise-separable net, exercising
# the dataflow mappers on residual-free and grouped-conv graphs.
# ---------------------------------------------------------------------------

def build_vgg11(input_hw: int = 224, num_classes: int = 1000) -> Graph:
    """VGG11 (configuration A) as a macro-layer chain.

    Eight 3x3 convs interleaved with five 2x2 maxpools, then the three-layer
    fully-connected classifier.  No residual edges, so fusion-plan boundaries
    come purely from tile-grid divisibility.
    """
    L: list[Layer] = []
    hw = input_hw
    cin = 3
    # (conv channel plan, pool-after flags) per VGG-A
    plan = [(64, True), (128, True), (256, False), (256, True),
            (512, False), (512, True), (512, False), (512, True)]
    for i, (cout, pool_after) in enumerate(plan):
        L.append(_conv(f"conv{i + 1}", cin, cout, hw, hw, k=3, s=1, p=1))
        cin = cout
        if pool_after:
            pool_hw = hw // 2
            L.append(Layer(f"pool{i + 1}", OpKind.POOL_MAX, cout, cout,
                           hw, hw, pool_hw, pool_hw, kh=2, kw=2, stride=2))
            hw = pool_hw
    flat = cin * hw * hw
    L.append(Layer("fc1", OpKind.FC, flat, 4096, 1, 1, 1, 1))
    L.append(Layer("fc2", OpKind.FC, 4096, 4096, 1, 1, 1, 1))
    L.append(Layer("fc3", OpKind.FC, 4096, num_classes, 1, 1, 1, 1))
    return Graph("vgg11", L)


def build_mobilenet_v1(input_hw: int = 224,
                       num_classes: int = 1000) -> Graph:
    """MobileNetV1 as a macro-layer chain of depthwise-separable blocks.

    Each block is a depthwise 3x3 conv (``groups == cin``) followed by a
    pointwise 1x1 conv; 13 blocks after the full-conv stem, then global
    average pool + FC.  Exercises the ``groups`` field end-to-end.
    """
    L: list[Layer] = []
    hw = input_hw
    L.append(_conv("conv1", 3, 32, hw, hw, k=3, s=2, p=1))
    hw = L[-1].oy
    cin = 32
    # (cout, stride) per depthwise-separable block (standard V1 schedule)
    blocks = [(64, 1), (128, 2), (128, 1), (256, 2), (256, 1), (512, 2),
              (512, 1), (512, 1), (512, 1), (512, 1), (512, 1),
              (1024, 2), (1024, 1)]
    for i, (cout, s) in enumerate(blocks):
        L.append(_conv(f"b{i + 1}_dw", cin, cin, hw, hw, k=3, s=s, p=1,
                       groups=cin))
        hw = L[-1].oy
        L.append(_conv(f"b{i + 1}_pw", cin, cout, hw, hw, k=1, s=1, p=0))
        cin = cout
    L.append(Layer("avgpool", OpKind.POOL_AVG, cin, cin, hw, hw, 1, 1,
                   kh=hw, kw=hw, stride=hw))
    L.append(Layer("fc", OpKind.FC, cin, num_classes, 1, 1, 1, 1))
    return Graph("mobilenet_v1", L)
