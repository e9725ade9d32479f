"""ConvNeXt-T (Liu et al. 2022, arXiv:2201.03545): a modern pure CNN whose
blocks are a depthwise 7x7 conv, LayerNorm and a GELU MLP.  CNN config
consumed by repro.models.convnext; not part of the LM cells."""

from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="convnext-tiny",
    family="cnn",
    num_layers=18,            # ConvNeXt blocks, 3/3/9/3
    vocab_size=1000,          # classifier classes
    norm_eps=1e-6,
    dtype="float32",
    param_dtype="float32",
)
