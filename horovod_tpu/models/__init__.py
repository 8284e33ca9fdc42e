"""Model zoo: the ResNets of the reference's synthetic benchmark
(`examples/tensorflow2_synthetic_benchmark.py:35-40`), the long-context
transformer flagship and the hybrid whose blocks are described by data
(Mamba-2, attention and gated short-conv mixers; SwiGLU and routed-expert
feed-forwards)."""

from .hybrid import HybridLM
from .resnet import (ResNet, ResNet18, ResNet34, ResNet50, ResNet101,
                     ResNet152)
from .transformer import TransformerLM

__all__ = ["HybridLM", "ResNet", "ResNet18", "ResNet34", "ResNet50",
           "ResNet101", "ResNet152", "TransformerLM"]
