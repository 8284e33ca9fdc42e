"""Model zoo: every family the reference's benchmarks/scaling table
exercises — ResNets (`examples/tensorflow2_synthetic_benchmark.py:35-40`),
Inception V3 and VGG-16/19 (the 90%/90%/68% scaling-efficiency trio,
`README.rst:74-79`) — plus the long-context transformer flagship and the
hybrid whose blocks are described by data (Mamba-2, attention and gated
short-conv mixers; SwiGLU and routed-expert feed-forwards)."""

from .hybrid import HybridLM
from .inception import InceptionV3
from .resnet import (ResNet, ResNet18, ResNet34, ResNet50, ResNet101,
                     ResNet152)
from .transformer import TransformerLM
from .vgg import VGG, VGG16, VGG19

__all__ = ["HybridLM", "InceptionV3", "ResNet", "ResNet18", "ResNet34",
           "ResNet50", "ResNet101", "ResNet152", "TransformerLM", "VGG",
           "VGG16", "VGG19"]
