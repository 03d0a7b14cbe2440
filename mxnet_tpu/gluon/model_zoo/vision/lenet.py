"""LeNet-5 on MNIST (SURVEY.md §7).

Not in the reference model_zoo (it lives in example/gluon/mnist); included
here as a first-class model since it is a driver baseline config.
"""
from __future__ import annotations

from ... import nn
from ...block import HybridBlock

__all__ = ["LeNet", "lenet"]


class LeNet(HybridBlock):
    def __init__(self, classes=10, **kw):
        super().__init__(**kw)
        self.features = nn.HybridSequential()
        self.features.add(
            nn.Conv2D(20, kernel_size=5, activation="tanh"),
            nn.MaxPool2D(2, 2),
            nn.Conv2D(50, kernel_size=5, activation="tanh"),
            nn.MaxPool2D(2, 2),
            nn.Flatten(),
            nn.Dense(500, activation="tanh"))
        self.output = nn.Dense(classes)

    def forward(self, x):
        return self.output(self.features(x))


def lenet(classes=10, **kw):
    return LeNet(classes=classes, **kw)
