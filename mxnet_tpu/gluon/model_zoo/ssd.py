"""SSD object detector (SSD-ResNet50).

The reference ships SSD as example/ssd + the multibox C++ ops
(src/operator/contrib/multibox_*.cc); GluonCV made it a zoo model. Here:
a HybridBlock SSD over a ResNet feature backbone with extra downsampling
stages, per-scale class/box conv heads, closed-form anchors
(ops/boxes.py multibox_prior), multibox_target training targets, and
decode+NMS inference via multibox_detection — all static-shape, jit-able.
"""
from __future__ import annotations

from typing import List, Sequence

import jax.numpy as jnp

from ... import numpy_extension as npx
from ...ndarray import NDArray
from ...ops import boxes as _boxes
from ...ops.dispatch import call
from .. import nn
from ..block import HybridBlock

__all__ = ["SSD", "ssd_512_resnet50_v1", "ssd_300_resnet34_v1",
           "SSDAnchorGenerator", "training_targets"]


class _FeatureExpander(HybridBlock):
    """Backbone trunk + extra conv stages producing multi-scale maps."""

    def __init__(self, backbone_features: Sequence[HybridBlock],
                 num_extras: int = 3, extra_channels: int = 256, **kw):
        super().__init__(**kw)
        self.trunk = nn.HybridSequential()
        for b in backbone_features:
            self.trunk.add(b)
        self.extras = nn.HybridSequential()
        for _ in range(num_extras):
            blk = nn.HybridSequential()
            blk.add(nn.Conv2D(extra_channels // 2, 1, activation="relu"),
                    nn.Conv2D(extra_channels, 3, strides=2, padding=1,
                              activation="relu"))
            self.extras.add(blk)

    def forward(self, x):
        feats = []
        y = self.trunk(x)
        feats.append(y)
        for blk in self.extras:
            y = blk(y)
            feats.append(y)
        return feats


class SSDAnchorGenerator:
    """Per-scale anchors; pure host-side closed form (multibox_prior)."""

    def __init__(self, sizes: Sequence[Sequence[float]],
                 ratios: Sequence[Sequence[float]]):
        self.sizes = sizes
        self.ratios = ratios

    def num_anchors_per_cell(self, scale_i: int) -> int:
        return len(self.sizes[scale_i]) + len(self.ratios[scale_i]) - 1

    def anchors_for(self, feat_shapes: Sequence[tuple]) -> jnp.ndarray:
        all_anchors = [
            _boxes.multibox_prior(fs, self.sizes[i], self.ratios[i])
            for i, fs in enumerate(feat_shapes)]
        return jnp.concatenate(all_anchors, 0)           # (A, 4)


class SSD(HybridBlock):
    """forward(x) -> (cls_preds (B, A, C+1), box_preds (B, A*4),
    anchors (A, 4) NDArray)."""

    def __init__(self, backbone_features, num_classes: int,
                 sizes: Sequence[Sequence[float]],
                 ratios: Sequence[Sequence[float]],
                 num_extras: int = 3, **kw):
        super().__init__(**kw)
        self.num_classes = num_classes
        self.features = _FeatureExpander(backbone_features,
                                         num_extras=num_extras)
        self.anchor_gen = SSDAnchorGenerator(sizes, ratios)
        self.class_predictors = nn.HybridSequential()
        self.box_predictors = nn.HybridSequential()
        n_scales = num_extras + 1
        if len(sizes) != n_scales or len(ratios) != n_scales:
            raise ValueError("one (sizes, ratios) entry per scale required")
        for i in range(n_scales):
            a = self.anchor_gen.num_anchors_per_cell(i)
            self.class_predictors.add(
                nn.Conv2D(a * (num_classes + 1), 3, padding=1))
            self.box_predictors.add(nn.Conv2D(a * 4, 3, padding=1))

    def forward(self, x):
        feats = self.features(x)
        cls_outs: List = []
        box_outs: List = []
        shapes = []
        for i, f in enumerate(feats):
            shapes.append((f.shape[2], f.shape[3]))
            c = self.class_predictors[i](f)      # (B, A*(C+1), H, W)
            bx = self.box_predictors[i](f)       # (B, A*4, H, W)
            cls_outs.append(self._flatten_pred(c, self.num_classes + 1))
            box_outs.append(self._flatten_pred(bx, 4))
        from ... import numpy as mnp
        cls_preds = mnp.concatenate(cls_outs, axis=1)    # (B, A, C+1)
        box_preds = mnp.concatenate(box_outs, axis=1)    # (B, A, 4)
        anchors = NDArray(self.anchor_gen.anchors_for(shapes))
        return cls_preds, box_preds.reshape(box_preds.shape[0], -1), anchors

    @staticmethod
    def _flatten_pred(p, last_dim):
        # (B, A*D, H, W) -> (B, H*W*A, D). Recorded as the registered
        # 'flatten_pred' op (symbol.symbol._flatten_pred_op) so a json
        # reload re-executes batch-polymorphically — an inline reshape
        # would bake the traced batch size into the graph.
        from ...symbol.symbol import _flatten_pred_op

        return call(lambda x: _flatten_pred_op(NDArray(x), last_dim)._data,
                    (p,), {}, name="flatten_pred",
                    attrs={"last_dim": last_dim})


def training_targets(anchors, labels, cls_preds=None, iou_thresh=0.5):
    """multibox_target over NDArrays -> (box_target, box_mask, cls_target)."""
    def f(a, lab):
        return _boxes.multibox_target(a, lab, iou_thresh=iou_thresh)
    return call(f, (anchors, labels), {}, name="multibox_target")


def detections(cls_preds, box_preds, anchors, nms_threshold=0.45,
               threshold=0.01, nms_topk=400):
    """softmax + multibox_detection -> (B, A, 6) decoded detections."""
    import jax

    def f(cp, bp, a):
        prob = jax.nn.softmax(cp, -1).transpose(0, 2, 1)  # (B, C+1, A)
        return _boxes.multibox_detection(prob, bp, a, threshold=threshold,
                                         nms_threshold=nms_threshold,
                                         nms_topk=nms_topk)
    return call(f, (cls_preds, box_preds, anchors), {},
                name="multibox_detection")


def _resnet_feature_trunk(name: str, thumbnail=False):
    from .vision.resnet import get_resnet

    version = 1
    layers = {"resnet34_v1": 34, "resnet50_v1": 50}[name]
    net = get_resnet(version, layers, thumbnail=thumbnail)
    # all conv stages, dropping the trailing global pool (stride-32 map)
    return [net.features[:-1]]


def ssd_512_resnet50_v1(classes: int = 20, **kwargs):
    """SSD-512 with ResNet-50 v1 trunk."""
    sizes = [[0.1, 0.141], [0.2, 0.272], [0.37, 0.447], [0.54, 0.619]]
    ratios = [[1, 2, 0.5]] * 4
    return SSD(_resnet_feature_trunk("resnet50_v1"), classes,
               sizes, ratios, num_extras=3, **kwargs)


def ssd_300_resnet34_v1(classes: int = 20, **kwargs):
    sizes = [[0.1, 0.141], [0.2, 0.272], [0.37, 0.447], [0.54, 0.619]]
    ratios = [[1, 2, 0.5]] * 4
    return SSD(_resnet_feature_trunk("resnet34_v1"), classes,
               sizes, ratios, num_extras=3, **kwargs)
