"""Faster-RCNN two-stage detector (BASELINE config 3, second half).

Parity target: the reference carries the op layer — src/operator/contrib/
{proposal.cc, proposal_target.cc, roi_align.cc} — with model assembly in
example/rcnn + GluonCV faster_rcnn.py; this module is the in-tree
assembly over this framework's `_contrib_Proposal` /
`_contrib_ProposalTarget` / `ROIAlign` ops.

TPU-first notes: the RPN → proposal → ROIAlign → head chain is entirely
fixed-shape (padded proposals carry -1 rows and zero-weight targets), so
train and inference steps trace into single XLA executables; NMS and ROI
sampling are the vectorised lax implementations in ops/rcnn.py."""
from __future__ import annotations

from ..gluon.block import HybridBlock
from ..gluon import nn

__all__ = ["FasterRCNN", "faster_rcnn_toy", "faster_rcnn_resnet50_v1b",
           "rcnn_training_targets", "RCNNTrainLoss"]


def _conv_block(channels, stride=1):
    blk = nn.HybridSequential()
    blk.add(nn.Conv2D(channels, kernel_size=3, strides=stride, padding=1))
    blk.add(nn.BatchNorm(in_channels=channels))
    blk.add(nn.Activation("relu"))
    return blk


class FasterRCNN(HybridBlock):
    """Two-stage detector: backbone → RPN → proposals → ROIAlign →
    class/box heads.

    forward(x, im_info) returns
      (cls_pred (R, classes+1), box_pred (R, 4*(classes+1)),
       rois (R, 5), rpn_cls (B, 2A, H, W), rpn_box (B, 4A, H, W))
    with R = B * rpn_post_nms_top_n — everything downstream (targets,
    losses, detection decode) consumes these fixed-shape tensors."""

    def __init__(self, classes, backbone_channels=(16, 32, 64),
                 feature_stride=8, rpn_channels=64,
                 anchor_scales=(2, 4), anchor_ratios=(0.5, 1, 2),
                 rpn_pre_nms_top_n=256, rpn_post_nms_top_n=64,
                 rpn_min_size=4, roi_size=7, top_units=128,
                 features=None, top_features=None, **kwargs):
        super().__init__(**kwargs)
        self._classes = classes
        self._stride = feature_stride
        self._scales = anchor_scales
        self._ratios = anchor_ratios
        self._pre = rpn_pre_nms_top_n
        self._post = rpn_post_nms_top_n
        self._min_size = rpn_min_size
        self._roi = roi_size
        num_anchors = len(anchor_scales) * len(anchor_ratios)

        if features is not None:
            # externally supplied backbone (e.g. resnet50_v1b stages
            # 1-3), mirroring the reference's pretrained-backbone
            # assembly (ref: example/rcnn/symdata resnet conv4 feature)
            self.features = features
        else:
            # toy backbone: simple strided conv stack (stride = prod 2s)
            import math
            n_down = int(math.log2(feature_stride))
            self.features = nn.HybridSequential()
            for i, ch in enumerate(backbone_channels):
                self.features.add(_conv_block(ch, stride=2 if i < n_down
                                              else 1))
        # RPN
        self.rpn_conv = nn.Conv2D(rpn_channels, kernel_size=3, padding=1,
                                  activation="relu")
        self.rpn_cls = nn.Conv2D(2 * num_anchors, kernel_size=1)
        self.rpn_box = nn.Conv2D(4 * num_anchors, kernel_size=1)
        # heads: a conv `top_features` (e.g. resnet stage 4 + global avg
        # pool, the reference's conv5 head) consumes the 4-D pooled
        # rois; the default dense top consumes them flattened
        self._conv_top = top_features is not None
        if self._conv_top:
            self.top = top_features
        else:
            self.top = nn.HybridSequential()
            self.top.add(nn.Dense(top_units, activation="relu"),
                         nn.Dense(top_units, activation="relu"))
        self.cls_head = nn.Dense(classes + 1)
        self.box_head = nn.Dense(4 * (classes + 1))

    def forward(self, x, im_info, gt_boxes=None, batch_rois=None,
                num_classes=None):
        """Inference: forward(x, im_info) →
            (cls_pred, box_pred, rois, rpn_cls, rpn_box)
        over all rpn_post_nms_top_n proposals.

        Training: forward(x, im_info, gt_boxes) runs ProposalTarget
        BETWEEN proposal and ROIAlign (like the reference's train graph)
        so head predictions align row-for-row with the sampled rois →
            (cls_pred, box_pred, rois, labels, bbox_targets,
             bbox_weights, rpn_cls, rpn_box)."""
        from .. import ndarray as F
        feat = self.features(x)
        rpn = self.rpn_conv(feat)
        rpn_cls = self.rpn_cls(rpn)                  # (B, 2A, H, W)
        rpn_box = self.rpn_box(rpn)                  # (B, 4A, H, W)
        B, twoA = rpn_cls.shape[0], rpn_cls.shape[1]
        # softmax over {bg, fg} per anchor
        sig = F.reshape(rpn_cls, (B, 2, -1))
        prob = F.softmax(sig, axis=1)
        rpn_prob = F.reshape(prob, (B, twoA) + rpn_cls.shape[2:])
        rois = F.invoke(
            "_contrib_Proposal", rpn_prob, rpn_box, im_info,
            rpn_pre_nms_top_n=self._pre, rpn_post_nms_top_n=self._post,
            rpn_min_size=self._min_size, scales=self._scales,
            ratios=self._ratios, feature_stride=self._stride)

        target = None
        if gt_boxes is not None:
            target = F.invoke(
                "_contrib_ProposalTarget", rois, gt_boxes,
                num_classes=(num_classes or self._classes) + 1,
                batch_images=B,
                batch_rois=batch_rois or self._post)
            rois = target[0]                 # sampled + reordered

        pooled = F.invoke("ROIAlign", feat, rois,
                          pooled_size=(self._roi, self._roi),
                          spatial_scale=1.0 / self._stride)
        if self._conv_top:
            top = self.top(pooled)
            top = F.reshape(top, (top.shape[0], -1))
        else:
            top = self.top(F.reshape(pooled, (pooled.shape[0], -1)))
        cls_pred = self.cls_head(top)
        box_pred = self.box_head(top)
        if target is not None:
            _, labels, bbox_targets, bbox_weights = target
            return (cls_pred, box_pred, rois, labels, bbox_targets,
                    bbox_weights, rpn_cls, rpn_box)
        return cls_pred, box_pred, rois, rpn_cls, rpn_box


def rcnn_training_targets(rois, gt_boxes, num_classes,
                          batch_rois=64, fg_fraction=0.25,
                          fg_overlap=0.5):
    """ROI sampling + targets for the box head (ref: proposal_target.cc
    consumed by example/rcnn train_end2end)."""
    from .. import ndarray as F
    return F.invoke("_contrib_ProposalTarget", rois, gt_boxes,
                    num_classes=num_classes + 1,
                    batch_images=int(gt_boxes.shape[0]),
                    batch_rois=batch_rois, fg_fraction=fg_fraction,
                    fg_overlap=fg_overlap)


def faster_rcnn_resnet50_v1b(classes=20, **kwargs):
    """Config-3b headline geometry: Faster-RCNN on resnet50_v1b — the
    backbone the reference benchmarks (ref: example/rcnn resnet
    symbol: conv1-conv4 as the shared feature, conv5 as the per-roi
    head; GluonCV faster_rcnn_resnet50_v1b).  Stages 1-3 (stride 16,
    1024 ch) feed the RPN; stage 4 + global average pooling is the
    per-roi top — ROIAlign at 14x14, stage 4's stride-2 takes it to
    7x7, pooled to a 2048-vector per roi.

    TPU-first: proposals are the padded mask-based NMS over the top
    2000 anchors, sampling keeps rois fixed-shape, so the whole train
    graph is one XLA executable at ~600x800 input."""
    from ..gluon.model_zoo.vision import resnet50_v1b
    base = resnet50_v1b()
    features = nn.HybridSequential()
    for i in range(7):          # stem (conv, bn, relu, pool) + stages 1-3
        features.add(base.features[i])
    top = nn.HybridSequential()
    top.add(base.features[7])   # stage 4 (stride 2: 14x14 roi -> 7x7)
    from ..gluon.nn import GlobalAvgPool2D
    top.add(GlobalAvgPool2D())
    kwargs.setdefault("rpn_pre_nms_top_n", 2000)
    kwargs.setdefault("rpn_post_nms_top_n", 1000)
    return FasterRCNN(classes, features=features, top_features=top,
                      feature_stride=16, rpn_channels=512,
                      anchor_scales=(8, 16, 32),
                      anchor_ratios=(0.5, 1, 2),
                      rpn_min_size=16, roi_size=14, **kwargs)


def faster_rcnn_toy(classes=3, **kwargs):
    """Tiny config for tests/smoke training."""
    return FasterRCNN(classes, backbone_channels=(8, 16),
                      feature_stride=4, rpn_channels=16,
                      anchor_scales=(2, 4), anchor_ratios=(0.5, 1, 2),
                      rpn_pre_nms_top_n=64, rpn_post_nms_top_n=16,
                      rpn_min_size=2, roi_size=3, top_units=32, **kwargs)


class RCNNTrainLoss(HybridBlock):
    """Hybridizable Faster-RCNN head loss (classification CE over
    sampled ROIs + smooth-L1 on weighted box targets), so the training
    forward's 8 outputs feed ONE fused loss program instead of a chain
    of eager ops.

    forward(cls_pred, box_pred, labels, bbox_targets, bbox_weights)
    → scalar loss.  (Proposal/ProposalTarget already ran inside the
    net's training forward.)
    """

    def __init__(self, box_weight=0.1, **kwargs):
        super().__init__(**kwargs)
        self._box_w = box_weight
        from ..gluon.loss import SoftmaxCrossEntropyLoss
        # child block: reuses the ONE fused-CE hot path (gluon/loss.py)
        self._ce = SoftmaxCrossEntropyLoss()

    def hybrid_forward(self, F, cls_pred, box_pred, labels, targets,
                       weights):
        # F.* throughout: must also trace with Symbol inputs (export)
        mask = F._greater_equal_scalar(labels, scalar=0.0)
        safe = F.clip(labels, a_min=0.0, a_max=1e9)
        cls_l = F.mean(self._ce(cls_pred, safe) * mask)
        box_l = F.mean(F.sum(
            F.smooth_l1((box_pred - targets) * weights, scalar=1.0),
            axis=1))
        return cls_l + self._box_w * box_l
