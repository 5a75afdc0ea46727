"""SSD single-shot detector (BASELINE config 3).

Parity target: GluonCV SSD-512 built on this framework's contrib box ops
(ref: the reference carries the op layer — src/operator/contrib/
multibox_prior.cc / multibox_target.cc / multibox_detection.cc — and the
model assembly lives in example/ssd + GluonCV ssd.py; this module is the
in-tree assembly of those ops).  The headline `ssd_512_vgg16` uses the
reference's actual backbone — VGG16 with the reduced/atrous fc6-fc7
(ref: example/ssd/symbol/symbol_vgg16_reduced.py) — while `ssd_toy` /
`ssd_300` / `ssd_512` keep the small convnet stand-ins for tests.

TPU-first notes: every stage is static-shape — anchors are computed from
feature-map shapes at trace time, targets are vmapped matching (no
dynamic boolean indexing), and NMS is the padded mask-based box_nms — so
the whole train step jits into one executable.  The atrous fc6 is a
dilated conv XLA maps straight onto the MXU.
"""
from __future__ import annotations

from ..gluon.block import HybridBlock
from ..gluon import nn

__all__ = ["SSD", "ssd_300", "ssd_512", "ssd_512_vgg16", "ssd_toy",
           "VGG16ReducedFeatures", "ssd_training_targets", "SSDTrainLoss"]


def _down_block(channels):
    blk = nn.HybridSequential()
    for _ in range(2):
        blk.add(nn.Conv2D(channels, kernel_size=3, padding=1))
        blk.add(nn.BatchNorm(in_channels=channels))
        blk.add(nn.Activation("relu"))
    blk.add(nn.MaxPool2D(pool_size=2))
    return blk


class _StackedFeatures(HybridBlock):
    """Toy multi-scale extractor (tests/smokes): a stack of
    conv-BN-relu down-blocks, one feature map per block."""

    def __init__(self, base_channels, **kwargs):
        super().__init__(**kwargs)
        self.blocks = nn.HybridSequential()
        for ch in base_channels:
            self.blocks.add(_down_block(ch))

    def forward(self, x):
        feats = []
        for blk in self.blocks:
            x = blk(x)
            feats.append(x)
        return feats


def _vgg_stage(num, channels):
    blk = nn.HybridSequential()
    for _ in range(num):
        blk.add(nn.Conv2D(channels, kernel_size=3, padding=1,
                          activation="relu"))
    return blk


class VGG16ReducedFeatures(HybridBlock):
    """VGG16-reduced-atrous SSD feature extractor (ref:
    example/ssd/symbol/symbol_vgg16_reduced.py): conv1_1..conv4_3, then
    conv5 + the subsampled fc6 (3x3 conv, dilation 6) / fc7 (1x1 conv)
    pair, then the conv8..conv12 extra stages.  Returns 7 feature maps
    for a 512x512 input (64, 32, 16, 8, 4, 2, 1 spatial).

    conv4_3's head branch is channel-L2-normalized with a learned
    per-channel scale (init 20) — the original SSD trick to balance its
    larger activation magnitudes against the deeper maps.
    """

    def __init__(self, **kwargs):
        super().__init__(**kwargs)
        from ..initializer import Constant
        self.stage1 = nn.HybridSequential()     # -> conv4_3 (stride 8)
        self.stage1.add(_vgg_stage(2, 64), nn.MaxPool2D(pool_size=2),
                        _vgg_stage(2, 128), nn.MaxPool2D(pool_size=2),
                        _vgg_stage(3, 256), nn.MaxPool2D(pool_size=2),
                        _vgg_stage(3, 512))
        self.stage2 = nn.HybridSequential()     # -> fc7 (stride 16)
        self.stage2.add(nn.MaxPool2D(pool_size=2), _vgg_stage(3, 512))
        # pool5 is 3x3 stride-1 (keeps resolution; fc6's dilation-6
        # atrous conv supplies the receptive field instead)
        self.stage2.add(nn.MaxPool2D(pool_size=3, strides=1, padding=1))
        self.stage2.add(nn.Conv2D(1024, kernel_size=3, padding=6,
                                  dilation=6, activation="relu"))  # fc6
        self.stage2.add(nn.Conv2D(1024, kernel_size=1,
                                  activation="relu"))              # fc7
        self.extras = nn.HybridSequential()
        for squeeze, out, kernel, stride, pad in (
                (256, 512, 3, 2, 1),        # conv8  -> 16
                (128, 256, 3, 2, 1),        # conv9  -> 8
                (128, 256, 3, 2, 1),        # conv10 -> 4
                (128, 256, 3, 2, 1),        # conv11 -> 2
                (128, 256, 4, 1, 1)):       # conv12 -> 1
            blk = nn.HybridSequential()
            blk.add(nn.Conv2D(squeeze, kernel_size=1, activation="relu"),
                    nn.Conv2D(out, kernel_size=kernel, strides=stride,
                              padding=pad, activation="relu"))
            self.extras.add(blk)
        self.norm_scale = self.params.get(
            "norm_scale", shape=(1, 512, 1, 1), init=Constant(20.0))

    def forward(self, x):
        from .. import ndarray as F
        c43 = self.stage1(x)
        # head branch only: the un-normalized conv4_3 feeds stage 2
        feats = [F.L2Normalization(c43, mode="channel")
                 * self.norm_scale.data(ctx=c43.context)]
        f = self.stage2(c43)
        feats.append(f)
        for blk in self.extras:
            f = blk(f)
            feats.append(f)
        return feats


class SSD(HybridBlock):
    """Multi-scale one-shot detector.

    ``features`` is any block mapping the image to a LIST of feature
    maps (one per anchor scale); ``base_channels`` builds the toy
    stacked extractor instead.  Returns (anchors (1, N, 4), cls_preds
    (B, N, classes+1), box_preds (B, N*4)) — the exact tensors
    MultiBoxTarget / MultiBoxDetection consume."""

    def __init__(self, classes, base_channels=None, features=None,
                 sizes=((0.2, 0.272), (0.37, 0.447), (0.54, 0.619)),
                 ratios=((1, 2, 0.5),) * 3, **kwargs):
        super().__init__(**kwargs)
        if features is None:
            if base_channels is None:
                raise ValueError(
                    "SSD: pass either features= (a block returning a "
                    "list of feature maps) or base_channels= (toy "
                    "stacked extractor)")
            assert len(base_channels) == len(sizes)
            features = _StackedFeatures(base_channels)
        assert len(sizes) == len(ratios)
        self._classes = classes
        self._sizes = sizes
        self._ratios = ratios
        self.features = features
        self.cls_preds = nn.HybridSequential()
        self.box_preds = nn.HybridSequential()
        for i in range(len(sizes)):
            a = len(sizes[i]) + len(ratios[i]) - 1
            self.cls_preds.add(nn.Conv2D(a * (classes + 1), kernel_size=3,
                                         padding=1))
            self.box_preds.add(nn.Conv2D(a * 4, kernel_size=3, padding=1))

    def forward(self, x):
        from .. import ndarray as F
        B = x.shape[0]
        anchors, cls_outs, box_outs = [], [], []
        for i, feat in enumerate(self.features(x)):
            anchors.append(F.MultiBoxPrior(feat, sizes=self._sizes[i],
                                           ratios=self._ratios[i]))
            c = self.cls_preds[i](feat)
            cls_outs.append(c.transpose((0, 2, 3, 1)).reshape(
                (B, -1, self._classes + 1)))
            b = self.box_preds[i](feat)
            box_outs.append(b.transpose((0, 2, 3, 1)).reshape((B, -1)))
        anchors = F.concat(*anchors, dim=1)             # (1, N, 4)
        cls_preds = F.concat(*cls_outs, dim=1)          # (B, N, C+1)
        box_preds = F.concat(*box_outs, dim=1)          # (B, N*4)
        return anchors, cls_preds, box_preds


def ssd_training_targets(anchors, cls_preds, labels):
    """MultiBoxTarget front (ref: example/ssd training_targets)."""
    from .. import ndarray as F
    return F.MultiBoxTarget(anchors, labels,
                            cls_preds.transpose((0, 2, 1)))


def ssd_toy(classes=1, **kwargs):
    """Small config for tests/smokes (32×32 inputs)."""
    return SSD(classes, base_channels=(8, 16), sizes=((0.2, 0.3),
                                                      (0.5, 0.6)),
               ratios=((1, 2, 0.5),) * 2, **kwargs)


def ssd_300(classes=20, **kwargs):
    return SSD(classes, base_channels=(32, 64, 128, 128),
               sizes=((0.1, 0.141), (0.2, 0.272), (0.37, 0.447),
                      (0.54, 0.619)),
               ratios=((1, 2, 0.5),) * 4, **kwargs)


def ssd_512(classes=20, **kwargs):
    """Small-convnet 512×512 config (kept as a smoke model; the
    config-3 headline is `ssd_512_vgg16`)."""
    return SSD(classes, base_channels=(32, 64, 128, 128, 256),
               sizes=((0.07, 0.1), (0.15, 0.222), (0.3, 0.367),
                      (0.45, 0.519), (0.6, 0.671)),
               ratios=((1, 2, 0.5),) * 5, **kwargs)


def ssd_512_vgg16(classes=20, **kwargs):
    """Config-3 headline geometry: SSD-512 on VGG16-reduced-atrous —
    the reference's benchmark model (ref: example/ssd
    symbol_vgg16_reduced.py; GluonCV ssd_512_vgg16_atrous sizes/ratios,
    normalized to [0, 1])."""
    sizes = ((0.07, 0.1025), (0.15, 0.2121), (0.3, 0.3674),
             (0.45, 0.5196), (0.6, 0.6708), (0.75, 0.8216),
             (0.9, 0.9721))
    ratios = ((1, 2, 0.5),) + ((1, 2, 0.5, 3, 1.0 / 3),) * 4 \
        + ((1, 2, 0.5),) * 2
    return SSD(classes, features=VGG16ReducedFeatures(),
               sizes=sizes, ratios=ratios, **kwargs)


class SSDTrainLoss(HybridBlock):
    """Hybridizable SSD training loss: MultiBoxTarget + softmax-CE +
    smooth-L1 in ONE cached-op block, so net(x) → loss(...) composes
    into a single fused train-step executable (the eager target/loss
    ops otherwise break whole-step fusion).

    forward(anchors, cls_preds, box_preds, labels) → scalar loss.
    """

    def __init__(self, box_weight=1.0, **kwargs):
        super().__init__(**kwargs)
        self._box_w = box_weight
        from ..gluon.loss import SoftmaxCrossEntropyLoss
        # child block: reuses the ONE fused-CE hot path (gluon/loss.py)
        # and traces inline, so fusion is preserved
        self._ce = SoftmaxCrossEntropyLoss()

    def hybrid_forward(self, F, anchors, cls_preds, box_preds, labels):
        # F.* throughout: this block must also trace with Symbol inputs
        # (export path); -3 merges (B, N) into one axis
        loc_t, loc_m, cls_t = F.MultiBoxTarget(
            anchors, labels, F.transpose(cls_preds, axes=(0, 2, 1)))
        ce = F.mean(self._ce(F.reshape(cls_preds, (-3, 0)),
                             F.reshape(cls_t, (-1,))))
        box_l = F.mean(F.smooth_l1(box_preds - loc_t) * loc_m)
        return ce + self._box_w * box_l
