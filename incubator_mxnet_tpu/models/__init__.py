"""Model families beyond the Gluon model zoo (transformer/BERT etc.)."""
from . import transformer
from .transformer import (BERTModel, TransformerEncoder, bert_base,
                          bert_small, TransformerNMT,
                          transformer_nmt_base, transformer_nmt_small)
from . import wide_deep as wide_deep_mod
from .wide_deep import WideDeep, wide_deep
from .ssd import (SSD, ssd_300, ssd_512, ssd_512_vgg16, ssd_toy,
                  VGG16ReducedFeatures, ssd_training_targets,
                  SSDTrainLoss)
from .seq2seq import Seq2Seq, GNMT, gnmt_large, gnmt_sym_gen
from .sparse_decoder import (SparseDecoder, SelectAttention, HeldExperts,
                             RMSNorm)
from .hybrid_decoder import HybridDecoder, GatedAttention, GatedDeltaNet
from .latent_decoder import LatentDecoder, LatentAttention, DenseSwiGLU
from .looped_decoder import LoopedDecoder, SandwichAttention
from .window_decoder import WindowDecoder, KindAttention
from .faster_rcnn import (FasterRCNN, faster_rcnn_toy,
                          faster_rcnn_resnet50_v1b,
                          rcnn_training_targets, RCNNTrainLoss)

__all__ = ["transformer", "BERTModel", "TransformerEncoder", "bert_base",
           "TransformerNMT", "transformer_nmt_base",
           "transformer_nmt_small",
           "bert_small", "WideDeep", "wide_deep", "SSD", "ssd_300",
           "ssd_512", "ssd_512_vgg16", "VGG16ReducedFeatures",
           "ssd_toy", "ssd_training_targets", "SSDTrainLoss",
           "Seq2Seq", "GNMT", "gnmt_large",
           "FasterRCNN", "faster_rcnn_toy", "faster_rcnn_resnet50_v1b",
           "rcnn_training_targets",
           "RCNNTrainLoss",
           "gnmt_sym_gen", "SparseDecoder", "SelectAttention",
           "HeldExperts", "RMSNorm", "HybridDecoder", "GatedAttention",
           "GatedDeltaNet", "LatentDecoder", "LatentAttention",
           "DenseSwiGLU", "LoopedDecoder", "SandwichAttention",
           "WindowDecoder", "KindAttention"]
