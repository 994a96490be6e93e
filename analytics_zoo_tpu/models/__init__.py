"""Built-in model zoo (reference: zoo.models — SURVEY.md §2.7).

Every family from the reference's Scala+Py twin zoo, rebuilt as pure-JAX
modules over analytics_zoo_tpu.nn: recommendation (NeuralCF, WideAndDeep,
SessionRecommender), text classification, text matching (KNRM), anomaly
detection, seq2seq, image classification (ResNet), object detection (SSD),
plus the BERT family the reference shipped through TFPark, and four
causal decoders the reference had no analog of: three sparse-expert ones, a
hybrid linear-attention one (Qwen3Next), a sliding-window / full-attention
one with a bias-balanced sigmoid router (AFMoE) and a latent-attention one
with a multi-token prediction module (GlmMoeLite), and a dense hybrid of
Mamba-2 state-space blocks and attention with a tied, scaled head
(GraniteHybrid).
"""

from .common import ZooModel
from .recommendation import (NCFTail, NeuralCF, SessionRecommender,
                             UserItemFeature, UserItemPrediction,
                             WideAndDeep)
from .textclassification import TextClassifier
from .textmatching import KNRM
from .anomalydetection import AnomalyDetector, unroll
from .seq2seq import Seq2seq, RNNEncoder, RNNDecoder
from .image import ImageClassifier, ResNet
from .objectdetection import ObjectDetector, SSDLite, Visualizer
from .bert import BERT, BERTClassifier, BERTNER, BERTSQuAD
from .qwen3_next import Qwen3Next
from .afmoe import AFMoE
from .granite_hybrid import GraniteHybrid
from .glm_moe_lite import GlmMoeLite
from .graphnet import GraphNet
from .net import ForeignNet, Net

__all__ = [
    "Net", "ForeignNet", "GraphNet",
    "ZooModel", "NeuralCF", "NCFTail", "WideAndDeep", "SessionRecommender",
    "UserItemFeature", "UserItemPrediction", "TextClassifier", "KNRM",
    "AnomalyDetector", "unroll", "Seq2seq", "RNNEncoder", "RNNDecoder",
    "ImageClassifier", "ResNet", "ObjectDetector", "SSDLite", "Visualizer",
    "BERT", "BERTClassifier", "BERTNER", "BERTSQuAD", "Qwen3Next",
    "AFMoE", "GraniteHybrid", "GlmMoeLite",
]
