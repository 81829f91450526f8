"""``fedml_tpu.models`` — model zoo factory.

Public surface mirrors the reference (``fedml.model.create``,
``python/fedml/model/model_hub.py:20-83``): keyed on ``(args.model,
args.dataset)``. Returns a :class:`ModelBundle` — the Flax module plus enough
input metadata to initialise parameters without a dataset in hand.
"""

from __future__ import annotations

import logging
from dataclasses import dataclass, field
from typing import Any, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..data.datasets import REGISTRY as DATA_REGISTRY
from .layers import MLP
from .nlp import RNNOriginalFedAvg, RNNStackOverflow
from .vision import (
    VGG,
    VGG11_CFG,
    VGG16_CFG,
    CNNDropOut,
    EfficientNetB0,
    LogisticRegression,
    MobileNetV1,
    MobileNetV2,
    MobileNetV3Small,
    resnet18_gn,
    resnet20,
    resnet56,
)

logger = logging.getLogger(__name__)

# model names whose forward is convolution-dominated (cohort-impl heuristic)
CONV_MODEL_FAMILIES = frozenset((
    "cnn", "cnn_dropout", "cnn_web", "resnet18_gn", "resnet18", "resnet20",
    "resnet56", "mobilenet", "mobilenet_v1", "mobilenet_v2", "mobilenet_v3",
    "mobilenet_v3_small", "vgg11", "vgg16", "vgg", "efficientnet",
    "efficientnet_b0", "efficientnet-b0", "fcn", "deeplab", "deeplabv3_plus",
    "unet", "darts", "darts_search", "centernet", "centernet_lite", "yolo",
    "detector", "dcgan", "gan",
))

__all__ = ["create", "ModelBundle"]


@dataclass
class ModelBundle:
    """A Flax module + input spec, the unit the trainers consume."""

    module: nn.Module
    name: str
    input_shape: Tuple[int, ...]  # per-sample shape (no batch dim)
    input_dtype: Any = jnp.float32
    task: str = "classification"
    meta: dict = field(default_factory=dict)

    def dummy_input(self, batch_size: int = 2) -> jax.Array:
        if jnp.issubdtype(self.input_dtype, jnp.integer):
            return jnp.zeros((batch_size,) + self.input_shape, self.input_dtype)
        return jnp.zeros((batch_size,) + self.input_shape, self.input_dtype)

    def init(self, rng: jax.Array, batch_size: int = 2):
        return self.module.init(
            {"params": rng, "dropout": rng}, self.dummy_input(batch_size), train=False
        )

    def apply(self, params, x, train: bool = False, rngs=None):
        return self.module.apply(params, x, train=train, rngs=rngs)

    def param_count(self, params) -> int:
        return sum(int(p.size) for p in jax.tree.leaves(params))


def create(args, output_dim: int) -> ModelBundle:
    """Build a model for ``(args.model, args.dataset)``.

    Name registry follows the reference's dispatch (model_hub.py:20-83):
    lr, cnn (CNN_DropOut), resnet18_gn, resnet20, resnet56, mobilenet,
    mobilenet_v2, mobilenet_v3, efficientnet, vgg11/vgg16, rnn
    (dataset-routed), mlp, fcn/deeplab (segmentation), darts (NAS search).
    """
    name = str(args.model).lower()
    dataset = getattr(args, "dataset", "synthetic")
    spec = DATA_REGISTRY.get(dataset)
    sample_shape = spec.sample_shape if spec else (60,)
    task = spec.task if spec else "classification"
    int_input = task in ("nwp", "seq_tagging", "span_extraction")

    if name in ("cheetah_tagger", "cheetah_span"):
        # FedNLP heads on the REAL Cheetah backbone (row 75 scale path):
        # same transformer as the flagship, task head on hidden states
        from .transformer_heads import create_head_bundle

        return create_head_bundle(
            args, output_dim, spec,
            "tagger" if name == "cheetah_tagger" else "span",
        )

    if name in ("cheetah", "llama", "cheetah_lm"):
        # the flagship Cheetah transformer as a federated model (FedLLM):
        # its own bundle type — local training runs mesh-sharded
        # (cross_silo/fedllm.py), the FL planes see the ModelBundle surface
        from .transformer_lm import create_transformer_bundle

        return create_transformer_bundle(args, output_dim, spec)

    if name in ("lr", "logistic_regression"):
        module: nn.Module = LogisticRegression(output_dim)
    elif name in ("cnn", "cnn_dropout", "cnn_web"):
        module = CNNDropOut(output_dim)
    elif name in ("resnet18_gn", "resnet18"):
        module = resnet18_gn(output_dim)
    elif name == "resnet20":
        module = resnet20(output_dim)
    elif name == "resnet56":
        module = resnet56(output_dim)
    elif name in ("mobilenet", "mobilenet_v1"):
        module = MobileNetV1(output_dim)
    elif name in ("mobilenet_v2",):
        module = MobileNetV2(output_dim)
    elif name == "vgg11":
        module = VGG(VGG11_CFG, output_dim)
    elif name in ("vgg16", "vgg"):
        module = VGG(VGG16_CFG, output_dim)
    elif name == "rnn":
        # dataset-routed like the reference (model_hub.py rnn branches)
        if dataset in ("stackoverflow_nwp",):
            module = RNNStackOverflow(vocab_size=output_dim)
        else:
            module = RNNOriginalFedAvg(vocab_size=output_dim)
    elif name == "mlp":
        module = MLP((128, 64, output_dim))
    elif name in ("efficientnet", "efficientnet_b0", "efficientnet-b0"):
        module = EfficientNetB0(output_dim)
    elif name in ("mobilenet_v3", "mobilenet_v3_small"):
        module = MobileNetV3Small(output_dim)
    elif name in ("fcn", "deeplab", "deeplabv3_plus", "unet"):
        from .segmentation import FCNSeg

        module = FCNSeg(output_dim,
                        width=int(getattr(args, "seg_model_width", 32) or 32))
    elif name in ("darts", "darts_search"):
        from .darts import DartsNetwork

        module = DartsNetwork(output_dim)
    elif name in ("centernet", "centernet_lite", "yolo", "detector"):
        # FedCV detection (reference: app/fedcv/object_detection) —
        # dense anchor-free head, see models/detection.py; real-resolution
        # inputs (>=128px) get a deeper feature stack
        from .detection import CenterNetLite

        widths = (
            (32, 64, 128, 128) if sample_shape[0] >= 128 else (32, 64, 64)
        )
        module = CenterNetLite(num_classes=output_dim, widths=widths)
    elif name in ("transformer", "tiny_transformer", "transformer_lm",
                  "bilstm_tagger", "tagger", "span_extractor", "bilstm_span"):
        # FedNLP zoo (reference: app/fednlp/{seq_tagging,span_extraction,
        # seq2seq}) — all need a token-vocab dataset
        if spec is None or spec.vocab_size <= 0:
            raise ValueError(
                f"model {name!r} needs a text dataset with a vocab "
                f"(got {dataset!r})"
            )
        if name in ("bilstm_tagger", "tagger"):
            from .nlp import TokenTagger

            module = TokenTagger(vocab_size=spec.vocab_size,
                                 num_tags=output_dim)
        elif name in ("span_extractor", "bilstm_span"):
            from .nlp import SpanExtractor

            module = SpanExtractor(vocab_size=spec.vocab_size)
        else:
            from .nlp import TinyTransformerLM

            module = TinyTransformerLM(
                vocab_size=max(spec.vocab_size, output_dim),
                max_len=spec.seq_len if spec.seq_len > 0 else 128,
            )
    elif name in ("gcn", "gat", "sage", "graphsage"):
        # FedGraphNN zoo (reference: app/fedgraphnn/*/model/) — head routed
        # by the dataset's task, conv by the model name
        from .gnn import GraphClassifier, LinkPredictor, NodeClassifier

        conv = {"graphsage": "sage"}.get(name, name)
        if spec is None or spec.n_nodes == 0:
            raise ValueError(
                f"model {name!r} needs a graph dataset (got {dataset!r})"
            )
        if task == "node_clf":
            module = NodeClassifier(spec.n_feats, output_dim, conv=conv)
        elif task == "link_pred":
            module = LinkPredictor(spec.n_feats, conv=conv)
        elif task == "regression":
            module = GraphClassifier(spec.n_feats, 1, conv=conv)
        else:
            module = GraphClassifier(spec.n_feats, output_dim, conv=conv)
    else:
        raise ValueError(f"unknown model {name!r}")

    bundle = ModelBundle(
        module=module,
        name=name,
        input_shape=tuple(sample_shape),
        input_dtype=jnp.int32 if int_input else jnp.float32,
        task=task,
        meta={"dataset": dataset, "output_dim": output_dim},
    )
    # convolutional families: read by the sp engine's cohort rule
    # (sp_api.cohort_chunk_rule). jax.vmap over per-client kernels makes
    # every convolution a grouped one, which the TPU compiler lowers with
    # the cohort as one more spatial dimension, so such a model's cohort
    # trains a few clients at a time; lr/mlp on an image dataset are
    # batched matmuls and must not be chunked by input shape alone
    bundle.conv_model = name in CONV_MODEL_FAMILIES
    logger.info("model: %s for %s (output_dim=%d)", name, dataset, output_dim)
    return bundle
