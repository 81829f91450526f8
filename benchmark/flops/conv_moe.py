"""Model FLOPs per token, and the gated short convolution's least time, of a
decoder whose layers are a mixer and a feed-forward part each, the mixer
chosen by the published ``layer_types`` (LFM2 shapes): ``conv`` a gated short
convolution, ``full_attention`` GQA; the first ``num_dense_layers`` layers'
feed-forward dense, the others' an expert layer of which this chip holds a
share. One multiply-add = 2 FLOPs. Keys are the configuration file's.

Forward, per token:

- a ``conv`` mixer: the input projection (hidden x 3 hidden) and the output
  projection (hidden x hidden); ``conv_L_cache`` multiply-adds a channel of
  the depthwise convolution and one multiply a channel for each of the two
  gates
- a ``full_attention`` mixer: ``q`` (hidden x heads x head size), ``k`` and
  ``v`` (hidden x kv_heads x head size each), ``o``; causal scores and values
  over ``(seq + 1) / 2`` keys on average; the head size is ``hidden_size /
  num_attention_heads``
- a dense feed-forward: three matrices at ``intermediate_size``
- an expert layer: the router (hidden x router_experts) and of the routed
  experts the expected share this chip computes, ``num_experts_per_tok x
  num_experts / router_experts`` experts a token, three matrices at
  ``moe_intermediate_size`` each; no shared expert
- the head over the vocabulary rows held (tied to the embedding: one product
  all the same)

Training is three times the forward (backward twice the forward); the
embedding is a row gather; recomputation under remat is not counted.
"""

from __future__ import annotations

# the experts' grouped products are what they are in every expert-layer cell:
# ``moe_products.kernel_roofline`` asks the configuration's module for this
from benchmark.flops.mla_moe import grouped_product_least_seconds  # noqa: F401


def shortconv_gate_conv_flops_per_token(config: dict) -> float:
    """The two gates and the convolution alone, forward, one layer: what
    ``shortconv_conv`` computes between the projections."""
    D = int(config["hidden_size"])
    return 2 * int(config["conv_L_cache"]) * D + 2 * D


def shortconv_forward_flops_per_token(config: dict) -> float:
    D = int(config["hidden_size"])
    return (2 * D * 3 * D + 2 * D * D
            + shortconv_gate_conv_flops_per_token(config))


def attention_forward_flops_per_token(config: dict, seq_len: int) -> float:
    D, H = int(config["hidden_size"]), int(config["num_attention_heads"])
    Hkv, hd = int(config["num_key_value_heads"]), D // H
    return 2 * D * hd * (2 * H + 2 * Hkv) + 2 * 2 * H * hd * (seq_len + 1) / 2


def swiglu_forward_flops_per_token(hidden: int, width: float) -> float:
    return 2 * 3 * hidden * width


def expert_layer_forward_flops_per_token(config: dict) -> float:
    D = int(config["hidden_size"])
    routed = (int(config["num_experts_per_tok"]) * int(config["num_experts"])
              / int(config["router_experts"]))
    return (2 * D * int(config["router_experts"])
            + swiglu_forward_flops_per_token(
                D, routed * int(config["moe_intermediate_size"])))


def train_flops_per_token(config: dict, seq_len: int) -> float:
    """Forward + backward model FLOPs per token of the configuration as cut
    (the layers of ``layer_types``)."""
    D = int(config["hidden_size"])
    mixer = {"conv": shortconv_forward_flops_per_token(config),
             "full_attention": attention_forward_flops_per_token(config, seq_len)}
    dense = int(config["num_dense_layers"])
    kinds = config["layer_types"]
    forward = (sum(mixer[kind] for kind in kinds)
               + dense * swiglu_forward_flops_per_token(
                   D, int(config["intermediate_size"]))
               + (len(kinds) - dense) * expert_layer_forward_flops_per_token(config)
               + 2 * D * int(config["vocab_rows_held"]))
    return 3.0 * forward


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------


def shortconv_least_seconds(config: dict, seq_len: int, sequences: int,
                            kind: str, peaks: dict, itemsize: int = 2):
    """The least time one pass of one layer's gated convolution can take over
    ``sequences`` sequences of ``seq_len`` tokens: everything between the
    input projection's ``[B | C | x]`` and what the output projection reads,
    ``C * conv(B * x)``. ``kind`` is ``"fwd"`` or ``"bwd"``. Per token:

    - bytes, ``fwd``: ``B``, ``C`` and ``x`` read and ``y`` written, hidden
      entries each in ``itemsize`` bytes; ``bwd``: ``B``, ``C``, ``x`` and
      ``y``'s cotangent read, the cotangents of ``B``, ``C`` and ``x``
      written. The taps' few entries and their gradient are not counted
    - operations, ``fwd``: :func:`shortconv_gate_conv_flops_per_token`;
      ``bwd`` twice that. They are far under the bytes' time, and count only
      where they are the larger
    - running the forward again under remat is not needed work

    Returns (seconds, which bound: "flops" or "bytes")."""
    D = int(config["hidden_size"])
    tokens = float(seq_len) * sequences
    if kind == "fwd":
        flops = tokens * shortconv_gate_conv_flops_per_token(config)
        moved = tokens * itemsize * 4 * D
    elif kind == "bwd":
        flops = tokens * 2 * shortconv_gate_conv_flops_per_token(config)
        moved = tokens * itemsize * 7 * D
    else:
        raise ValueError(f"kind must be fwd|bwd, got {kind!r}")
    by_flops = flops / peaks["bf16_flops_per_s"]
    by_bytes = moved / peaks["hbm_bytes_per_s"]
    return max(by_flops, by_bytes), "flops" if by_flops >= by_bytes else "bytes"
