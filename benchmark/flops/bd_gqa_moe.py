"""Model FLOPs per *data* token, and each kernel's operations and bytes, of a
GQA decoder with a head size of its own and expert layers of which this chip
holds a share, trained by diffusion over blocks (SDAR / Qwen3-MoE shapes under
BD3-LM's objective). One multiply-add = 2 FLOPs. Keys are the configuration
file's.

A sequence of ``L`` data tokens is read as ``2L`` rows (a noised and a clean
copy) under the block-diffusion mask at blocks of ``B = block_length``, which
lets ``L^2 + L B`` of the ``4 L^2`` pairs through. Forward, per data token:

- two rows of projections: ``q`` (hidden x heads x head_dim), ``k`` and ``v``
  (hidden x kv_heads x head_dim each), ``o`` (heads x head_dim x hidden); the
  per-head q/k norms are elementwise and not counted
- scores and values over the mask's pairs: ``(L^2 + L B) / L = L + B`` pairs
  a data token and head, a q.k product and a p.v product at ``head_dim`` each
- two rows of the expert layer: the router (hidden x router_experts) and of
  the routed experts the expected share this chip computes,
  ``num_experts_per_tok x num_experts / router_experts`` experts a row (the
  absent experts' work is another chip's), a SwiGLU of
  ``moe_intermediate_size``
- the head once, over the noised row alone and the vocabulary rows held

Training is three times the forward (backward twice the forward); the
embedding is a row gather; the noise draw is elementwise; recomputation under
remat is not counted.
"""

from __future__ import annotations

# the experts' grouped products are what they are in every expert-layer cell:
# ``moe_experts.kernel_roofline`` asks the configuration's module for this
from benchmark.flops.mla_moe import grouped_product_least_seconds  # noqa: F401


def _sizes(config: dict):
    return (int(config["hidden_size"]), int(config["num_attention_heads"]),
            int(config["num_key_value_heads"]), int(config["head_dim"]))


def mask_pairs(seq_len: int, block: int) -> int:
    """Pairs (row, key) the block-diffusion mask lets through, of the
    ``(2 seq_len)^2``: a noised block sees itself (``L B``) and the clean
    blocks before it, the clean copy is block-causal (together ``L^2``)."""
    return seq_len * seq_len + seq_len * block


def attention_forward_flops_per_token(config: dict, seq_len: int) -> float:
    D, H, Hkv, hd = _sizes(config)
    proj = 2 * (2 * D * hd * (2 * H + 2 * Hkv))          # both rows
    pairs = mask_pairs(seq_len, int(config["block_length"])) / seq_len
    return proj + 2 * 2 * H * hd * pairs


def expert_layer_forward_flops_per_token(config: dict) -> float:
    D = int(config["hidden_size"])
    F = int(config["moe_intermediate_size"])
    routed = (int(config["num_experts_per_tok"]) * int(config["num_experts"])
              / int(config["router_experts"]))
    return 2 * (2 * D * int(config["router_experts"]) + routed * 2 * 3 * D * F)


def train_flops_per_token(config: dict, seq_len: int) -> float:
    """Forward + backward model FLOPs per data token of the configuration as
    cut (``num_hidden_layers`` blocks, every one an expert layer)."""
    layers = int(config["num_hidden_layers"])
    forward = (layers * (attention_forward_flops_per_token(config, seq_len)
                         + expert_layer_forward_flops_per_token(config))
               + 2 * int(config["hidden_size"]) * int(config["vocab_rows_held"]))
    return 3.0 * forward


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------


def attention_kernel_flops(config: dict, seq_len: int, sequences: int) -> dict:
    """Operations one call of each splash kernel needs over ``sequences``
    sequences of ``seq_len`` data tokens (``2 seq_len`` rows), by the kind its
    name carries, over the pairs the mask lets through and no others (a tile
    the mask crosses costs the kernel the whole tile; that is the kernel's
    loss, not needed work): ``fwd`` computes the scores and the values' sum
    (a q.k and a p.v product at head_dim); ``dq`` needs the scores again,
    ``dO.v`` and ``dS.k``; ``dkv`` the scores again, ``dO.v``, ``P.dO`` and
    ``dS.q``."""
    _, H, _, hd = _sizes(config)
    pairs = sequences * H * mask_pairs(seq_len, int(config["block_length"]))
    return {"fwd": 2 * pairs * 2 * hd,
            "dq": 2 * pairs * 3 * hd,
            "dkv": 2 * pairs * 4 * hd}
