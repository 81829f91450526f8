"""Model FLOPs per token of a pre-norm GQA + SwiGLU decoder (Mistral/Llama block).

Forward, per token, one multiply-add = 2 FLOPs:

- projections: q, k, v, o = ``2 * hidden * head_dim * (2 * heads + 2 * kv_heads)``
- feed-forward: gate, up, down = ``2 * 3 * hidden * intermediate``
- attention scores and values, causal: a query at position ``i`` sees ``i + 1``
  keys, ``(seq + 1) / 2`` on average, so ``2 * 2 * heads * head_dim * (seq + 1) / 2``
- output head: ``2 * hidden * vocab``

The embedding table is a row gather and costs no FLOPs. The program's
``parallel.transformer.train_flops_per_token`` counts the same from its own
configuration; the benchmark keeps its count from the published keys, where a
PR that claims a gain cannot change it. Backward is twice the forward;
recomputation under remat is not counted, so utilization built on this is
model FLOP/s utilization, not hardware utilization.
"""

from __future__ import annotations


def block_forward_flops_per_token(hidden: int, heads: int, kv_heads: int,
                                  head_dim: int, intermediate: int,
                                  seq_len: int, causal: bool = True) -> float:
    proj = 2 * hidden * head_dim * (2 * heads + 2 * kv_heads)
    ffn = 2 * 3 * hidden * intermediate
    keys = (seq_len + 1) / 2 if causal else seq_len
    attn = 2 * 2 * heads * head_dim * keys
    return proj + ffn + attn


def head_forward_flops_per_token(hidden: int, vocab: int) -> float:
    return 2 * hidden * vocab


def train_flops_per_token(config: dict, seq_len: int) -> float:
    """Forward + backward model FLOPs per token for a Hugging Face style
    ``config`` (``hidden_size``, ``num_attention_heads``, ...)."""
    hidden = int(config["hidden_size"])
    heads = int(config["num_attention_heads"])
    head_dim = int(config.get("head_dim") or hidden // heads)
    block = block_forward_flops_per_token(
        hidden, heads, int(config["num_key_value_heads"]), head_dim,
        int(config["intermediate_size"]), seq_len)
    head = head_forward_flops_per_token(hidden, int(config["vocab_size"]))
    return 3.0 * (int(config["num_hidden_layers"]) * block + head)
