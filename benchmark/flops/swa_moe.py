"""Model FLOPs per token, and the window layers' attention kernels' operations,
of a GQA decoder whose layers attend over a window of the last keys or over
all of them by ``layer_types`` (Mellum2 shapes: ``sliding_attention`` and
``full_attention``), with q/k norms, a head size of its own and expert layers
of which this chip holds a share. One multiply-add = 2 FLOPs. Keys are the
configuration file's.

Forward, per token of a sequence of ``L``:

- each layer's projections: ``q`` (hidden x heads x head_dim), ``k`` and
  ``v`` (hidden x kv_heads x head_dim each), ``o`` (heads x head_dim x
  hidden); the per-head q/k norms and the rotary are elementwise and not
  counted
- scores and values over the pairs the layer's mask lets through, a q.k and a
  p.v product at ``head_dim`` each: a ``full_attention`` query sees ``(L +
  1) / 2`` keys on average, a ``sliding_attention`` query
  :func:`window_pairs` ``/ L``
- each layer's expert layer: the router (hidden x router_experts) and of the
  routed experts the expected share this chip computes,
  ``num_experts_per_tok x num_experts / router_experts`` experts a token (the
  absent experts' work is another chip's), a SwiGLU of
  ``moe_intermediate_size``
- the head over the vocabulary rows held

Training is three times the forward (backward twice the forward); the
embedding is a row gather; recomputation under remat is not counted.
"""

from __future__ import annotations

# the experts' grouped products are what they are in every expert-layer cell:
# ``moe_products.kernel_roofline`` asks the configuration's module for this
from benchmark.flops.mla_moe import grouped_product_least_seconds  # noqa: F401


def _sizes(config: dict):
    return (int(config["hidden_size"]), int(config["num_attention_heads"]),
            int(config["num_key_value_heads"]), int(config["head_dim"]))


def window_pairs(seq_len: int, window: int) -> int:
    """(query, key) pairs of a sequence when query ``i`` sees the keys ``(i -
    window, i]``: ``sum_i min(i + 1, window)``."""
    w = min(window, seq_len)
    return w * (w + 1) // 2 + (seq_len - w) * w


def attention_forward_flops_per_token(config: dict, seq_len: int,
                                      layer_type: str) -> float:
    D, H, Hkv, hd = _sizes(config)
    proj = 2 * D * hd * (2 * H + 2 * Hkv)
    if layer_type == "sliding_attention":
        pairs = window_pairs(seq_len, int(config["sliding_window"])) / seq_len
    else:
        pairs = (seq_len + 1) / 2
    return proj + 2 * 2 * H * hd * pairs


def expert_layer_forward_flops_per_token(config: dict) -> float:
    D = int(config["hidden_size"])
    F = int(config["moe_intermediate_size"])
    routed = (int(config["num_experts_per_tok"]) * int(config["num_experts"])
              / int(config["router_experts"]))
    return 2 * D * int(config["router_experts"]) + routed * 2 * 3 * D * F


def train_flops_per_token(config: dict, seq_len: int) -> float:
    """Forward + backward model FLOPs per token of the configuration as cut
    (the layers of ``layer_types``, every one an expert layer)."""
    forward = sum(attention_forward_flops_per_token(config, seq_len, t)
                  + expert_layer_forward_flops_per_token(config)
                  for t in config["layer_types"])
    forward += 2 * int(config["hidden_size"]) * int(config["vocab_rows_held"])
    return 3.0 * forward


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------


def attention_kernel_flops(config: dict, seq_len: int, sequences: int) -> dict:
    """Operations one call of each splash kernel of a ``sliding_attention``
    layer needs over ``sequences`` sequences of ``seq_len`` tokens, by the
    kind its name carries, over the band's pairs and no others (a tile the
    band crosses costs the kernel the whole tile, and a tile it misses none;
    the first is the kernel's loss, not needed work): ``fwd`` computes the
    scores and the values' sum (a q.k and a p.v product at head_dim); ``dq``
    needs the scores again, ``dO.v`` and ``dS.k``; ``dkv`` the scores again,
    ``dO.v``, ``P.dO`` and ``dS.q``."""
    _, H, _, hd = _sizes(config)
    pairs = sequences * H * window_pairs(seq_len, int(config["sliding_window"]))
    return {"fwd": 2 * pairs * 2 * hd,
            "dq": 2 * pairs * 3 * hd,
            "dkv": 2 * pairs * 4 * hd}
