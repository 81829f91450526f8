"""Model FLOPs per token, and each kernel's operations and bytes, of a decoder
whose layer list is a pattern of single sublayers: Mamba-2 state-space mixers
(``M``), expert layers of which this chip holds a share (``E``), GQA attention
without positions (``*``) and dense feed-forwards (``-``), the feed-forwards
squared-ReLU with no gate matrix (Nemotron-H / Nemotron-3 shapes). One
multiply-add = 2 FLOPs. Keys are the configuration file's.

Forward, per token:

- an ``M`` layer: the input projection (hidden x (2 d_inner + 2 groups x state
  + heads), ``d_inner = mamba_num_heads x mamba_head_dim``) and the output
  projection back; the depthwise convolution of ``conv_kernel`` taps over the
  ``d_inner + 2 groups x state`` convolved channels; the recurrence in its
  chunked form at chunks of ``chunk_size`` = Q: ``C B^T`` a group (``2 Q
  state``), the pairs against the inputs a head (``2 Q head_dim``), the
  chunk's own state and the read of the carried one (``2 head_dim state`` each
  a head). The token-by-token recurrence would need ``4 head_dim state`` a
  head and nothing else; the chunked form is what a matrix unit can run
- an ``*`` layer: ``q`` (hidden x heads x head_dim), ``k`` and ``v`` (hidden x
  kv_heads x head_dim each), ``o``; causal scores and values over ``(seq + 1)
  / 2`` keys on average
- an ``E`` layer: the router (hidden x router_experts), the shared expert (two
  matrices at ``moe_shared_expert_intermediate_size``) and of the routed
  experts the expected share this chip computes, ``num_experts_per_tok x
  n_routed_experts / router_experts`` experts a token, two matrices at
  ``moe_intermediate_size`` each
- a ``-`` layer: two matrices at ``intermediate_size``
- the head over the vocabulary rows held

Training is three times the forward (backward twice the forward); the
embedding is a row gather; recomputation under remat is not counted.
"""

from __future__ import annotations

# the experts' grouped products are what they are in every expert-layer cell:
# ``moe_experts.kernel_roofline`` asks the configuration's module for this
from benchmark.flops.mla_moe import grouped_product_least_seconds  # noqa: F401


def _ssm_sizes(config: dict):
    return (int(config["mamba_num_heads"]), int(config["mamba_head_dim"]),
            int(config["n_groups"]), int(config["ssm_state_size"]),
            int(config["chunk_size"]))


def ssd_chunked_flops_per_token(config: dict) -> float:
    """The chunked recurrence alone, forward, one layer."""
    H, P, G, N, Q = _ssm_sizes(config)
    return G * 2 * Q * N + H * (2 * Q * P + 2 * 2 * P * N)


def mamba_forward_flops_per_token(config: dict) -> float:
    D = int(config["hidden_size"])
    H, P, G, N, _ = _ssm_sizes(config)
    inner, bc = H * P, 2 * G * N
    proj = 2 * D * (2 * inner + bc + H) + 2 * inner * D
    conv = 2 * int(config["conv_kernel"]) * (inner + bc)
    return proj + conv + ssd_chunked_flops_per_token(config)


def attention_forward_flops_per_token(config: dict, seq_len: int) -> float:
    D, H = int(config["hidden_size"]), int(config["num_attention_heads"])
    Hkv, hd = int(config["num_key_value_heads"]), int(config["head_dim"])
    return 2 * D * hd * (2 * H + 2 * Hkv) + 2 * 2 * H * hd * (seq_len + 1) / 2


def relu2_forward_flops_per_token(hidden: int, width: float) -> float:
    return 2 * 2 * hidden * width


def expert_layer_forward_flops_per_token(config: dict) -> float:
    D = int(config["hidden_size"])
    routed = (int(config["num_experts_per_tok"]) * int(config["n_routed_experts"])
              / int(config["router_experts"]))
    return (2 * D * int(config["router_experts"])
            + relu2_forward_flops_per_token(
                D, int(config["moe_shared_expert_intermediate_size"])
                + routed * int(config["moe_intermediate_size"])))


def train_flops_per_token(config: dict, seq_len: int) -> float:
    """Forward + backward model FLOPs per token of the configuration as cut
    (the layers of ``hybrid_override_pattern``)."""
    D = int(config["hidden_size"])
    layer = {"M": mamba_forward_flops_per_token(config),
             "*": attention_forward_flops_per_token(config, seq_len),
             "E": expert_layer_forward_flops_per_token(config),
             "-": relu2_forward_flops_per_token(
                 D, int(config["intermediate_size"]))}
    forward = (sum(layer[c] for c in config["hybrid_override_pattern"])
               + 2 * D * int(config["vocab_rows_held"]))
    return 3.0 * forward


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------


def ssd_least_seconds(config: dict, seq_len: int, sequences: int, kind: str,
                      peaks: dict, itemsize: int = 2):
    """The least time one pass of one layer's chunked recurrence can take over
    ``sequences`` sequences of ``seq_len`` tokens, all heads: everything
    between the convolved ``X``, ``B``, ``C`` with the step sizes ``dt`` and
    the recurrence's output. ``kind`` is ``"fwd"`` or ``"bwd"``. Per token:

    - operations, ``fwd``: :func:`ssd_chunked_flops_per_token`; ``bwd`` twice
      that (every product has two transposes); running the forward again
      under remat is not counted
    - bytes, ``fwd``: ``X`` (heads x head_dim), ``B`` and ``C`` (groups x
      state each) in ``itemsize`` bytes, ``dt`` (heads, float32) in, the
      output (heads x head_dim) out. ``bwd``: the same operands and the
      output's cotangent in, the four operands' gradients out. The states at
      the chunk boundaries, which a forward may save for its backward, are an
      implementation's choice and not counted

    Returns (seconds, which bound: "flops" or "bytes")."""
    H, P, G, N, _ = _ssm_sizes(config)
    tokens = float(seq_len) * sequences
    operands = itemsize * (H * P + 2 * G * N) + 4 * H
    if kind == "fwd":
        flops = tokens * ssd_chunked_flops_per_token(config)
        moved = tokens * (operands + itemsize * H * P)
    elif kind == "bwd":
        flops = tokens * 2 * ssd_chunked_flops_per_token(config)
        moved = tokens * (2 * operands + itemsize * H * P)
    else:
        raise ValueError(f"kind must be fwd|bwd, got {kind!r}")
    by_flops = flops / peaks["bf16_flops_per_s"]
    by_bytes = moved / peaks["hbm_bytes_per_s"]
    return max(by_flops, by_bytes), "flops" if by_flops >= by_bytes else "bytes"
