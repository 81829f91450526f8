"""Model FLOPs per token, and each kernel's operations and bytes, of a decoder
whose layers mix by Kimi delta attention (KDA) or by latent attention without
a q-LoRA, ``layer_group_size`` deciding which, with leading dense layers and
then expert layers of which this chip holds a share (Ling-3.0-flash shapes).
One multiply-add = 2 FLOPs. Keys are the configuration file's.

Forward, per token:

- a KDA layer (layer ``i`` where ``(i + 1) % layer_group_size != 0``): the
  projections of q, k, v and the decay gate (hidden x heads x head_dim each),
  of beta and the output gate (hidden x heads each) and the output back; three
  depthwise convolutions of ``short_conv_kernel_size`` taps; per head the
  chunked form's products at the chunk length ``kda_chunk`` = C: the two
  ``C x C`` pair matrices (``2 C dk`` each a token), ``W`` and ``U`` through
  the triangular inverse (``2 C dk`` and ``2 C dv``, and ``C^2 / 3``
  multiply-adds for the inverse by substitution), then against the state
  ``W S``, ``Qg S`` and the state's update (``2 dk dv`` each) and ``Aqk U~``
  (``2 C dv``)
- an MLA layer: ``q`` (hidden x heads x (nope + rope)), ``kv_a`` (hidden x
  (kv_lora + rope)), ``kv_b`` (kv_lora x heads x (nope + v)), ``o``; causal
  scores and values over ``(seq + 1) / 2`` keys on average at the 192-wide
  query/key head and the 128-wide value head
- a dense layer's SwiGLU at ``intermediate_size``; an expert layer's router
  (hidden x router_experts), its shared expert, and of its routed experts the
  expected share this chip computes: ``num_experts_per_tok x num_experts /
  router_experts`` experts a token
- the head over the vocabulary rows held

Training is three times the forward (backward twice the forward); the
embedding is a row gather; recomputation under remat is not counted.
"""

from __future__ import annotations


def mixer_of(layer: int, config: dict) -> str:
    G = int(config["layer_group_size"])
    return "mla" if G and (layer + 1) % G == 0 else "kda"


def kda_forward_flops_per_token(config: dict) -> float:
    D, H = int(config["hidden_size"]), int(config["num_attention_heads"])
    hd, C = int(config["head_dim"]), int(config["kda_chunk"])
    K = int(config["short_conv_kernel_size"])
    proj = 2 * D * (4 * H * hd + 2 * H) + 2 * H * hd * D
    conv = 2 * K * 3 * H * hd
    chunked = H * (2 * C * 5 * hd + 2 * C * C / 3 + 2 * 3 * hd * hd)
    return proj + conv + chunked


def mla_forward_flops_per_token(config: dict, seq_len: int) -> float:
    D, H = int(config["hidden_size"]), int(config["num_attention_heads"])
    dn, dr = int(config["qk_nope_head_dim"]), int(config["qk_rope_head_dim"])
    dv, rkv = int(config["v_head_dim"]), int(config["kv_lora_rank"])
    proj = 2 * (D * H * (dn + dr) + D * (rkv + dr) + rkv * H * (dn + dv)
                + H * dv * D)
    return proj + 2 * H * (dn + dr + dv) * (seq_len + 1) / 2


def swiglu_forward_flops_per_token(hidden: int, width: int) -> float:
    return 2 * 3 * hidden * width


def expert_layer_forward_flops_per_token(config: dict) -> float:
    D = int(config["hidden_size"])
    routed = (int(config["num_experts_per_tok"]) * int(config["num_experts"])
              / int(config["router_experts"]))
    return (2 * D * int(config["router_experts"])
            + swiglu_forward_flops_per_token(
                D, int(config["moe_shared_expert_intermediate_size"]))
            + routed * swiglu_forward_flops_per_token(
                D, int(config["moe_intermediate_size"])))


def train_flops_per_token(config: dict, seq_len: int) -> float:
    """Forward + backward model FLOPs per token of the configuration as cut
    (``num_hidden_layers`` blocks, ``first_k_dense_replace`` of them dense)."""
    D = int(config["hidden_size"])
    layers = int(config["num_hidden_layers"])
    dense = int(config["first_k_dense_replace"])
    mixers = sum(mla_forward_flops_per_token(config, seq_len)
                 if mixer_of(i, config) == "mla"
                 else kda_forward_flops_per_token(config) for i in range(layers))
    forward = (mixers
               + dense * swiglu_forward_flops_per_token(
                   D, int(config["intermediate_size"]))
               + (layers - dense) * expert_layer_forward_flops_per_token(config)
               + 2 * D * int(config["vocab_rows_held"]))
    return 3.0 * forward


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------


def attention_kernel_flops(config: dict, seq_len: int, sequences: int) -> dict:
    """Operations one call of each splash kernel (the MLA layers') needs over
    ``sequences`` causal sequences, by the kind its name carries: ``fwd``
    computes the scores and the values' sum; ``dq`` needs the scores again,
    ``dO.v`` and ``dS.k``; ``dkv`` the scores again, ``dO.v``, ``P.dO`` and
    ``dS.q``. Over the causal half, ``seq (seq + 1) / 2`` pairs a head; the
    192-wide head counts as 192 whatever the kernel pads it to."""
    H = int(config["num_attention_heads"])
    dqk = int(config["qk_nope_head_dim"]) + int(config["qk_rope_head_dim"])
    dv = int(config["v_head_dim"])
    pairs = sequences * H * seq_len * (seq_len + 1) / 2
    return {"fwd": 2 * pairs * (dqk + dv),
            "dq": 2 * pairs * (2 * dqk + dv),
            "dkv": 2 * pairs * (2 * dqk + 2 * dv)}


def grouped_product_least_seconds(rows: float, groups: int, k: int, n: int,
                                  peaks: dict, itemsize: int = 2):
    """The least time one grouped product can take: ``rows`` arrived rows in
    all against ``groups`` [k, n] matrices. Operations ``2 rows k n``; bytes
    the matrices once, the rows in and the rows out. Returns (seconds, which
    bound: "flops" or "bytes")."""
    flops = 2.0 * rows * k * n
    moved = itemsize * (groups * k * n + rows * (k + n))
    by_flops = flops / peaks["bf16_flops_per_s"]
    by_bytes = moved / peaks["hbm_bytes_per_s"]
    return max(by_flops, by_bytes), "flops" if by_flops >= by_bytes else "bytes"


def kda_kernel_least_seconds(config: dict, seq_len: int, sequences: int,
                             kind: str, peaks: dict, itemsize: int = 2):
    """The least time one call of a KDA chunk kernel can take over
    ``sequences`` sequences of ``seq_len`` tokens: the part of the chunked
    form that is sequential over chunks, all heads. ``kind`` is ``"fwd"`` or
    ``"bwd"``. Per token and head, with ``dk = dv = head_dim`` and ``C =
    kda_chunk``:

    - operations, ``fwd``: ``W S``, ``Qg S`` and ``Kd^T U~`` (``2 dk dv``
      each) and ``Aqk U~`` (``2 C dv``). ``bwd``: ``Aqk^T dO`` and ``dO U~^T``
      (``2 C dv`` each), ``Kd dS'``, ``dO S``, ``U~ dS'``, ``dU~ S``,
      ``Qg^T dO`` and ``W^T dU~`` (``2 dk dv`` each); recomputing ``U~`` is
      not counted
    - bytes, ``fwd``: the operands once, ``Qg``, ``Kd``, ``W`` (dk each),
      ``U`` (dv) and ``Aqk`` (C) in ``itemsize`` bytes and the chunk's decay
      (``dk / C`` float32), and the output (dv). ``bwd``: the same operands
      and the output's cotangent in, the five operands' gradients and the
      decay's out. The states at the chunk boundaries, which the forward may
      save and the backward may read, are a choice of the kernel's and not
      counted

    Returns (seconds, which bound: "flops" or "bytes")."""
    H, hd = int(config["num_attention_heads"]), int(config["head_dim"])
    C = int(config["kda_chunk"])
    tokens = float(seq_len) * sequences * H
    operands = itemsize * (3 * hd + hd + C) + 4 * hd / C
    if kind == "fwd":
        flops = tokens * 2 * (3 * hd * hd + C * hd)
        moved = tokens * (operands + itemsize * hd)
    elif kind == "bwd":
        flops = tokens * 2 * (6 * hd * hd + 2 * C * hd)
        moved = tokens * (2 * operands + itemsize * hd)
    else:
        raise ValueError(f"kind must be fwd|bwd, got {kind!r}")
    by_flops = flops / peaks["bf16_flops_per_s"]
    by_bytes = moved / peaks["hbm_bytes_per_s"]
    return max(by_flops, by_bytes), "flops" if by_flops >= by_bytes else "bytes"
