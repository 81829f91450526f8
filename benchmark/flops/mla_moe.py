"""Model FLOPs per token, and each kernel's operations and bytes, of a decoder
with multi-head latent attention, leading dense layers, expert layers of which
this chip holds a share, and hyper-connection maps (Xing4.0 / DeepSeek-V3
shapes). One multiply-add = 2 FLOPs. Keys are the configuration file's.

Forward, per token:

- MLA projections: ``q_a`` (hidden x q_lora), ``q_b`` (q_lora x heads x
  (nope + rope)), ``kv_a`` (hidden x (kv_lora + rope)), ``kv_b`` (kv_lora x
  heads x (nope + v)), ``o`` (heads x v x hidden)
- causal scores and values: a query at position ``i`` sees ``i + 1`` keys,
  ``(seq + 1) / 2`` on average, at a query/key head of nope + rope (192 here,
  whatever the kernel pads it to) and a value head of v
- a dense layer's SwiGLU at ``intermediate_size``; an expert layer's router
  (hidden x router_experts), its shared experts, and of its routed experts
  the expected share this chip computes: ``num_experts_per_tok x
  n_routed_experts / router_experts`` experts a token (the absent experts'
  work is another chip's)
- the hyper-connection maps of both sublayers: ``hc_mult x hidden`` by
  ``2 hc_mult + hc_mult^2`` each (the stream reads and writes are elementwise
  and not counted)
- the head over the vocabulary rows held

Training is three times the forward (backward twice the forward); the
embedding is a row gather; recomputation under remat is not counted.
"""

from __future__ import annotations


def _sizes(config: dict):
    return (int(config["hidden_size"]), int(config["num_attention_heads"]),
            int(config["qk_nope_head_dim"]) + int(config["qk_rope_head_dim"]),
            int(config["v_head_dim"]))


def mla_forward_flops_per_token(config: dict, seq_len: int) -> float:
    D, H, dqk, dv = _sizes(config)
    rq, rkv = int(config["q_lora_rank"]), int(config["kv_lora_rank"])
    dn, dr = int(config["qk_nope_head_dim"]), int(config["qk_rope_head_dim"])
    proj = 2 * (D * rq + rq * H * dqk + D * (rkv + dr) + rkv * H * (dn + dv)
                + H * dv * D)
    attn = 2 * H * (dqk + dv) * (seq_len + 1) / 2
    return proj + attn


def swiglu_forward_flops_per_token(hidden: int, width: int) -> float:
    return 2 * 3 * hidden * width


def expert_layer_forward_flops_per_token(config: dict) -> float:
    D = int(config["hidden_size"])
    F = int(config["moe_intermediate_size"])
    held_share = int(config["n_routed_experts"]) / int(config["router_experts"])
    routed = int(config["num_experts_per_tok"]) * held_share
    return (2 * D * int(config["router_experts"])
            + (int(config["n_shared_experts"]) + routed)
            * swiglu_forward_flops_per_token(D, F))


def hyper_connection_forward_flops_per_token(config: dict) -> float:
    n, D = int(config["hc_mult"]), int(config["hidden_size"])
    return 2 * (2 * (n * D) * (2 * n + n * n))  # two sublayers a block


def train_flops_per_token(config: dict, seq_len: int) -> float:
    """Forward + backward model FLOPs per token of the configuration as cut
    (``num_hidden_layers`` blocks, ``first_k_dense_replace`` of them dense,
    no MTP module where ``num_nextn_predict_layers`` is 0)."""
    D = int(config["hidden_size"])
    layers = int(config["num_hidden_layers"])
    dense = int(config["first_k_dense_replace"])
    every = (mla_forward_flops_per_token(config, seq_len)
             + hyper_connection_forward_flops_per_token(config))
    forward = (layers * every
               + dense * swiglu_forward_flops_per_token(
                   D, int(config["intermediate_size"]))
               + (layers - dense) * expert_layer_forward_flops_per_token(config)
               + 2 * D * int(config["vocab_rows_held"]))
    if int(config["num_nextn_predict_layers"]):
        # one more expert block, the 2D x D projection, the head once more
        forward += (every + expert_layer_forward_flops_per_token(config)
                    + 2 * 2 * D * D + 2 * D * int(config["vocab_rows_held"]))
    return 3.0 * forward


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------


def attention_kernel_flops(config: dict, seq_len: int, sequences: int) -> dict:
    """Operations one call of each splash kernel needs over ``sequences``
    causal sequences, by the kind its name carries: ``fwd`` computes the
    scores and the values' sum (a q.k product at nope + rope, a p.v product
    at v); ``dq`` needs the scores again, ``dO.v`` and ``dS.k``; ``dkv`` the
    scores again, ``dO.v``, ``P.dO`` and ``dS.q``. Over the causal half,
    ``seq (seq + 1) / 2`` pairs a head; the 192-wide head counts as 192
    whatever the kernel pads it to."""
    _, H, dqk, dv = _sizes(config)
    pairs = sequences * H * seq_len * (seq_len + 1) / 2
    return {"fwd": 2 * pairs * (dqk + dv),
            "dq": 2 * pairs * (2 * dqk + dv),
            "dkv": 2 * pairs * (2 * dqk + 2 * dv)}


def grouped_product_least_seconds(rows: float, matrices: float, k: int,
                                  n: int, peaks: dict, itemsize: int = 2):
    """The least time one grouped product can take: ``rows`` arrived rows in
    all, and ``matrices`` [k, n] matrices that the call must touch.
    Operations ``2 rows k n``; bytes the matrices once, the rows in and the
    rows out. A product of the rows by the matrices (forward, rows' gradient)
    must read the matrices of the groups that received rows and no other;
    the product that yields the matrices' gradient reads two row arrays and
    writes every group's matrix, an empty group's as zeros, and reads none.
    One definition for every configuration's module. Returns (seconds, which
    bound: "flops" or "bytes")."""
    flops = 2.0 * rows * k * n
    moved = itemsize * (matrices * k * n + rows * (k + n))
    by_flops = flops / peaks["bf16_flops_per_s"]
    by_bytes = moved / peaks["hbm_bytes_per_s"]
    return max(by_flops, by_bytes), "flops" if by_flops >= by_bytes else "bytes"
