"""Mellum2-12B-A2.5B (``model_type`` ``mellum``) forward pass and next-token
loss, one sequence at a time.

Plain ``jax.numpy`` in float32, every matrix product at ``highest`` precision
(on a TPU a float32 product is otherwise rounded to bfloat16 passes), no
kernels, no batching, nothing from ``fedml_tpu``. The catalog gives the
model's ``config.json``; its keys (``max_window_layers``,
``use_sliding_window``, ``norm_topk_prob``, ``attention_bias``) are the
Qwen3-MoE block's, and the equations below are that block's at the config's
sizes, with a window on the layers the config names ``sliding_attention`` and
a rotary of its own for each kind of layer (``rope_parameters``). What the
config does not settle is marked *assumed* and listed under ``assumed`` in the
configuration file.

**Stack.** Every layer is two pre-norm residual sublayers, RMSNorm at
``rms_norm_eps``, no biases anywhere::

    h = x + Attn_i(RMSNorm(x))
    y = h + MoE(RMSNorm(h))

and after the last layer a final RMSNorm and the untied head
(``lm_head``: hidden x vocabulary).

**Attention.** GQA: ``num_attention_heads`` query and ``num_key_value_heads``
key / value heads of ``head_dim``; query head ``h`` reads key / value head
``h // (heads / kv_heads)``. Every q head and every k head is RMS-normalised
over its ``head_dim`` entries with one learned weight of ``head_dim`` for all
the q heads and one for the k heads (*assumed*: no config key says so, the
Qwen3-MoE block has them), then rotated: the pair ``(x[i], x[i + d/2])`` by
``position * inv_freq[i]`` (HF's ``rotate_half``), cos and sin multiplied by
the layer kind's attention factor. Scores ``q . k / sqrt(head_dim)``, softmax
over the keys the layer's mask lets through, values, ``W_o``.

- ``sliding_attention`` layers: plain rotary, ``inv_freq[i] =
  theta^(-2i/d)`` at ``rope_parameters.sliding_attention.rope_theta``, factor
  1; query ``i`` sees exactly the keys ``j`` with ``i - sliding_window < j <=
  i`` (HF's ``sliding_window_overlay``: ``sliding_window`` keys, its own
  included).
- ``full_attention`` layers: causal (``j <= i``) under YaRN
  (``rope_parameters.full_attention``): with ``base`` its ``rope_theta``,
  ``d`` the head size and ``n`` ``original_max_position_embeddings``, the
  dimension that turns ``r`` times over ``n`` positions is
  ``d ln(n / (2 pi r)) / (2 ln base)``; ``low`` is that of ``beta_fast``
  rounded down (at least 0), ``high`` that of ``beta_slow`` rounded up (at
  most ``d - 1``); ``ramp[i] = clip((i - low) / (high - low), 0, 1)``;
  ``inv_freq[i] = base^(-2i/d) (ramp[i] / factor + 1 - ramp[i])``; cos and sin
  times ``attention_factor``, so q . k is scaled by its square.

**Expert layer** (``mlp_layer_types``: every layer ``sparse``)::

    p = softmax(h W_r)                 float32, over all router_experts
    selected = the num_experts_per_tok largest p
    g_e = p_e / sum_{selected} p        norm_topk_prob
    MoE(h) = sum_{e selected and held here} g_e W_down,e(silu(W_gate,e h) * W_up,e h)

no shared expert. The Switch load-balancing loss ``E sum_e f_e P_e`` (``f_e``
the share of the ``k x tokens`` assignments that chose ``e``, ``P_e`` the mean
of ``p_e``) over all ``router_experts`` outputs, weighted ``aux_weight``
(*assumed* 0.001), is added to the loss.

**The held share** (model-configs guide, section 4): this chip holds
``num_experts`` experts from ``expert_offset`` of the router's
``router_experts``; what the absent experts would have added is left out, and
that partial result goes on to the next layer. The vocabulary is the rows held
(``vocab_rows_held``), the embedding and the head alike. These are the
departures from the published model, the same in the program.

**Departures in the computation.** (1) Attention runs one block of
``Q_BLOCK`` queries at a time under ``lax.map``, so the ``[heads, L, L]``
scores are never whole in memory; a window layer's block reads only the
``sliding_window - 1 + Q_BLOCK`` keys its band can reach. (2) The expert layer
computes every held expert over every row and weighs the result by the gate
(zero where the expert was not chosen): the same sum, no sort, no dispatch.
(3) The auxiliary loss is folded into the sequence's summed loss as ``(L - 1)
aux_weight sum_layers aux`` (:func:`loss_sum_and_tail_logits`), so that the
caller's mean over the ``L - 1`` predicted positions is the step's loss of a
batch of one sequence; over several sequences the program's auxiliary loss is
over all their rows at once. Nothing else departs.

Parameters are a plain dict; :func:`reference_params` builds it from the
program's tree and documents the layout.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
# None: float32 products. A dtype: every product's inputs are rounded to it
# first, to read what a lower precision than the configuration's gives (the
# benchmark's tolerances must refuse float8; PERF.md)
MATMUL_INPUT_DTYPE = None
Q_BLOCK = 512


def _round(a):
    if MATMUL_INPUT_DTYPE is None:
        return a
    return a.astype(MATMUL_INPUT_DTYPE).astype(jnp.float32)


def _mm(a, b):
    return jnp.matmul(_round(a), _round(b), precision=HIGHEST)


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def swiglu(x, w_gate, w_up, w_down):
    return _mm(jax.nn.silu(_mm(x, w_gate)) * _mm(x, w_up), w_down)


# ---------------------------------------------------------------------------
# rotary, one kind a layer type
# ---------------------------------------------------------------------------


def inv_freq_and_factor(config, layer_type: str):
    """(inverse frequencies [d/2] as float32, the factor on cos and sin) of a
    layer of ``layer_type``, from ``rope_parameters[layer_type]`` by the
    formulas of the module's docstring."""
    rope = config["rope_parameters"][layer_type]
    d, base = int(config["head_dim"]), float(rope["rope_theta"])
    plain = 1.0 / base ** (np.arange(0, d, 2, dtype=np.float64) / d)
    if rope["rope_type"] == "default":
        return plain.astype(np.float32), 1.0
    if rope["rope_type"] != "yarn":
        raise ValueError(f"no rotary named {rope['rope_type']!r}")
    n = float(rope["original_max_position_embeddings"])

    def dimension_turning(r):
        return d * math.log(n / (2 * math.pi * r)) / (2 * math.log(base))

    low = max(math.floor(dimension_turning(float(rope["beta_fast"]))), 0)
    high = min(math.ceil(dimension_turning(float(rope["beta_slow"]))), d - 1)
    if high == low:
        high += 0.001
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0.0, 1.0)
    inv = plain * (ramp / float(rope["factor"]) + 1.0 - ramp)
    return inv.astype(np.float32), float(rope["attention_factor"])


def rotary(x, layer_type, config):
    """x: [L, H, d] at positions 0..L-1, rotated by the layer kind's tables."""
    L, _, d = x.shape
    inv_freq, factor = inv_freq_and_factor(config, layer_type)
    angles = jnp.arange(L, dtype=jnp.float32)[:, None] * jnp.asarray(inv_freq)
    cos = (jnp.cos(angles) * factor)[:, None, :]
    sin = (jnp.sin(angles) * factor)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


# ---------------------------------------------------------------------------
# attention by blocks of queries
# ---------------------------------------------------------------------------


def visible(q_pos, k_pos, window):
    """May query ``q_pos`` see key ``k_pos``? ``j <= i``, and ``j > i -
    window`` where ``window`` is not None."""
    see = k_pos <= q_pos
    if window is not None:
        see = see & (k_pos > q_pos - window)
    return see


def blocked_attention(q, k, v, window=None, q_block=Q_BLOCK):
    """q: [L, H, d]; k, v: [L, Hkv, d] -> [L, H, d], one block of ``q_block``
    queries at a time; ``window`` None: causal; else the band ``(i - window,
    i]``, each block reading the ``window - 1 + q_block`` keys before and
    inside it (positions below 0 are padding and never visible)."""
    L, H, d = q.shape
    q_block = min(q_block, L)
    if L % q_block:
        raise ValueError(f"{L} queries are not blocks of {q_block}")
    rep = H // k.shape[1]
    q, k, v = _round(q), _round(k), _round(v)
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    reach = L if window is None else window - 1 + q_block
    pad = 0 if window is None else window - 1
    k = jnp.pad(k, ((pad, 0), (0, 0), (0, 0)))
    v = jnp.pad(v, ((pad, 0), (0, 0), (0, 0)))

    def one_block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, q_block)
        first = 0 if window is None else start      # in the padded rows
        kb = jax.lax.dynamic_slice_in_dim(k, first, reach)
        vb = jax.lax.dynamic_slice_in_dim(v, first, reach)
        k_pos = first - pad + jnp.arange(reach)
        q_pos = start + jnp.arange(q_block)
        see = visible(q_pos[:, None], k_pos[None, :], window) & (k_pos >= 0)
        scores = jnp.einsum("qhd,khd->hqk", qb, kb, precision=HIGHEST) * d ** -0.5
        probs = _round(jax.nn.softmax(jnp.where(see[None], scores, -jnp.inf),
                                      axis=-1))
        return jnp.einsum("hqk,khd->qhd", probs, vb, precision=HIGHEST)

    out = jax.lax.map(one_block, jnp.arange(0, L, q_block))
    return out.reshape(L, H, d)


def attention(p, x, layer_type, config):
    L = x.shape[0]
    H, Hkv = int(config["num_attention_heads"]), int(config["num_key_value_heads"])
    d, eps = int(config["head_dim"]), float(config["rms_norm_eps"])
    q = rms_norm(_mm(x, p["wq"]).reshape(L, H, d), p["q_norm"], eps)
    k = rms_norm(_mm(x, p["wk"]).reshape(L, Hkv, d), p["k_norm"], eps)
    v = _mm(x, p["wv"]).reshape(L, Hkv, d)
    q, k = rotary(q, layer_type, config), rotary(k, layer_type, config)
    window = (int(config["sliding_window"])
              if layer_type == "sliding_attention" else None)
    out = blocked_attention(q, k, v, window)
    return _mm(out.reshape(L, H * d), p["wo"])


# ---------------------------------------------------------------------------
# expert layer
# ---------------------------------------------------------------------------


def route(p, x, config):
    """-> (selected [L, k] expert ids, gates [L, k], margin [L], counts [E],
    prob_sum [E]). ``margin``: how far the router's logits (``log p`` up to a
    constant a row) are from a choice that changes what this chip computes:
    the smaller of (the lowest selected expert held here) minus (the best
    expert left out), and (the last expert selected) minus (the best expert
    held here that was left out); infinity where neither exists. A swap among
    experts held elsewhere moves nothing here but the renormalisation,
    continuously, and does not count. ``counts`` and ``prob_sum`` are what
    the load-balancing loss is made of (:func:`aux_loss`)."""
    k = int(config["num_experts_per_tok"])
    lo = int(config["expert_offset"])
    hi = lo + int(config["num_experts"])
    z = jnp.matmul(x, p["router"], precision=HIGHEST)
    prob = jax.nn.softmax(z, axis=-1)
    top, idx = jax.lax.top_k(z, k + 1)
    selected = idx[:, :k]
    chosen = jnp.take_along_axis(prob, selected, axis=-1)
    gates = chosen / chosen.sum(-1, keepdims=True)      # norm_topk_prob
    experts = jnp.arange(z.shape[-1])
    held = (experts >= lo) & (experts < hi)
    is_selected = (selected[:, :, None] == experts).any(1)
    lowest_held_in = jnp.where(held & is_selected, z, jnp.inf).min(-1)
    best_held_out = jnp.where(held & ~is_selected, z, -jnp.inf).max(-1)
    margin = jnp.minimum(lowest_held_in - top[:, k], top[:, k - 1] - best_held_out)
    return (selected, gates, margin,
            is_selected.sum(0).astype(jnp.float32), prob.sum(0))


def expert_layer(p, x, config):
    lo = int(config["expert_offset"])
    selected, gates, margin, counts, prob_sum = route(p, x, config)
    y = jnp.zeros_like(x)
    ex = p["experts"]
    for e in range(int(config["num_experts"])):
        g = jnp.where(selected == lo + e, gates, 0.0).sum(-1)
        y = y + g[:, None] * swiglu(x, ex["w_gate"][e], ex["w_up"][e],
                                    ex["w_down"][e])
    return y, margin, counts, prob_sum


def aux_loss(counts, prob_sum, rows, config):
    """The Switch load-balancing loss of one expert layer over ``rows`` rows:
    ``E sum_e f_e P_e`` from ``counts`` [E] (assignments to each expert) and
    ``prob_sum`` [E] (the router's probabilities summed over the rows). 1 for
    a balanced router."""
    E, k = int(config["router_experts"]), int(config["num_experts_per_tok"])
    return E * jnp.sum(counts / (k * rows) * prob_sum / rows)


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------


def hidden_states(params, tokens, config):
    """tokens: [L] int -> (final-norm hidden states [L, D], the smallest
    routing margin of every position over the expert layers [L], the sum of
    the layers' auxiliary losses)."""
    eps = float(config["rms_norm_eps"])
    L = tokens.shape[0]
    h = params["embed"][tokens]
    margin, aux = jnp.full((L,), jnp.inf), 0.0
    for layer, layer_type in zip(params["layers"], config["layer_types"],
                                 strict=True):
        h = h + attention(layer["attn"], rms_norm(h, layer["attn_norm"], eps),
                          layer_type, config)
        y, m, counts, prob_sum = expert_layer(
            layer["moe"], rms_norm(h, layer["ffn_norm"], eps), config)
        h, margin = h + y, jnp.minimum(margin, m)
        aux = aux + aux_loss(counts, prob_sum, L, config)
    return rms_norm(h, params["final_norm"], eps), margin, aux


def logits_and_loss(params, tokens, config):
    """Logits [L, V] over the vocabulary rows held; the summed next-token
    cross-entropy over the ``L - 1`` predicted positions; the routing margins
    [L]; the layers' summed auxiliary loss."""
    hidden, margin, aux = hidden_states(params, tokens, config)
    logits = _mm(hidden, params["lm_head"])
    logp = jax.nn.log_softmax(logits[:-1], axis=-1)
    loss = -jnp.take_along_axis(logp, tokens[1:, None], axis=-1)[:, 0].sum()
    return logits, loss, margin, aux


def loss_sum_and_tail_logits(params, tokens, config, tail: int):
    """What the benchmark's job compares (``jobs/pretrain_moe.py``): the summed
    loss of one sequence with its auxiliary term folded in (departure 3), the
    MTP module's (none here: 0), the logits of its last ``tail`` positions and
    their routing margins."""
    logits, loss, margin, aux = logits_and_loss(params, tokens, config)
    loss = loss + (tokens.shape[0] - 1) * float(config["aux_weight"]) * aux
    return loss, jnp.zeros(()), logits[-tail:], margin[-tail:]


# ---------------------------------------------------------------------------
# the program's parameter tree in this module's layout
# ---------------------------------------------------------------------------


def _numbered(tree, word):
    names = [k for k in tree if k.rsplit("_", 1)[0].endswith(word)]
    return sorted(names, key=lambda k: int(k.rsplit("_", 1)[1]))


def reference_params(params, config, router_state=None):
    """The program's parameter tree (``TrainState.params``) in this module's
    plain layout::

        {"embed": [V, D], "final_norm": [D], "lm_head": [D, V],
         "layers": [{"attn_norm": [D], "attn": {wq [D, H d], wk [D, Hkv d],
                     wv [D, Hkv d], wo [H d, D], q_norm [d], k_norm [d]},
                     "ffn_norm": [D],
                     "moe": {router [D, E], experts: {w_gate [held, D, F],
                             w_up [held, D, F], w_down [held, F, D]}}}]}

    The program fuses q, k, v into ``wqkv`` (columns in that order) and gate,
    up into ``w_gate_up`` (gate first); blocks are ``[Checkpoint]Block_<i>``.
    The softmax router carries no state, so ``router_state`` is not read."""
    del router_state
    H, Hkv = int(config["num_attention_heads"]), int(config["num_key_value_heads"])
    d = int(config["head_dim"])
    layers = []
    for name in _numbered(params, "Block"):
        b = params[name]
        a, m = b["Attention_0"], b["MoEFeedForward_0"]
        half = m["w_gate_up"].shape[-1] // 2
        layers.append({
            "attn_norm": b["RMSNorm_0"]["weight"],
            "attn": {"wq": a["wqkv"][:, :H * d],
                     "wk": a["wqkv"][:, H * d:(H + Hkv) * d],
                     "wv": a["wqkv"][:, (H + Hkv) * d:], "wo": a["wo"],
                     "q_norm": a["q_norm"]["weight"],
                     "k_norm": a["k_norm"]["weight"]},
            "ffn_norm": b["RMSNorm_1"]["weight"],
            "moe": {"router": m["w_router"],
                    "experts": {"w_gate": m["w_gate_up"][..., :half],
                                "w_up": m["w_gate_up"][..., half:],
                                "w_down": m["w_down"]}},
        })
    return {"embed": params["embed"], "layers": layers,
            "final_norm": params["RMSNorm_0"]["weight"],
            "lm_head": params["w_lm_head"]}
