"""LFM2-8B-A1B (``model_type`` ``lfm2_moe``) forward pass and next-token loss,
one sequence at a time.

Plain ``jax.numpy`` in float32, every matrix product at ``highest`` precision
(on a TPU a float32 product is otherwise rounded to bfloat16 passes), no
kernels, no batching, nothing from ``fedml_tpu``. The catalog gives the
model's ``config.json``; the equations below are the published code's
(``transformers``' ``Lfm2Moe*`` modules) at the config's sizes, with what the
config does not settle marked *assumed* (and listed under ``assumed`` in the
configuration file).

**Stack.** Every layer is two pre-norm residual sublayers::

    h   = x + Mixer_i(RMSNorm(x))            operator_norm, eps norm_eps
    out = h + FF_i(RMSNorm(h))               ffn_norm

``layer_types[i]`` chooses the mixer; the first ``num_dense_layers`` layers'
feed-forward part is dense, the others' an expert layer. A final RMSNorm
(``embedding_norm``) and the head, **tied to the embedding** (*assumed*: the
row's config carries no tie key): ``logits = h embed^T``.

**conv** (``Lfm2ShortConv``, ``conv_L_cache`` = K taps, ``conv_bias``
false)::

    [B | C | x] = h W_in                     [D, 3 D], no bias; B, C, x in order
    u = B * x
    v[t, c] = sum_j w[j, c] u[t - (K - 1) + j, c]     zeros before t = 0
    y = (C * v) W_out                        [D, D], no bias

No activation anywhere, no positions.

**full_attention**: GQA, ``num_attention_heads`` query and
``num_key_value_heads`` key / value heads of ``hidden_size /
num_attention_heads``, no bias; per-head RMS norms of q and k
(``q_layernorm`` / ``k_layernorm``, one weight of the head size each) before
the rotary (half-split pairs, ``rope_theta``); causal softmax of ``q k^T /
sqrt(head size)``; the output projection.

**Dense feed-forward** (``intermediate_size``): ``w2(silu(w1 h) * w3 h)``.

**Expert layer** (``num_experts`` routed experts of ``moe_intermediate_size``,
no shared expert)::

    s = sigmoid(h W_r)                       float32, over all router_experts
    selected = top num_experts_per_tok of (s + b)     b: use_expert_bias
    w_i = s_i / (sum_{selected} s + 1e-6) * routed_scaling_factor
    y = sum_{i selected and held here} w_i expert_i(h)
    expert(h) = w2(silu(w1 h) * w3 h)

``b`` is state no gradient reaches, used for the selection alone; it is zero
at initialisation and moved after each step by the program (*assumed* rate).
The renormalisation adds ``1e-6`` as the published code does, where the
program's ``route`` divides by ``max(sum, 1e-9)``: the two differ by
``1e-6 / sum`` relatively, under 1e-6 for four sigmoid scores whose sum is
above one (``tests/test_lfm2.py`` holds it).

**The held share** (model-configs guide, section 4): this chip holds
``num_experts`` experts from ``expert_offset`` of the router's
``router_experts``; what the absent experts would have added is left out, and
that partial result goes on to the next layer. The vocabulary is the rows
held (``vocab_rows_held``), the embedding and the tied head alike. These are
the departures from the published model, the same in the program.

Parameters are a plain dict; :func:`reference_params` builds it from the
program's tree and documents the layout.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
# None: float32 products. A dtype: every product's inputs are rounded to it
# first, to read what a lower precision than the configuration's gives (the
# benchmark's tolerances must refuse float8; PERF.md)
MATMUL_INPUT_DTYPE = None


def _round(a):
    if MATMUL_INPUT_DTYPE is None:
        return a
    return a.astype(MATMUL_INPUT_DTYPE).astype(jnp.float32)


def _mm(a, b):
    return jnp.matmul(_round(a), _round(b), precision=HIGHEST)


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def swiglu(x, w1, w3, w2):
    return _mm(jax.nn.silu(_mm(x, w1)) * _mm(x, w3), w2)


# ---------------------------------------------------------------------------
# the gated short convolution
# ---------------------------------------------------------------------------


def causal_depthwise_conv(x, taps):
    """x: [L, C]; taps: [K, C] -> [L, C]. Output ``t`` reads inputs ``t - (K
    - 1) .. t``: tap ``K - 1`` multiplies the current token (torch's
    ``Conv1d(padding=K - 1)`` cut to the first L outputs)."""
    K, L = taps.shape[0], x.shape[0]
    padded = jnp.concatenate([jnp.zeros((K - 1, x.shape[1]), x.dtype), x])
    y = jnp.zeros_like(x)
    for k in range(K):
        y = y + taps[k] * padded[k:k + L]
    return y


def short_conv(p, x, config):
    D = int(config["hidden_size"])
    proj = _mm(x, p["w_in"])
    B, C, xx = proj[:, :D], proj[:, D:2 * D], proj[:, 2 * D:]
    v = causal_depthwise_conv(B * xx, p["conv"])
    return _mm(C * v, p["w_out"])


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------


def rotary(x, theta):
    """x: [L, H, d]: the pair (x[..., i], x[..., i + d/2]) rotated by
    ``position * theta^(-2i/d)``."""
    L, _, d = x.shape
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(L, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def causal_attention(q, k, v, q_block=512):
    """q: [L, H, d]; k, v: [L, Hkv, d] -> [L, H, d]; query head ``h`` reads
    key / value head ``h // (H / Hkv)``; one block of queries at a time so the
    [H, L, L] scores are never whole in memory."""
    L, H, d = q.shape
    rep = H // k.shape[1]
    q, k, v = _round(q), _round(k), _round(v)
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    out = []
    for start in range(0, L, q_block):
        qb = q[start:start + q_block]
        scores = jnp.einsum("qhd,khd->hqk", qb, k, precision=HIGHEST) * d ** -0.5
        q_pos = jnp.arange(start, start + qb.shape[0])[:, None]
        scores = jnp.where((jnp.arange(L)[None, :] <= q_pos)[None], scores,
                           -jnp.inf)
        probs = _round(jax.nn.softmax(scores, axis=-1))
        out.append(jnp.einsum("hqk,khd->qhd", probs, v, precision=HIGHEST))
    return jnp.concatenate(out, axis=0)


def head_dim(config) -> int:
    return int(config["hidden_size"]) // int(config["num_attention_heads"])


def qk_norm(x, weight, eps):
    """``q_layernorm`` / ``k_layernorm``: an RMS norm over each head's
    entries, one weight of the head size for all heads. x: [L, heads, d]."""
    return rms_norm(x, weight, eps)


def attention(p, x, config):
    L = x.shape[0]
    H, Hkv = int(config["num_attention_heads"]), int(config["num_key_value_heads"])
    hd, eps = head_dim(config), float(config["norm_eps"])
    q = qk_norm(_mm(x, p["wq"]).reshape(L, H, hd), p["q_norm"], eps)
    k = qk_norm(_mm(x, p["wk"]).reshape(L, Hkv, hd), p["k_norm"], eps)
    v = _mm(x, p["wv"]).reshape(L, Hkv, hd)
    theta = float(config["rope_theta"])
    q, k = rotary(q, theta), rotary(k, theta)
    return _mm(causal_attention(q, k, v).reshape(L, H * hd), p["wo"])


# ---------------------------------------------------------------------------
# expert layer
# ---------------------------------------------------------------------------


def renormalise(chosen):
    """``norm_topk_prob``: the chosen experts' scores over their sum, plus
    1e-6 as the published code adds it. chosen: [L, k]."""
    return chosen / (chosen.sum(-1, keepdims=True) + 1e-6)


def route(p, x, config):
    """-> (selected [L, k] expert ids, weights [L, k], margin [L]): how far
    the selection scores ``s + b`` are from a choice that changes what this
    chip computes: the smaller of (the lowest selected expert held here) minus
    (the best expert left out), and (the last expert selected) minus (the best
    expert held here that was left out); infinity where neither exists. A
    swap among experts held elsewhere moves nothing here but the
    renormalisation, continuously, and does not count."""
    k = int(config["num_experts_per_tok"])
    lo = int(config["expert_offset"])
    hi = lo + int(config["num_experts"])
    s = jax.nn.sigmoid(jnp.matmul(x, p["router"], precision=HIGHEST))
    score = s + p["bias"]
    top, idx = jax.lax.top_k(score, k + 1)
    selected = idx[:, :k]
    chosen = jnp.take_along_axis(s, selected, axis=-1)
    weights = renormalise(chosen) * float(config["routed_scaling_factor"])
    experts = jnp.arange(score.shape[-1])
    held = (experts >= lo) & (experts < hi)
    is_selected = (selected[:, :, None] == experts).any(1)
    lowest_held_in = jnp.where(held & is_selected, score, jnp.inf).min(-1)
    best_held_out = jnp.where(held & ~is_selected, score, -jnp.inf).max(-1)
    margin = jnp.minimum(lowest_held_in - top[:, k], top[:, k - 1] - best_held_out)
    return selected, weights, margin


def expert_layer(p, x, config):
    lo = int(config["expert_offset"])
    selected, weights, margin = route(p, x, config)
    y = jnp.zeros_like(x)
    ex = p["experts"]
    for e in range(int(config["num_experts"])):
        w = jnp.where(selected == lo + e, weights, 0.0).sum(-1)
        y = y + w[:, None] * swiglu(x, ex["w1"][e], ex["w3"][e], ex["w2"][e])
    return y, margin


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------


def hidden_states(params, tokens, config):
    """tokens: [L] int -> (final-norm hidden states [L, D], the smallest
    routing margin of every position over the expert layers [L])."""
    eps = float(config["norm_eps"])
    h = params["embed"][tokens]
    margin = jnp.full((tokens.shape[0],), jnp.inf)
    for layer in params["layers"]:
        x = rms_norm(h, layer["operator_norm"], eps)
        if "conv" in layer:
            h = h + short_conv(layer["conv"], x, config)
        else:
            h = h + attention(layer["attn"], x, config)
        x = rms_norm(h, layer["ffn_norm"], eps)
        if "moe" in layer:
            y, m = expert_layer(layer["moe"], x, config)
            h, margin = h + y, jnp.minimum(margin, m)
        else:
            h = h + swiglu(x, **layer["mlp"])
    return rms_norm(h, params["final_norm"], eps), margin


def logits_and_loss(params, tokens, config):
    """Logits [L, V] over the vocabulary rows held, through the head tied to
    the embedding; the summed next-token cross-entropy over the ``L - 1``
    predicted positions; the routing margins [L]."""
    hidden, margin = hidden_states(params, tokens, config)
    logits = _mm(hidden, params["embed"].T)
    logp = jax.nn.log_softmax(logits[:-1], axis=-1)
    loss = -jnp.take_along_axis(logp, tokens[1:, None], axis=-1)[:, 0].sum()
    return logits, loss, margin


def loss_sum_and_tail_logits(params, tokens, config, tail: int):
    """What the benchmark's job compares (``jobs/pretrain_moe.py``): the summed
    loss of one sequence, the MTP module's (none here: 0), the logits of its
    last ``tail`` positions and their routing margins."""
    logits, loss, margin = logits_and_loss(params, tokens, config)
    return loss, jnp.zeros(()), logits[-tail:], margin[-tail:]


# ---------------------------------------------------------------------------
# the program's parameter tree in this module's layout
# ---------------------------------------------------------------------------


def _numbered(tree, word):
    names = [k for k in tree if k.rsplit("_", 1)[0].endswith(word)]
    return sorted(names, key=lambda k: int(k.rsplit("_", 1)[1]))


def _swiglu_params(w_gate_up, w_down):
    """The program fuses ``w1`` (gate) and ``w3`` (up) into ``w_gate_up``,
    gate first, along its last axis."""
    F = w_down.shape[-2]
    return {"w1": w_gate_up[..., :F], "w3": w_gate_up[..., F:], "w2": w_down}


def _layer_params(b, bias, config):
    out = {"operator_norm": b["RMSNorm_0"]["weight"],
           "ffn_norm": b["RMSNorm_1"]["weight"]}
    if "ShortConvMixer_0" in b:
        m = b["ShortConvMixer_0"]
        out["conv"] = {key: m[key] for key in ("w_in", "conv", "w_out")}
    else:
        a = b["Attention_0"]
        H, Hkv = (int(config["num_attention_heads"]),
                  int(config["num_key_value_heads"]))
        hd = head_dim(config)
        out["attn"] = {"wq": a["wqkv"][:, :H * hd],
                       "wk": a["wqkv"][:, H * hd:(H + Hkv) * hd],
                       "wv": a["wqkv"][:, (H + Hkv) * hd:], "wo": a["wo"],
                       "q_norm": a["q_norm"]["weight"],
                       "k_norm": a["k_norm"]["weight"]}
    if "MoEFeedForward_0" in b:
        m = b["MoEFeedForward_0"]
        out["moe"] = {"router": m["w_router"], "bias": bias,
                      "experts": _swiglu_params(m["w_gate_up"], m["w_down"])}
    else:
        f = b["FeedForward_0"]
        out["mlp"] = _swiglu_params(f["w_gate_up"], f["w_down"])
    return out


def reference_params(params, config, router_state=None):
    """The program's parameter tree (``TrainState.params``; ``router_state``
    is ``TrainState.model_state["router_state"]``, zeros where absent) in
    this module's plain layout::

        {"embed": [V, D] (the tied head is its transpose), "final_norm": [D],
         "layers": [{"operator_norm": [D], "ffn_norm": [D],
                     "conv": {w_in [D, 3 D], conv [K, D], w_out [D, D]}
                     | "attn": {wq, wk, wv, wo, q_norm [hd], k_norm [hd]},
                     "moe": {router [D, E], bias [E],
                             experts: {w1, w3 [held, D, F], w2 [held, F, D]}}
                     | "mlp": {w1, w3 [D, F], w2 [F, D]}}]}

    The program fuses q, k, v into ``wqkv`` (columns in that order); blocks
    are ``[Checkpoint]Block_<i>``, each with two norms, a mixer and a
    feed-forward part."""
    E = int(config["router_experts"])
    router_state = router_state or {}

    def bias_of(name):
        return router_state.get(name, {}).get("MoEFeedForward_0", {}).get(
            "bias", jnp.zeros((E,), jnp.float32))

    return {"embed": params["embed"],
            "layers": [_layer_params(params[name], bias_of(name), config)
                       for name in _numbered(params, "Block")],
            "final_norm": params["RMSNorm_0"]["weight"]}
