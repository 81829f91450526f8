"""Ling-3.0-flash-VL's language model: forward pass and next-token loss, one
sequence at a time.

Plain ``jax.numpy`` in float32, every matrix product at ``highest`` precision
(on a TPU a float32 product is otherwise rounded to bfloat16 passes), no
kernels, no chunking, no batching, nothing from ``fedml_tpu``. The catalog
gives the model's ``config.json`` and no prose, so the equations are written
down here from the keys, with what had to be assumed marked *assumed* (and
listed under ``assumed`` in the configuration file). The vision tower has no
sizes in the catalog row and is left out: this is the language model alone.

**Stack.** Token embedding; ``first_k_dense_replace`` blocks with a dense
SwiGLU of ``intermediate_size``, then blocks with an expert layer; every block
is ``x + mixer(RMSNorm(x))`` then ``x + ffn(RMSNorm(x))``. Layer ``i`` mixes by
latent attention (MLA) where ``(i + 1) % layer_group_size == 0`` and by Kimi
delta attention (KDA) elsewhere. Final RMSNorm, untied head. No
hyper-connections, and no MTP module (the row gives no count).

**KDA** (Kimi Linear, arXiv:2510.26692, with this config's keys), per head
``h`` of ``num_attention_heads``, ``dk = dv = head_dim``::

    q = l2norm(silu(conv(x W_q))) / sqrt(dk),  k = l2norm(silu(conv(x W_k)))
    v = silu(conv(x W_v))
    g_t = kda_lower_bound * sigmoid(exp(A_log_h) * (x W_f + dt_bias))   in R^dk
    beta_t = sigmoid(x W_b)_h
    S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t                      S_0 = 0, a scan over the tokens
    y_t = concat_h(RMSNorm(o_t) * sigmoid(x W_g)_h) W_o

``conv`` is a causal depthwise convolution of ``short_conv_kernel_size`` taps
over the sequence, one filter a channel (``linear_silu``: the activation
after it is silu); as many key / value heads as query heads
(``num_kv_heads_for_linear_attn`` 0); no positions in these layers. The norm
of ``o`` is over each head's ``dv`` values with one weight vector shared by
the heads (``group_norm_size`` 1; the shared weight is *assumed*), the output
gate one scalar a head (``gated_attention_proj_granularity_type`` head_wise).
*Assumed*: the decay gate's formula. The catalog gives ``kda_safe_gate`` and
``kda_lower_bound`` -5 and no formula; Kimi Linear's own gate is
``g_t = -exp(A_log) * softplus(x W_f + dt_bias)``, unbounded below. The
bounded reading above keeps ``exp(g_t)`` in ``(e^-5, 1)``. ``l2norm`` adds
1e-6 under the root (*assumed*, FLA's).

**MLA** without a q-LoRA (``q_lora_rank`` null)::

    q = x W_q -> per head (q_nope[128], q_rope[64])
    (c_kv, k_rope) = split(x W_kva, [kv_lora_rank, 64]);  k_rope shared by heads
    (k_nope[128], v[128]) = split(RMSNorm(c_kv) W_kvb) per head
    rotary on q_rope and k_rope only (``rotary_dim`` 64, ``use_mla_nope``
      false), ``rope_theta`` 6e6, no scaling, half-split pairs (i, i + 32)
      (*assumed*: the program's ``apply_rotary``)
    scores = (q . k) * 192^-0.5, causal softmax, o = concat(heads) W_o

``use_qk_norm`` true is read, *as an assumption*, as the norms these layers
have by construction (MLA's latent norm, KDA's L2 norm of q and k): the row
does not say where a further per-head norm would sit, and none is added.

**Expert layer** (``noaux_tc`` with groups; DeepSeek-V3's rule)::

    s = sigmoid(x W_r)                    float32, over all router_experts (512)
    the experts are n_group contiguous groups; a group scores the sum of its
      two largest (s + b) (*assumed*: top 2, DeepSeek-V3's); the topk_group
      best groups are kept, the rest masked out
    selected = top num_experts_per_tok of (s + b) among the kept
    w_i = s_i / sum_{selected} s * routed_scaling_factor
    y = shared(x) + sum_{i selected and held here} w_i expert_i(x)

SwiGLU of ``moe_intermediate_size`` everywhere, no clamp
(``expert_swiglu_limit_list`` and ``share_expert_swiglu_limit_list`` are 0 in
layers 0 to 34, which holds every layer of the cut). This chip holds
``num_experts`` experts from ``expert_offset``; what the absent experts would
have added is left out (model-configs guide, section 4), and that partial
result goes on to the next layer. ``b`` is state, moved after each training
step; zero at initialisation.

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


def swiglu(x, w_gate, w_up, w_down):
    return _mm(jax.nn.silu(_mm(x, w_gate)) * _mm(x, w_up), w_down)


# ---------------------------------------------------------------------------
# Kimi delta attention
# ---------------------------------------------------------------------------


def causal_depthwise_conv(x, taps):
    """x: [L, channels]; taps: [K, channels]. ``y_t = sum_j taps[j] *
    x_{t - (K - 1 - j)}``, zeros before the sequence's start."""
    K, L = taps.shape[0], x.shape[0]
    padded = jnp.concatenate([jnp.zeros((K - 1, x.shape[1]), x.dtype), x])
    return sum(taps[j] * padded[j:j + L] for j in range(K))


def l2norm(x):
    return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + 1e-6)


def delta_rule_recurrence(q, k, v, g, beta):
    """The recurrence, token by token. q (scaled), k: [L, H, dk]; v:
    [L, H, dv]; g: [L, H, dk] log decay; beta: [L, H]. Returns (o
    [L, H, dv], the final state [H, dk, dv])."""
    def step(S, xs):
        qt, kt, vt, gt, bt = xs
        S = jnp.exp(gt)[..., None] * S                       # Diag(alpha) S
        seen = jnp.einsum("hk,hkv->hv", kt, S, precision=HIGHEST)
        S = S + jnp.einsum("hk,hv->hkv", kt, bt[:, None] * (vt - seen),
                           precision=HIGHEST)
        return S, jnp.einsum("hk,hkv->hv", qt, S, precision=HIGHEST)

    S0 = jnp.zeros((k.shape[1], k.shape[2], v.shape[2]), jnp.float32)
    S, o = jax.lax.scan(step, S0, (q, k, v, g, beta))
    return o, S


def kda(p, x, config):
    L = x.shape[0]
    H, hd = int(config["num_attention_heads"]), int(config["head_dim"])

    def heads(a):
        return a.reshape(L, H, hd)

    q = heads(jax.nn.silu(causal_depthwise_conv(_mm(x, p["wq"]), p["conv_q"])))
    k = heads(jax.nn.silu(causal_depthwise_conv(_mm(x, p["wk"]), p["conv_k"])))
    v = heads(jax.nn.silu(causal_depthwise_conv(_mm(x, p["wv"]), p["conv_v"])))
    q, k = l2norm(q) * hd ** -0.5, l2norm(k)
    g = float(config["kda_lower_bound"]) * jax.nn.sigmoid(
        jnp.exp(p["A_log"])[None, :, None] * heads(_mm(x, p["wf"]) + p["dt_bias"]))
    beta = jax.nn.sigmoid(_mm(x, p["wb"]))
    o, _ = delta_rule_recurrence(q, k, v, g, beta)
    o = rms_norm(o, p["o_norm"], float(config["rms_norm_eps"]))
    o = o * jax.nn.sigmoid(_mm(x, p["wg"]))[:, :, None]
    return _mm(o.reshape(L, H * hd), p["wo"])


# ---------------------------------------------------------------------------
# latent attention
# ---------------------------------------------------------------------------


def rotary(x, theta):
    """x: [L, H, d]. Rotates the pair (x[..., i], x[..., i + d/2]) by
    ``position * theta^(-2i/d)``."""
    L, _, d = x.shape
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(L, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def causal_attention(q, k, v, scale, q_block=512):
    """q, k: [L, H, dqk]; v: [L, H, dv] -> [L, H, dv], one block of queries at
    a time so the [H, L, L] scores are never whole in memory."""
    L = q.shape[0]
    q, k, v = _round(q), _round(k), _round(v)
    out = []
    for start in range(0, L, q_block):
        qb = q[start:start + q_block]
        scores = jnp.einsum("qhd,khd->hqk", qb, k, precision=HIGHEST) * scale
        q_pos = jnp.arange(start, start + qb.shape[0])[:, None]
        scores = jnp.where((jnp.arange(L)[None, :] <= q_pos)[None], scores,
                           -jnp.inf)
        probs = _round(jax.nn.softmax(scores, axis=-1))
        out.append(jnp.einsum("hqk,khd->qhd", probs, v, precision=HIGHEST))
    return jnp.concatenate(out, axis=0)


def mla(p, x, config):
    if config.get("q_lora_rank"):
        raise ValueError("this model's latent attention has no q-LoRA")
    L = x.shape[0]
    H = int(config["num_attention_heads"])
    dn, dr = int(config["qk_nope_head_dim"]), int(config["qk_rope_head_dim"])
    dv, rkv = int(config["v_head_dim"]), int(config["kv_lora_rank"])
    eps, theta = float(config["rms_norm_eps"]), float(config["rope_theta"])
    q = _mm(x, p["wq"]).reshape(L, H, dn + dr)
    kv_a = _mm(x, p["wkv_a"])
    c_kv, k_rope = kv_a[:, :rkv], kv_a[:, rkv:]
    kv = _mm(rms_norm(c_kv, p["kv_norm"], eps), p["wkv_b"]).reshape(L, H, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    q = jnp.concatenate([q[..., :dn], rotary(q[..., dn:], theta)], axis=-1)
    k_rope = rotary(k_rope[:, None, :], theta)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (L, H, dr))], axis=-1)
    out = causal_attention(q, k, v, (dn + dr) ** -0.5)
    return _mm(out.reshape(L, H * dv), p["wo"])


# ---------------------------------------------------------------------------
# expert layer
# ---------------------------------------------------------------------------


def _kept(score, n_group, kept_groups):
    """Selection scores [L, E] -> (the scores with the experts outside the
    kept groups at minus infinity, the group scores [L, n_group] sorted
    downwards, the groups' ranks [L, n_group])."""
    L, E = score.shape
    grouped = score.reshape(L, n_group, E // n_group)
    group_score = jax.lax.top_k(grouped, 2)[0].sum(-1)
    order = jnp.argsort(-group_score, axis=-1)
    rank = jnp.argsort(order, axis=-1)                       # [L, n_group]
    masked = jnp.where((rank < kept_groups)[:, :, None], grouped, -jnp.inf)
    return (masked.reshape(L, E),
            jnp.take_along_axis(group_score, order, axis=-1), rank)


def _select(masked, s, k, held):
    """Top k of the masked selection scores -> (selected [L, k], their raw
    scores, the margin [L] of the choice among these candidates: the smaller
    of (the lowest selected expert held here) minus (the best expert left
    out), and (the last expert selected) minus (the best expert held here
    that was left out); infinity where neither exists; whether any expert
    held here was selected [L])."""
    top, idx = jax.lax.top_k(masked, k + 1)
    selected = idx[:, :k]
    experts = jnp.arange(masked.shape[-1])
    is_selected = (selected[:, :, None] == experts).any(1)
    lowest_held_in = jnp.where(held & is_selected, masked, jnp.inf).min(-1)
    best_held_out = jnp.where(held & ~is_selected, masked, -jnp.inf).max(-1)
    margin = jnp.minimum(lowest_held_in - top[:, k],
                         top[:, k - 1] - best_held_out)
    return (selected, jnp.take_along_axis(s, selected, axis=-1), margin,
            (held & is_selected).any(-1))


def route(p, x, config):
    """-> (selected [L, k] expert ids, weights [L, k], margin [L]): how far
    the selection scores ``s + b`` are from a choice that changes what this
    chip computes. Among the experts of the kept groups that is
    :func:`_select`'s margin. Among the groups it is the gap between the last
    group kept and the best group left out, counted where the other choice
    would change this chip's work: where one of the two is a group in which
    held experts live, or where a held expert is selected with either set of
    groups (the candidates, and with them the renormalised weights, change
    at once). A swap among experts or groups held elsewhere that leaves no
    held expert selected moves nothing here and does not count."""
    k = int(config["num_experts_per_tok"])
    lo = int(config["expert_offset"])
    hi = lo + int(config["num_experts"])
    n_group, kept_groups = int(config["n_group"]), int(config["topk_group"])
    s = jax.nn.sigmoid(jnp.matmul(x, p["router"], precision=HIGHEST))
    score = s + p["bias"]
    experts = jnp.arange(score.shape[-1])
    held = (experts >= lo) & (experts < hi)
    if n_group > 1:
        masked, group_sorted, rank = _kept(score, n_group, kept_groups)
    else:
        masked = score
    selected, chosen, margin, any_held = _select(masked, s, k, held)
    if 1 < n_group and kept_groups < n_group:
        gap = group_sorted[:, kept_groups - 1] - group_sorted[:, kept_groups]
        size = score.shape[-1] // n_group
        ours = (jnp.arange(n_group) >= lo // size) & (
            jnp.arange(n_group) <= (hi - 1) // size)
        # the other choice: the best group left out in, the last kept out
        swapped = jnp.where(rank == kept_groups - 1, kept_groups,
                            jnp.where(rank == kept_groups, kept_groups - 1, rank))
        other = jnp.where((swapped < kept_groups)[:, :, None],
                          score.reshape(-1, n_group, size), -jnp.inf)
        _, _, _, any_held_other = _select(
            other.reshape(score.shape), s, k, held)
        at_the_edge = (ours & ((rank == kept_groups - 1)
                               | (rank == kept_groups))).any(-1)
        counts = at_the_edge | any_held | any_held_other
        margin = jnp.minimum(margin, jnp.where(counts, gap, jnp.inf))
    weights = chosen / chosen.sum(-1, keepdims=True) * float(
        config["routed_scaling_factor"])
    return selected, weights, margin


def expert_layer(p, x, config):
    lo = int(config["expert_offset"])
    selected, weights, margin = route(p, x, config)
    y = swiglu(x, **p["shared"])
    for e in range(int(config["num_experts"])):
        w = jnp.where(selected == lo + e, weights, 0.0).sum(-1)
        y = y + w[:, None] * swiglu(
            x, p["experts"]["w_gate"][e], p["experts"]["w_up"][e],
            p["experts"]["w_down"][e])
    return y, margin


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------


def mixer_of(layer: int, config) -> str:
    G = int(config["layer_group_size"])
    return "mla" if G and (layer + 1) % G == 0 else "kda"


def block(p, x, layer, config):
    """-> (hidden states, routing margin [L] or None)."""
    eps = float(config["rms_norm_eps"])
    mix = mla if mixer_of(layer, config) == "mla" else kda
    x = x + mix(p["mixer"], rms_norm(x, p["mixer_norm"], eps), config)
    h = rms_norm(x, p["ffn_norm"], eps)
    if "moe" in p:
        y, margin = expert_layer(p["moe"], h, config)
        return x + y, margin
    return x + swiglu(h, **p["mlp"]), None


def hidden_states(params, tokens, config):
    """tokens: [L] int -> (final-norm hidden states [L, D], the smallest
    routing margin of every position over the expert layers [L])."""
    x = params["embed"][tokens]
    margin = jnp.full((tokens.shape[0],), jnp.inf)
    for i, layer in enumerate(params["layers"]):
        x, m = block(layer, x, i, config)
        if m is not None:
            margin = jnp.minimum(margin, m)
    return rms_norm(x, params["final_norm"], float(config["rms_norm_eps"])), margin


def logits_and_loss(params, tokens, config):
    """Logits [L, V], the summed next-token cross-entropy over the ``L - 1``
    predicted positions, the routing margins [L]."""
    hidden, margin = hidden_states(params, tokens, config)
    logits = _mm(hidden, params["lm_head"])
    logp = jax.nn.log_softmax(logits[:-1], axis=-1)
    nll = -jnp.take_along_axis(logp, tokens[1:, None], axis=-1)[:, 0].sum()
    return logits, nll, margin


def loss_sum_and_tail_logits(params, tokens, config, tail: int):
    """What the benchmark's job compares: the summed loss of one sequence,
    the MTP module's (none here: zero), the logits of its last ``tail``
    positions and their routing margins."""
    logits, nll, margin = logits_and_loss(params, tokens, config)
    return nll, jnp.zeros(()), logits[-tail:], margin[-tail:]


# ---------------------------------------------------------------------------
# the program's parameter tree in this module's layout
# ---------------------------------------------------------------------------


def _numbered(tree, word):
    names = [k for k in tree if k.rsplit("_", 1)[0].endswith(word)]
    return sorted(names, key=lambda k: int(k.rsplit("_", 1)[1]))


def _gate_up(w):
    half = w.shape[-1] // 2
    return {"w_gate": w[..., :half], "w_up": w[..., half:]}


def _mixer_params(b, config):
    if "KimiDeltaAttention_0" in b:
        a = b["KimiDeltaAttention_0"]
        H = int(config["num_attention_heads"])
        w = H * int(config["head_dim"])
        return {"wq": a["wqkv"][:, :w], "wk": a["wqkv"][:, w:2 * w],
                "wv": a["wqkv"][:, 2 * w:],
                "conv_q": a["conv"][:, :w], "conv_k": a["conv"][:, w:2 * w],
                "conv_v": a["conv"][:, 2 * w:],
                "wf": a["wf"], "A_log": a["A_log"], "dt_bias": a["dt_bias"],
                "wb": a["wbg"][:, :H], "wg": a["wbg"][:, H:],
                "o_norm": a["o_norm"], "wo": a["wo"]}
    a = b["LatentAttention_0"]
    return {"wq": a["wq"], "wkv_a": a["wkv_a"],
            "kv_norm": a["kv_norm"]["weight"], "wkv_b": a["wkv_b"],
            "wo": a["wo"]}


def _block_params(b, bias, config):
    out = {"mixer_norm": b["RMSNorm_0"]["weight"],
           "mixer": _mixer_params(b, config),
           "ffn_norm": b["RMSNorm_1"]["weight"]}
    if "MoEFeedForward_0" in b:
        m = b["MoEFeedForward_0"]
        out["moe"] = {
            "router": m["w_router"], "bias": bias,
            "shared": {**_gate_up(m["shared"]["w_gate_up"]),
                       "w_down": m["shared"]["w_down"]},
            "experts": {**_gate_up(m["w_gate_up"]), "w_down": m["w_down"]},
        }
    else:
        f = b["FeedForward_0"]
        out["mlp"] = {**_gate_up(f["w_gate_up"]), "w_down": f["w_down"]}
    return out


def reference_params(params, config, router_state=None):
    """The program's parameter tree (``TrainState.params``; ``router_state``
    is ``TrainState.model_state["router_state"]``, zeros where absent) in
    this module's plain layout::

        {"embed": [V, D], "final_norm": [D], "lm_head": [D, V],
         "layers": [{"mixer_norm": [D], "ffn_norm": [D],
                     "mixer": {wq, wk, wv [D, H dk], conv_q, conv_k, conv_v
                               [K, H dk], wf [D, H dk], A_log [H], dt_bias
                               [H dk], wb, wg [D, H], o_norm [dv], wo}  (KDA)
                            | {wq [D, H 192], wkv_a, kv_norm, wkv_b, wo} (MLA)
                     "mlp": {w_gate, w_up, w_down}            (dense layer)
                     | "moe": {router [D, E], bias [E], shared: {...},
                               experts: {w_gate [held, D, F], w_up, w_down}}}]}

    The program fuses q, k, v into ``wqkv`` and their filters into ``conv``
    (columns in that order), beta and the output gate into ``wbg``, gate and
    up into ``w_gate_up``; blocks are ``[Checkpoint]Block_<i>``."""
    E = int(config["router_experts"])
    router_state = router_state or {}

    def bias_of(*path):
        node = router_state
        for key in path:
            node = node.get(key, {})
        return node.get("bias", jnp.zeros((E,), jnp.float32))

    return {"embed": params["embed"],
            "layers": [_block_params(params[name],
                                     bias_of(name, "MoEFeedForward_0"), config)
                       for name in _numbered(params, "Block")],
            "final_norm": params["RMSNorm_0"]["weight"],
            "lm_head": params["w_lm_head"]}
