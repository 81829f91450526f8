"""NVIDIA-Nemotron-3-Nano-30B-A3B (``model_type`` ``nemotron_h``) forward pass
and next-token loss, one sequence at a time.

Plain ``jax.numpy`` in float32, every matrix product at ``highest`` precision
(on a TPU a float32 product is otherwise rounded to bfloat16 passes), no
kernels, no chunks, no batching, nothing from ``fedml_tpu``. The catalog gives
the model's ``config.json`` and a one-line description; the equations below are
the family's (Mamba-2, arXiv:2405.21060; Nemotron-H, arXiv:2504.03624) at the
config's sizes, with what the config does not settle marked *assumed* (and
listed under ``assumed`` in the configuration file).

**Stack.** ``hybrid_override_pattern`` gives one letter a layer, and a layer
is ONE pre-norm residual sublayer: ``h <- h + F_i(RMSNorm_i(h))`` with ``F_i``
a Mamba-2 mixer (``M``), an expert layer (``E``), attention (``*``) or a dense
feed-forward (``-``; none in this model). Final RMSNorm, untied head.
``rescale_prenorm_residual`` scales an initialiser and is not applied to the
drawn weights (*assumed*).

**M**, with ``H = mamba_num_heads`` heads of ``P = mamba_head_dim`` (so
``d_inner = H P``, not ``expand x hidden_size``), ``G = n_groups`` groups of
state size ``N = ssm_state_size``, ``K = conv_kernel`` taps::

    [z | xBC | dt] = x W_in                 [H P | H P + 2 G N | H], no bias
    xBC = silu(conv(xBC))                   causal depthwise with bias:
        y[t, c] = b[c] + sum_k w[k, c] xBC[t - (K - 1) + k, c], zeros before 0
    (X [H, P], B [G, N], C [G, N]) = split(xBC);  head h reads group h // (H / G)
    dt[t, h] = softplus(dt[t, h] + dt_bias[h])     no clamp beyond it (*assumed*:
                                                   time_step_limit is (0, inf))
    A[h] = -exp(A_log[h])
    S[t, h] = exp(dt[t, h] A[h]) S[t-1, h] + dt[t, h] X[t, h] (x) B[t, g]   [P, N]
    y[t, h] = S[t, h] C[t, g] + D[h] X[t, h]                     S[-1] = 0
    out = GroupRMSNorm(y * silu(z)) W_out   the gate first (*assumed*:
        Mamba-2's ``norm_before_gate`` false), the norm over each of the G
        groups of H P / G channels, one weight of H P

The recurrence is scanned token by token (:func:`ssd_scan`): the program runs
a chunked form, and this one has no chunk to get wrong.

**E** (``n_group`` = ``topk_group`` = 1, so no group step)::

    s = sigmoid(x W_r)                      float32, over all router_experts
    selected = top num_experts_per_tok of (s + b)
    w_i = s_i / sum_{selected} s * routed_scaling_factor
    y = shared(x) + sum_{i selected and held here} w_i expert_i(x)
    expert(x) = relu(x W_up)^2 W_down       no gate matrix (``relu2``)

The shared expert is the same at ``moe_shared_expert_intermediate_size``.
This chip holds ``n_routed_experts`` experts from ``expert_offset``; what the
absent experts would have added is left out (model-configs guide, section 4),
and that partial result goes on to the next layer. ``b`` is state, moved after
each training step (*assumed* rate); it is zero at initialisation.

**\\*** GQA, ``num_attention_heads`` query and ``num_key_value_heads`` key /
value heads of ``head_dim``, no bias, causal softmax of ``q k^T /
sqrt(head_dim)``, **no rotary** (*assumed*: the family's attention layers
take no positions, the recurrent layers order the tokens; ``rope_theta`` and
``partial_rotary_factor`` go unused). :data:`ROTARY` switches a half-split
rotation on, to read what that mistake costs.

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
# the one-mistake study's switch: rotate q and k as the other cells' GQA does
ROTARY = False


def _round(a):
    if MATMUL_INPUT_DTYPE is None:
        return a
    return a.astype(MATMUL_INPUT_DTYPE).astype(jnp.float32)


def _mm(a, b):
    return jnp.matmul(_round(a), _round(b), precision=HIGHEST)


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def relu2(x, w_up, w_down):
    return _mm(jnp.square(jax.nn.relu(_mm(x, w_up))), w_down)


# ---------------------------------------------------------------------------
# the Mamba-2 mixer
# ---------------------------------------------------------------------------


def causal_depthwise_conv(x, taps, bias):
    """x: [L, C]; taps: [K, C]; bias: [C] -> [L, C]. Output ``t`` reads inputs
    ``t - (K - 1) .. t``: tap ``K - 1`` multiplies the current token."""
    K, L = taps.shape[0], x.shape[0]
    padded = jnp.concatenate([jnp.zeros((K - 1, x.shape[1]), x.dtype), x])
    y = bias
    for k in range(K):
        y = y + taps[k] * padded[k:k + L]
    return y


def ssd_scan(X, dt, A, B, C):
    """The recurrence, token by token. X: [L, H, P]; dt: [L, H]; A: [H]; B,
    C: [L, G, N] -> y [L, H, P] (without the ``D`` skip)."""
    H, P = X.shape[1:]
    G, N = B.shape[1:]
    B, C = (jnp.repeat(_round(v), H // G, axis=1) for v in (B, C))  # [L, H, N]
    written = _round(dt[..., None] * X)                             # [L, H, P]

    def step(S, inputs):
        decay_t, w_t, B_t, C_t = inputs
        S = decay_t[:, None, None] * S + w_t[:, :, None] * B_t[:, None, :]
        return S, jnp.einsum("hpn,hn->hp", S, C_t, precision=HIGHEST)

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), jnp.float32),
                        (jnp.exp(dt * A), written, B, C))
    return y


def gated_group_norm(y, z, weight, groups, eps):
    """``GroupRMSNorm(y * silu(z))``: the gate first, then an RMS norm over
    each of ``groups`` contiguous groups of channels. y, z: [L, C]."""
    L, C = y.shape
    g = (y * jax.nn.silu(z)).reshape(L, groups, C // groups)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
    return g.reshape(L, C) * weight


def mamba(p, x, config):
    L = x.shape[0]
    H, P = int(config["mamba_num_heads"]), int(config["mamba_head_dim"])
    G, N = int(config["n_groups"]), int(config["ssm_state_size"])
    inner = H * P
    proj = _mm(x, p["w_in"])
    z, xbc, dt = (proj[:, :inner], proj[:, inner:2 * inner + 2 * G * N],
                  proj[:, 2 * inner + 2 * G * N:])
    xbc = jax.nn.silu(causal_depthwise_conv(xbc, p["conv"], p["conv_bias"]))
    X = xbc[:, :inner].reshape(L, H, P)
    B = xbc[:, inner:inner + G * N].reshape(L, G, N)
    C = xbc[:, inner + G * N:].reshape(L, G, N)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    y = ssd_scan(X, dt, -jnp.exp(p["A_log"]), B, C) + p["D"][:, None] * X
    y = gated_group_norm(y.reshape(L, inner), z, p["norm"], G,
                         float(config["rms_norm_eps"]))
    return _mm(y, p["w_out"])


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------


def rotary(x, theta):
    """x: [L, H, d]: the pair (x[..., i], x[..., i + d/2]) rotated by
    ``position * theta^(-2i/d)`` (only under :data:`ROTARY`)."""
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


def attention(p, x, config):
    L = x.shape[0]
    H, Hkv = int(config["num_attention_heads"]), int(config["num_key_value_heads"])
    hd = int(config["head_dim"])
    q = _mm(x, p["wq"]).reshape(L, H, hd)
    k = _mm(x, p["wk"]).reshape(L, Hkv, hd)
    v = _mm(x, p["wv"]).reshape(L, Hkv, hd)
    if ROTARY:
        theta = float(config["rope_theta"])
        q, k = rotary(q, theta), rotary(k, theta)
    return _mm(causal_attention(q, k, v).reshape(L, H * hd), p["wo"])


# ---------------------------------------------------------------------------
# expert layer
# ---------------------------------------------------------------------------


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
    hi = lo + int(config["n_routed_experts"])
    s = jax.nn.sigmoid(jnp.matmul(x, p["router"], precision=HIGHEST))
    score = s + p["bias"]
    top, idx = jax.lax.top_k(score, k + 1)
    selected = idx[:, :k]
    chosen = jnp.take_along_axis(s, selected, axis=-1)
    weights = chosen / chosen.sum(-1, keepdims=True) * float(
        config["routed_scaling_factor"])
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
    y = relu2(x, **p["shared"])
    for e in range(int(config["n_routed_experts"])):
        w = jnp.where(selected == lo + e, weights, 0.0).sum(-1)
        y = y + w[:, None] * relu2(x, p["experts"]["w_up"][e],
                                   p["experts"]["w_down"][e])
    return y, margin


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------


def hidden_states(params, tokens, config):
    """tokens: [L] int -> (final-norm hidden states [L, D], the smallest
    routing margin of every position over the expert layers [L])."""
    eps = float(config["rms_norm_eps"])
    h = params["embed"][tokens]
    margin = jnp.full((tokens.shape[0],), jnp.inf)
    for layer in params["layers"]:
        x = rms_norm(h, layer["norm"], eps)
        if "mamba" in layer:
            h = h + mamba(layer["mamba"], x, config)
        elif "attn" in layer:
            h = h + attention(layer["attn"], x, config)
        elif "moe" in layer:
            y, m = expert_layer(layer["moe"], x, config)
            h, margin = h + y, jnp.minimum(margin, m)
        else:
            h = h + relu2(x, **layer["mlp"])
    return rms_norm(h, params["final_norm"], eps), margin


def logits_and_loss(params, tokens, config):
    """Logits [L, V] over the vocabulary rows held; the summed next-token
    cross-entropy over the ``L - 1`` predicted positions; the routing margins
    [L]."""
    hidden, margin = hidden_states(params, tokens, config)
    logits = _mm(hidden, params["lm_head"])
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


def _up_down(p):
    return {"w_up": p["w_up"], "w_down": p["w_down"]}


def _layer_params(b, bias, config):
    out = {"norm": b["RMSNorm_0"]["weight"]}
    if "Mamba2Mixer_0" in b:
        m = b["Mamba2Mixer_0"]
        out["mamba"] = {key: m[key] for key in (
            "w_in", "conv", "conv_bias", "dt_bias", "A_log", "D", "norm",
            "w_out")}
    elif "Attention_0" in b:
        a = b["Attention_0"]
        H, Hkv = (int(config["num_attention_heads"]),
                  int(config["num_key_value_heads"]))
        hd = int(config["head_dim"])
        out["attn"] = {"wq": a["wqkv"][:, :H * hd],
                       "wk": a["wqkv"][:, H * hd:(H + Hkv) * hd],
                       "wv": a["wqkv"][:, (H + Hkv) * hd:], "wo": a["wo"]}
    elif "MoEFeedForward_0" in b:
        m = b["MoEFeedForward_0"]
        out["moe"] = {"router": m["w_router"], "bias": bias,
                      "shared": _up_down(m["shared"]), "experts": _up_down(m)}
    else:
        out["mlp"] = _up_down(b["FeedForward_0"])
    return out


def reference_params(params, config, router_state=None):
    """The program's parameter tree (``TrainState.params``; ``router_state``
    is ``TrainState.model_state["router_state"]``, zeros where absent) in
    this module's plain layout::

        {"embed": [V, D], "final_norm": [D], "lm_head": [D, V],
         "layers": [{"norm": [D],
                     "mamba": {w_in [D, 2 H P + 2 G N + H], conv [K, H P + 2 G N],
                               conv_bias, dt_bias [H], A_log [H], D [H],
                               norm [H P], w_out [H P, D]}
                     | "attn": {wq, wk, wv, wo}
                     | "moe": {router [D, E], bias [E], shared: {w_up, w_down},
                               experts: {w_up [held, D, F], w_down [held, F, D]}}
                     | "mlp": {w_up, w_down}}]}

    The program fuses q, k, v into ``wqkv`` (columns in that order); blocks
    are ``[Checkpoint]Block_<i>``, each with one norm and one sublayer."""
    E = int(config["router_experts"])
    router_state = router_state or {}

    def bias_of(name):
        return router_state.get(name, {}).get("MoEFeedForward_0", {}).get(
            "bias", jnp.zeros((E,), jnp.float32))

    return {"embed": params["embed"],
            "layers": [_layer_params(params[name], bias_of(name), config)
                       for name in _numbered(params, "Block")],
            "final_norm": params["RMSNorm_0"]["weight"],
            "lm_head": params["w_lm_head"]}
