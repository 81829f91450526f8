"""Xing4.0-29B-A4B forward pass and next-token loss, one sequence at a time.

Plain ``jax.numpy`` in float32, every matrix product at ``highest`` precision
(on a TPU a float32 product is otherwise rounded to bfloat16 passes), no
kernels, no batching, nothing from ``fedml_tpu``. The catalog gives the
model's ``config.json`` and no prose, so the equations are written down here
from the keys, with what had to be assumed marked *assumed* (and listed under
``assumed`` in the configuration file).

**Stack.** The token embedding is copied into ``n = hc_mult`` residual
streams ``X`` in ``R^{n x C}`` per position. ``first_k_dense_replace`` blocks
with a dense SwiGLU of ``intermediate_size``, then blocks with an expert
layer. After the last block the streams are summed (*assumed*), RMS-normed and
projected by the untied head.

**mHC** (manifold-constrained hyper-connections) around each sublayer ``F``
(attention, feed-forward)::

    x^      = RMSNorm_w(vec(X))                        vec(X) in R^{nC}
    H~_pre  = a_pre  (x^ P_pre)  + b_pre               in R^n
    H~_post = a_post (x^ P_post) + b_post              in R^n
    H~_res  = a_res mat(x^ P_res) + b_res              in R^{n x n}, row-major
    H_pre = sigmoid(H~_pre),  H_post = 2 sigmoid(H~_post)
    H_res = hc_sinkhorn_iters Sinkhorn-Knopp sweeps (rows, then columns) of
            exp(clip(H~_res, mhc_h_res_clamp_min, mhc_h_res_clamp_max)),
            hc_eps added to each denominator (*assumed*)
    X'      = H_res X + H_post^T (x) F(RMSNorm(H_pre X))

**MLA** (multi-head latent attention), training form, nothing absorbed::

    c_q = RMSNorm(x W_qa),  q = c_q W_qb -> per head (q_nope[128], q_rope[64])
    (c_kv, k_rope) = split(x W_kva, [kv_lora_rank, 64]);  k_rope shared by heads
    (k_nope[128], v[128]) = split(RMSNorm(c_kv) W_kvb) per head
    rotary on q_rope and k_rope only, half-split pairs (i, i + 32) (*assumed*:
      the program's ``apply_rotary``), YaRN inverse frequencies
    scores = (q . k) * (192^-0.5 * m^2),  m = 0.1 * mscale_all_dim * ln(factor) + 1
    cos / sin scaled by mscale / mscale_all_dim (1 here); causal softmax
    o = concat(heads) W_o

YaRN: ``extra_i = theta^(-2i/d)``, ``inter_i = extra_i / factor``; with
``dim(r) = d ln(original / (2 pi r)) / (2 ln theta)``, ``low =
floor(dim(beta_fast))``, ``high = ceil(dim(beta_slow))`` (clamped to
``[0, d - 1]``), ``ramp_i = clip((i - low) / (high - low), 0, 1)`` and
``inv_freq_i = inter_i ramp_i + extra_i (1 - ramp_i)``: independent of the
sequence length.

**Expert layer** (``noaux_tc``, ``n_group`` = ``topk_group`` = 1 so no group
step)::

    s = sigmoid(x W_r)                    float32, over all router_experts (64)
    selected = top num_experts_per_tok of (s + b)
    w_i = s_i / sum_{selected} s * routed_scaling_factor
    y = shared(x) + sum_{i selected and held here} w_i expert_i(x)

SwiGLU of ``moe_intermediate_size`` everywhere. This chip holds
``n_routed_experts`` experts from ``expert_offset``; what the absent experts
would have added is left out (model-configs guide, section 4), and that
partial result goes on to the next layer. ``b`` is state, moved after each
training step; it is zero at initialisation.

**MTP** (``num_nextn_predict_layers`` 1, off in the benchmark's cell)::

    h'_i = [RMSNorm(emb(t_{i+1})); RMSNorm(h_i)] W_eh

where ``h_i`` is the main stack's summed streams before the final norm
(*assumed*), then one more expert block with its own mHC over the copied
streams, its own final norm, the shared embedding and head; loss on
``t_{i+2}``, weighted ``mtp_weight`` (*assumed* 0.3).

Parameters are a plain dict; :func:`reference_params` builds it from the
program's tree and documents the layout.
"""

from __future__ import annotations

import math

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
# rotary positions with YaRN
# ---------------------------------------------------------------------------


def yarn_inv_freq(dim, theta, scaling):
    factor = float(scaling["factor"])
    original = float(scaling["original_max_position_embeddings"])

    def dim_of(rotations):
        return dim * math.log(original / (rotations * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(dim_of(float(scaling["beta_fast"]))), 0)
    high = min(math.ceil(dim_of(float(scaling["beta_slow"]))), dim - 1)
    if high == low:
        high += 0.001
    extra = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return extra / factor * ramp + extra * (1.0 - ramp)


def yarn_mscale(factor, mscale):
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def rotary(x, inv_freq, table_scale):
    """x: [L, H, d]. Rotates the pair (x[..., i], x[..., i + d/2]) by
    ``position * inv_freq[i]``."""
    L, _, d = x.shape
    angles = jnp.arange(L, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = (jnp.cos(angles) * table_scale)[:, None, :]
    sin = (jnp.sin(angles) * table_scale)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------


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
    L = x.shape[0]
    H = int(config["num_attention_heads"])
    dn, dr = int(config["qk_nope_head_dim"]), int(config["qk_rope_head_dim"])
    dv, rkv = int(config["v_head_dim"]), int(config["kv_lora_rank"])
    eps = float(config["rms_norm_eps"])
    scaling = config["rope_scaling"]
    factor = float(scaling["factor"])
    inv_freq = yarn_inv_freq(dr, float(config["rope_theta"]), scaling)
    table_scale = (yarn_mscale(factor, float(scaling["mscale"]))
                   / yarn_mscale(factor, float(scaling["mscale_all_dim"])))
    scale = (dn + dr) ** -0.5
    if scaling["mscale_all_dim"]:
        scale *= yarn_mscale(factor, float(scaling["mscale_all_dim"])) ** 2

    q = _mm(rms_norm(_mm(x, p["wq_a"]), p["q_norm"], eps), p["wq_b"])
    q = q.reshape(L, H, dn + dr)
    kv_a = _mm(x, p["wkv_a"])
    c_kv, k_rope = kv_a[:, :rkv], kv_a[:, rkv:]
    kv = _mm(rms_norm(c_kv, p["kv_norm"], eps), p["wkv_b"]).reshape(L, H, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    q_rope = rotary(q[..., dn:], inv_freq, table_scale)
    k_rope = rotary(k_rope[:, None, :], inv_freq, table_scale)
    q = jnp.concatenate([q[..., :dn], q_rope], axis=-1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (L, H, dr))], axis=-1)
    return _mm(causal_attention(q, k, v, scale).reshape(L, H * dv), p["wo"])


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
    y = swiglu(x, **p["shared"])
    for e in range(int(config["n_routed_experts"])):
        w = jnp.where(selected == lo + e, weights, 0.0).sum(-1)
        y = y + w[:, None] * swiglu(
            x, p["experts"]["w_gate"][e], p["experts"]["w_up"][e],
            p["experts"]["w_down"][e])
    return y, margin


# ---------------------------------------------------------------------------
# hyper-connections
# ---------------------------------------------------------------------------


def sinkhorn(logits, config):
    """[L, n, n] -> doubly stochastic [L, n, n]."""
    eps = float(config["hc_eps"])
    m = jnp.exp(jnp.clip(logits, float(config["mhc_h_res_clamp_min"]),
                         float(config["mhc_h_res_clamp_max"])))
    for _ in range(int(config["hc_sinkhorn_iters"])):
        m = m / (m.sum(-1, keepdims=True) + eps)   # rows
        m = m / (m.sum(-2, keepdims=True) + eps)   # columns
    return m


def hyper_connected(p, X, sublayer, norm_weight, config):
    """One sublayer over the streams X [L, n, C]; ``sublayer`` maps [L, C] to
    ([L, C], extra)."""
    L, n, C = X.shape
    eps = float(config["rms_norm_eps"])
    xhat = rms_norm(X.reshape(L, n * C), p["norm"], eps)
    pre = jax.nn.sigmoid(p["a_pre"] * _mm(xhat, p["w_pre"]) + p["b_pre"])
    post = 2.0 * jax.nn.sigmoid(p["a_post"] * _mm(xhat, p["w_post"]) + p["b_post"])
    res = sinkhorn(p["a_res"] * _mm(xhat, p["w_res"]).reshape(L, n, n)
                   + p["b_res"], config)
    x = jnp.einsum("ln,lnc->lc", pre, X, precision=HIGHEST)
    y, extra = sublayer(rms_norm(x, norm_weight, eps))
    X = (jnp.einsum("lij,ljc->lic", res, X, precision=HIGHEST)
         + post[:, :, None] * y[:, None, :])
    return X, extra


def block(p, X, config):
    """-> (streams, margin [L] or None)."""
    X, _ = hyper_connected(p["hc_attn"], X,
                           lambda x: (mla(p["attn"], x, config), None),
                           p["attn_norm"], config)
    if "moe" in p:
        return hyper_connected(p["hc_ffn"], X,
                               lambda x: expert_layer(p["moe"], x, config),
                               p["ffn_norm"], config)
    return hyper_connected(p["hc_ffn"], X,
                           lambda x: (swiglu(x, **p["mlp"]), None),
                           p["ffn_norm"], config)


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------


def hidden_states(params, tokens, config):
    """tokens: [L] int -> (final-norm hidden states [L, D], the MTP module's
    final-norm hidden states [L, D] or None, the smallest routing margin of
    every position over the main stack's expert layers [L])."""
    n = int(config["hc_mult"])
    eps = float(config["rms_norm_eps"])
    emb = params["embed"][tokens]
    X = jnp.broadcast_to(emb[:, None, :], (emb.shape[0], n, emb.shape[1]))
    margin = jnp.full((tokens.shape[0],), jnp.inf)
    for layer in params["layers"]:
        X, m = block(layer, X, config)
        if m is not None:
            margin = jnp.minimum(margin, m)
    h = X.sum(axis=1)
    mtp_hidden = None
    if "mtp" in params:
        p = params["mtp"]
        nxt = params["embed"][jnp.roll(tokens, -1)]
        z = _mm(jnp.concatenate([rms_norm(nxt, p["emb_norm"], eps),
                                 rms_norm(h, p["h_norm"], eps)], axis=-1),
                p["w_eh"])
        Z, _ = block(p["layer"], jnp.broadcast_to(
            z[:, None, :], (z.shape[0], n, z.shape[1])), config)
        mtp_hidden = rms_norm(Z.sum(axis=1), p["final_norm"], eps)
    return rms_norm(h, params["final_norm"], eps), mtp_hidden, margin


def _nll_sum(logits, targets):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0].sum()


def logits_and_losses(params, tokens, config):
    """Logits [L, V]; the summed next-token cross-entropy over the ``L - 1``
    predicted positions; the MTP module's summed cross-entropy over its
    ``L - 2`` (position ``i`` predicts ``t_{i+2}``; 0 without the module); the
    routing margins [L]."""
    hidden, mtp_hidden, margin = hidden_states(params, tokens, config)
    logits = _mm(hidden, params["lm_head"])
    main = _nll_sum(logits[:-1], tokens[1:])
    mtp = jnp.zeros(())
    if mtp_hidden is not None:
        mtp = _nll_sum(_mm(mtp_hidden[:-2], params["lm_head"]), tokens[2:])
    return logits, main, mtp, margin


def loss_sum_and_tail_logits(params, tokens, config, tail: int):
    """What the benchmark's job compares: the summed main and MTP losses of
    one sequence, the logits of its last ``tail`` positions and their routing
    margins."""
    logits, main, mtp, margin = logits_and_losses(params, tokens, config)
    return main, mtp, logits[-tail:], margin[-tail:]


# ---------------------------------------------------------------------------
# the program's parameter tree in this module's layout
# ---------------------------------------------------------------------------


def _numbered(tree, word):
    names = [k for k in tree if k.rsplit("_", 1)[0].endswith(word)]
    return sorted(names, key=lambda k: int(k.rsplit("_", 1)[1]))


def _hyper(p, n):
    w, b = p["w"], p["b"]
    return {"norm": p["norm"],
            "w_pre": w[:, :n], "w_post": w[:, n:2 * n], "w_res": w[:, 2 * n:],
            "b_pre": b[:n], "b_post": b[n:2 * n],
            "b_res": b[2 * n:].reshape(n, n),
            "a_pre": p["a"][0], "a_post": p["a"][1], "a_res": p["a"][2]}


def _gate_up(w):
    half = w.shape[-1] // 2
    return {"w_gate": w[..., :half], "w_up": w[..., half:]}


def _block_params(b, bias, n):
    a = b["LatentAttention_0"]
    out = {
        "hc_attn": _hyper(b["HyperConnection_0"], n),
        "attn_norm": b["RMSNorm_0"]["weight"],
        "attn": {"wq_a": a["wq_a"], "q_norm": a["q_norm"]["weight"],
                 "wq_b": a["wq_b"], "wkv_a": a["wkv_a"],
                 "kv_norm": a["kv_norm"]["weight"], "wkv_b": a["wkv_b"],
                 "wo": a["wo"]},
        "hc_ffn": _hyper(b["HyperConnection_1"], n),
        "ffn_norm": b["RMSNorm_1"]["weight"],
    }
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
         "layers": [{"hc_attn": H, "attn_norm": [D], "attn": {wq_a, q_norm,
                     wq_b, wkv_a, kv_norm, wkv_b, wo},
                     "hc_ffn": H, "ffn_norm": [D],
                     "mlp": {w_gate, w_up, w_down}            (dense layer)
                     | "moe": {router [D, E], bias [E], shared: {...},
                               experts: {w_gate [held, D, F], w_up, w_down}}}],
         "mtp": {emb_norm, h_norm, w_eh [2D, D], layer: {...}, final_norm}}

    with ``H = {norm [nC], w_pre [nC, n], w_post [nC, n], w_res [nC, n*n],
    b_pre, b_post, b_res [n, n], a_pre, a_post, a_res}``. The program fuses
    the three hyper-connection maps into one ``w`` (columns pre, post, res)
    and gate, up into ``w_gate_up``; blocks are ``[Checkpoint]Block_<i>``."""
    n = int(config["hc_mult"])
    E = int(config["router_experts"])
    router_state = router_state or {}

    def bias_of(*path):
        node = router_state
        for key in path:
            node = node.get(key, {})
        return node.get("bias", jnp.zeros((E,), jnp.float32))

    out = {"embed": params["embed"],
           "layers": [_block_params(params[name],
                                    bias_of(name, "MoEFeedForward_0"), n)
                      for name in _numbered(params, "Block")],
           "final_norm": params["RMSNorm_0"]["weight"],
           "lm_head": params["w_lm_head"]}
    if "mtp" in params:
        m = params["mtp"]
        (name,) = _numbered(m, "Block")
        out["mtp"] = {"emb_norm": m["emb_norm"]["weight"],
                      "h_norm": m["h_norm"]["weight"], "w_eh": m["w_eh"],
                      "layer": _block_params(
                          m[name], bias_of("mtp", name, "MoEFeedForward_0"), n),
                      "final_norm": m["final_norm"]["weight"]}
    return out
