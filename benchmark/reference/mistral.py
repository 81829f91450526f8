"""Mistral-7B-v0.1 forward pass and next-token loss, one sequence at a time.

Follows the published architecture (Jiang et al. 2023, arXiv:2310.06825, and
the Hugging Face ``MistralForCausalLM``): token embedding, ``L`` pre-norm
blocks of grouped-query attention with rotary positions (the two-halves
``rotate_half`` convention) and a SwiGLU feed-forward, a final RMSNorm and an
untied output head. Sliding-window attention is causal attention restricted
to the last ``sliding_window`` keys; the benchmark's cells run sequences no
longer than the window, where the two coincide, and ``forward`` refuses
longer ones rather than approximate.

Parameters are a plain dict::

    {"embed": [V, D], "final_norm": [D], "lm_head": [D, V],
     "layers": [{"attn_norm": [D], "wq": [D, H*hd], "wk": [D, Hkv*hd],
                 "wv": [D, Hkv*hd], "wo": [H*hd, D], "ffn_norm": [D],
                 "w_gate": [D, F], "w_up": [D, F], "w_down": [F, D]}, ...]}

Everything is float32 and every matmul runs at ``highest`` precision (on a
TPU a float32 matmul is otherwise rounded to bfloat16 passes).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def rotary(x, theta):
    """x: [L, H, hd]. Rotates the pair (x[..., i], x[..., i + hd/2]) by
    ``position * theta ** (-2i / hd)``."""
    L, _, hd = x.shape
    inv_freq = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    angles = jnp.arange(L, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def causal_attention(q, k, v, q_block=512):
    """q: [L, H, hd]; k, v: [L, Hkv, hd] -> [L, H, hd]. Query head ``h`` reads
    key/value head ``h // (H / Hkv)``. Computed one block of queries at a time
    so the [H, L, L] score tensor is never whole in memory."""
    L, H, hd = q.shape
    rep = H // k.shape[1]
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    out = []
    for start in range(0, L, q_block):
        qb = q[start:start + q_block]
        scores = jnp.einsum("qhd,khd->hqk", qb, k, precision=HIGHEST) / jnp.sqrt(
            jnp.float32(hd))
        q_pos = jnp.arange(start, start + qb.shape[0])[:, None]
        visible = jnp.arange(L)[None, :] <= q_pos
        scores = jnp.where(visible[None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        out.append(jnp.einsum("hqk,khd->qhd", probs, v, precision=HIGHEST))
    return jnp.concatenate(out, axis=0)


def hidden_states(params, tokens, config):
    """tokens: [L] int -> final-norm hidden states [L, D]."""
    L = tokens.shape[0]
    window = config.get("sliding_window")
    if window is not None and L > int(window):
        raise ValueError(
            f"sequence of {L} exceeds sliding_window {window}: this reference "
            "implements the window only where it equals causal attention")
    H = int(config["num_attention_heads"])
    Hkv = int(config["num_key_value_heads"])
    hd = int(config.get("head_dim") or int(config["hidden_size"]) // H)
    eps = float(config["rms_norm_eps"])
    theta = float(config["rope_theta"])
    x = params["embed"][tokens]
    for layer in params["layers"]:
        h = rms_norm(x, layer["attn_norm"], eps)
        q = rotary(_mm(h, layer["wq"]).reshape(L, H, hd), theta)
        k = rotary(_mm(h, layer["wk"]).reshape(L, Hkv, hd), theta)
        v = _mm(h, layer["wv"]).reshape(L, Hkv, hd)
        x = x + _mm(causal_attention(q, k, v).reshape(L, H * hd), layer["wo"])
        h = rms_norm(x, layer["ffn_norm"], eps)
        x = x + _mm(jax.nn.silu(_mm(h, layer["w_gate"])) * _mm(h, layer["w_up"]),
                    layer["w_down"])
    return rms_norm(x, params["final_norm"], eps)


def loss_sum_and_tail_logits(params, tokens, config, tail: int):
    """Summed next-token cross-entropy over the ``L - 1`` predicted positions
    of one sequence, and the logits of its last ``tail`` positions."""
    hidden = hidden_states(params, tokens, config)
    logits = _mm(hidden, params["lm_head"])
    logp = jax.nn.log_softmax(logits[:-1], axis=-1)
    nll = -jnp.take_along_axis(logp, tokens[1:, None], axis=-1)[:, 0]
    return nll.sum(), logits[-tail:]
