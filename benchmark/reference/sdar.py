"""SDAR-30B-A3B-Chat (``model_type`` ``sdar_moe``): forward pass and
block-diffusion loss, one sequence at a time.

Plain ``jax.numpy`` in float32, every matrix product at ``highest`` precision
(on a TPU a float32 product is otherwise rounded to bfloat16 passes), no
kernels, no batching, nothing from ``fedml_tpu``. The catalog gives the
model's ``config.json`` and two tags ("mixture of experts", "generation by
diffusion over blocks"); the block is Qwen3-MoE's, which ``sdar_moe`` keeps,
and the objective is BD3-LM's (arXiv:2503.09573), which SDAR
(arXiv:2510.06303) follows. What had to be assumed is marked *assumed* and
listed under ``assumed`` in the configuration file.

**Block.** ``h = x + Attn(RMSNorm(x))``, ``y = h + MoE(RMSNorm(h))``; after
the last block the final RMSNorm and the untied head.

**Attention.** ``q = x W_q`` (hidden x heads x head_dim), ``k = x W_k``,
``v = x W_v`` (hidden x kv_heads x head_dim), no bias; ``head_dim`` (128) is
a key of its own, so heads x head_dim (4,096) is not the hidden size (2,048).
Every q head and every k head is RMS-normalised over its ``head_dim`` entries
with a learned weight of ``head_dim`` shared by the heads (``q_norm``,
``k_norm``; *assumed*: no config key says so, Qwen3's block has them), then
rotated: half-split pairs ``(i, i + head_dim / 2)`` at ``rope_theta``
(*assumed* convention: the program's ``apply_rotary``). Scores ``q . k /
sqrt(head_dim)`` under the mask below, softmax, values, ``W_o``. Query head
``h`` reads key / value head ``h // (heads / kv_heads)``.

**Expert layer.** ``p = softmax(x W_r)`` over all ``router_experts`` (128) in
float32; the ``num_experts_per_tok`` (8) largest; ``norm_topk_prob``: gates
``p_e / sum of the chosen p``; ``MoE(x) = sum over the chosen of gate_e
W_down,e (silu(W_gate,e x) * W_up,e x)``; no shared expert. This chip holds
``num_experts`` experts from ``expert_offset``; what the absent experts would
have added is left out (model-configs guide, section 4), and that partial
result goes on to the next layer. The Switch load-balancing loss
``E sum_e f_e P_e`` (``f_e``: the share of the ``k x tokens`` assignments that
chose ``e``; ``P_e``: the mean of ``p_e``) over all 128 outputs and over all
the rows the layer sees, weighted ``aux_weight`` (*assumed* 0.001).

**Objective** (BD3-LM). A sequence ``x_0`` of ``L`` tokens in blocks of ``B``
(*assumed* 4); block ``b`` draws ``t_b`` and each of its tokens is replaced by
the mask token with probability ``t_b``: ``x_t``. The draw (``x_t``,
``masked``, ``weight = 1 / t_blk``) is *data* here: the caller makes it. The
model reads the ``2L`` rows ``[x_t ; x_0]`` at positions ``[0..L-1 ;
0..L-1]``. With ``n(i) = i < L`` and ``blk(i) = floor((i mod L) / B)``, row
``i`` sees row ``j`` iff::

    (n(i) and n(j) and blk(i) == blk(j))
    or (n(i) and not n(j) and blk(j) < blk(i))
    or (not n(i) and not n(j) and blk(j) <= blk(i))

The head reads the noised half; the loss of the sequence is ``(1 / L) sum
over masked i of weight_i CE(logits_i, x_0[i])``, at the position itself (no
shift; *assumed*, as BD3-LM's code does).

**Departures from the published description.** (1) Attention runs one block
of 512 queries at a time so the ``[heads, 2L, 2L]`` scores are never whole in
memory; the mask of each block is built from the three clauses above. (2) The
expert layer computes every held expert over every row and weighs the result
by the gate (zero where the expert was not chosen): the same sum, no sort, no
dispatch. (3) The held share: experts held elsewhere add nothing. Nothing
else departs.

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


def rotary(x, positions, theta):
    """x: [R, H, d]; rotates the pair (x[..., i], x[..., i + d/2]) by
    ``positions * theta^(-2i/d)``."""
    d = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


# ---------------------------------------------------------------------------
# the mask and the attention under it
# ---------------------------------------------------------------------------


def block_diffusion_mask(rows, cols, L, B):
    """Boolean [len(rows), len(cols)]: may row ``rows[a]`` of the ``2L`` see
    row ``cols[b]``? The three clauses of the module's docstring."""
    i, j = rows[:, None], cols[None, :]
    n_i, n_j = i < L, j < L
    blk_i, blk_j = (i % L) // B, (j % L) // B
    return ((n_i & n_j & (blk_i == blk_j))
            | (n_i & ~n_j & (blk_j < blk_i))
            | (~n_i & ~n_j & (blk_j <= blk_i)))


def masked_attention(q, k, v, scale, L, B, q_block=512):
    """q: [2L, H, d]; k, v: [2L, Hkv, d] -> [2L, H, d]."""
    R, H, _ = q.shape
    rep = H // k.shape[1]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    q, k, v = _round(q), _round(k), _round(v)
    cols = jnp.arange(R)
    out = []
    for start in range(0, R, q_block):
        qb = q[start:start + q_block]
        scores = jnp.einsum("qhd,khd->hqk", qb, k, precision=HIGHEST) * scale
        see = block_diffusion_mask(
            jnp.arange(start, start + qb.shape[0]), cols, L, B)
        probs = _round(jax.nn.softmax(
            jnp.where(see[None], scores, -jnp.inf), axis=-1))
        out.append(jnp.einsum("hqk,khd->qhd", probs, v, precision=HIGHEST))
    return jnp.concatenate(out, axis=0)


def attention(p, x, positions, config, L):
    R = x.shape[0]
    H, Hkv = int(config["num_attention_heads"]), int(config["num_key_value_heads"])
    d, eps = int(config["head_dim"]), float(config["rms_norm_eps"])
    theta = float(config["rope_theta"])
    q = rms_norm(_mm(x, p["wq"]).reshape(R, H, d), p["q_norm"], eps)
    k = rms_norm(_mm(x, p["wk"]).reshape(R, Hkv, d), p["k_norm"], eps)
    v = _mm(x, p["wv"]).reshape(R, Hkv, d)
    q, k = rotary(q, positions, theta), rotary(k, positions, theta)
    out = masked_attention(q, k, v, d ** -0.5, L, int(config["block_length"]))
    return _mm(out.reshape(R, H * d), p["wo"])


# ---------------------------------------------------------------------------
# expert layer
# ---------------------------------------------------------------------------


def route(p, x, config):
    """-> (selected [R, k] expert ids, gates [R, k], margin [R], counts [E],
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
    for e in range(int(config["num_experts"])):
        g = jnp.where(selected == lo + e, gates, 0.0).sum(-1)
        y = y + g[:, None] * swiglu(
            x, p["experts"]["w_gate"][e], p["experts"]["w_up"][e],
            p["experts"]["w_down"][e])
    return y, margin, counts, prob_sum


def aux_loss(counts, prob_sum, rows, config):
    """The Switch load-balancing loss of one expert layer over ``rows`` rows
    in all: ``E sum_e f_e P_e`` from the summed ``counts`` [E] (assignments
    to each expert) and ``prob_sum`` [E] (the router's probabilities summed
    over the rows). 1 for a balanced router."""
    E, k = int(config["router_experts"]), int(config["num_experts_per_tok"])
    return E * jnp.sum(counts / (k * rows) * prob_sum / rows)


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------


def hidden_states(params, x_t, x_0, config):
    """x_t, x_0: [L] int -> (the noised half's final-norm hidden states
    [L, D], its smallest routing margin over the expert layers [L], every
    layer's ``counts`` and ``prob_sum`` over the 2L rows [layers, E])."""
    L = x_0.shape[0]
    eps = float(config["rms_norm_eps"])
    rows = jnp.concatenate([x_t, x_0])
    positions = jnp.concatenate([jnp.arange(L), jnp.arange(L)])
    x = params["embed"][rows]
    margin = jnp.full((2 * L,), jnp.inf)
    counts, prob_sums = [], []
    for layer in params["layers"]:
        x = x + attention(layer["attn"], rms_norm(x, layer["attn_norm"], eps),
                          positions, config, L)
        y, m, c, s = expert_layer(layer["moe"],
                                  rms_norm(x, layer["ffn_norm"], eps), config)
        x = x + y
        margin = jnp.minimum(margin, m)
        counts.append(c)
        prob_sums.append(s)
    return (rms_norm(x[:L], params["final_norm"], eps), margin[:L],
            jnp.stack(counts), jnp.stack(prob_sums))


def weighted_nll_sum(logits, x_0, masked, weight):
    """``sum over masked i of weight_i CE(logits_i, x_0[i])``: position ``i``
    of the noised half answers for token ``i`` itself."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, x_0[:, None], axis=-1)[:, 0]
    return jnp.sum(jnp.where(masked, weight, 0.0) * nll)


def logits_and_loss(params, x_t, x_0, masked, weight, config):
    """Noised-half logits [L, V]; ``sum over masked i of weight_i CE(logits_i,
    x_0[i])`` (the caller divides by ``L``); margins [L]; the routers'
    ``counts`` and ``prob_sum`` [layers, E]."""
    hidden, margin, counts, prob_sums = hidden_states(params, x_t, x_0, config)
    logits = _mm(hidden, params["lm_head"])
    loss_sum = weighted_nll_sum(logits, x_0, masked, weight)
    return logits, loss_sum, margin, counts, prob_sums


def loss_sum_and_tail_logits(params, x_t, x_0, masked, weight, config,
                             tail: int, head: int = 0):
    """What the benchmark's job compares: the weighted summed loss of one
    sequence, the logits of the noised half's first ``head`` and last
    ``tail`` positions (in that order) with their routing margins, and what
    the auxiliary loss is made of. The first positions see few keys (a row of
    block ``b`` sees ``B + b B``), so a wrong clause of the mask moves them by
    far more than it moves the last, which see thousands."""
    logits, loss_sum, margin, counts, prob_sums = logits_and_loss(
        params, x_t, x_0, masked, weight, config)
    return (loss_sum, jnp.concatenate([logits[:head], logits[-tail:]]),
            jnp.concatenate([margin[:head], margin[-tail:]]), counts, prob_sums)


def training_loss(params, x_t, x_0, masked, weight, config):
    """The step's loss of a batch [N, L]: the mean over the sequences of
    ``loss_sum / L`` plus ``aux_weight`` times every expert layer's
    load-balancing loss over all the batch's ``N x 2L`` rows."""
    N, L = x_0.shape
    total, counts, prob_sums = 0.0, 0.0, 0.0
    for n in range(N):
        _, loss_sum, _, c, s = logits_and_loss(
            params, x_t[n], x_0[n], masked[n], weight[n], config)
        total, counts, prob_sums = total + loss_sum, counts + c, prob_sums + s
    aux = sum(aux_loss(c, s, N * 2 * L, config)
              for c, s in zip(counts, prob_sums))
    return total / (N * L) + float(config["aux_weight"]) * aux


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
    up into ``w_gate_up``; blocks are ``[Checkpoint]Block_<i>``. The softmax
    router carries no state, so ``router_state`` is not read."""
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
