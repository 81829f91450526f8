"""The state-space recurrence of a Mamba-2 mixer (SSD, arXiv:2405.21060): a
scalar decay a head, in its chunkwise-parallel form, as plain XLA.

**The recurrence** (the definition, :func:`ssd_recurrence`;
``benchmark/reference/nemotron_h.py`` scans it token by token in its own
code). Per head ``h`` of ``H``, a state ``S`` in ``R^{P x N}``, zero at the
start of a sequence; for token ``t`` with an input ``x_t`` in ``R^P``, a step
size ``dt_t > 0``, the head's ``A < 0``, and its group's ``B_t``, ``C_t`` in
``R^N`` (``H / G`` consecutive heads read group ``g``)::

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t
    y_t = S_t C_t

**The chunked form** (:func:`ssd_chunked`, what a layer runs). Over a chunk of
``Q`` tokens with ``a_t`` the running sum of ``dt A`` inside the chunk
(``a_t <= 0``, falling) and ``S`` the state that enters it::

    Y  = ((C B^T) o exp(a_t - a_s) [s <= t]) (dt x)     inside the chunk
       + exp(a_t) C_t S                                 what came before
    S' = exp(a_Q) S + sum_s exp(a_Q - a_s) dt_s x_s (x) B_s

Every exponent is at most 0: the pairs ``s > t`` are masked before the
exponential, not after it. The chunks' own states are one batched product,
the carry over the chunks a ``lax.scan`` of one multiply-add a chunk. A
sequence that is no multiple of ``Q`` is padded with steps of size 0, which
neither decay the state nor write to it, and cut.

Step sizes, running sums, decays and the carried state in float32; the
products' inputs in the dtype the caller computes in (bfloat16 in training),
accumulated in float32. The backward is autodiff's of this form; a kernel is
ROADMAP queue 1's.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# the one form there is; ``cheetah_init.ssd.path`` says it, as
# ``kda.scan_path`` does for the KDA layers
SSD_PATH = "xla"


def ssd_recurrence(x, dt, A, B, C, S0=None):
    """Token by token, float32. x: [b, L, H, P]; dt: [b, L, H] (after the
    softplus); A: [H]; B, C: [b, L, G, N] -> (y [b, L, H, P], the last state
    [b, H, P, N])."""
    b, L, H, P = x.shape
    G, N = B.shape[-2:]
    f32 = jnp.float32
    x, dt, A, B, C = (v.astype(f32) for v in (x, dt, A, B, C))
    B, C = (jnp.repeat(v, H // G, axis=2) for v in (B, C))       # [b, L, H, N]

    def step(S, inputs):
        x_t, dt_t, B_t, C_t = inputs
        S = (jnp.exp(dt_t * A)[..., None, None] * S
             + (dt_t[..., None] * x_t)[..., None] * B_t[..., None, :])
        return S, jnp.einsum("bhpn,bhn->bhp", S, C_t,
                             precision=jax.lax.Precision.HIGHEST)

    S0 = jnp.zeros((b, H, P, N), f32) if S0 is None else S0.astype(f32)
    S, y = jax.lax.scan(step, S0, tuple(
        jnp.moveaxis(v, 1, 0) for v in (x, dt, B, C)))
    return jnp.moveaxis(y, 0, 1), S


def ssd_chunked(x, dt, A, B, C, chunk: int, dtype=None, S0=None):
    """The same in chunks of ``chunk`` tokens. ``dtype``: the products'
    inputs (None: ``x``'s); ``dt`` and ``A`` float32. Returns (y [b, L, H, P],
    the last state [b, H, P, N]), both float32 as accumulated."""
    b, L, H, P = x.shape
    G, N = B.shape[-2:]
    f32 = jnp.float32
    dtype = dtype or x.dtype
    Q = min(chunk, L)
    pad = (-L) % Q
    if pad:
        x, dt, B, C = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
                       for v in (x, dt, B, C))
    n = (L + pad) // Q

    def chunks(v):  # [b, L, ...] -> [b, n, Q, ...]
        return v.reshape((b, n, Q) + v.shape[2:])

    dt = chunks(dt.astype(f32))                                  # [b, n, Q, H]
    a = jnp.cumsum(dt * A.astype(f32), axis=2)                   # <= 0, falling
    # the step size rides on the input, so both products below read dt x
    xs = (chunks(x).astype(f32) * dt[..., None]).astype(dtype)  # [b, n, Q, H, P]
    Bc, Cc = chunks(B).astype(dtype), chunks(C).astype(dtype)    # [b, n, Q, G, N]
    heads = (b, n, Q, G, H // G)

    # inside a chunk: token t reads the tokens s <= t of its chunk
    scores = jnp.einsum("bntgk,bnsgk->bngts", Cc, Bc,
                        preferred_element_type=f32)             # [b, n, G, Q, Q]
    a_h = jnp.moveaxis(a, 2, -1)                                 # [b, n, H, Q]
    gap = a_h[..., :, None] - a_h[..., None, :]                  # a_t - a_s
    seen = jnp.tril(jnp.ones((Q, Q), jnp.bool_))
    decay = jnp.exp(jnp.where(seen, gap, -jnp.inf))              # [b, n, H, Q, Q]
    pairs = (scores[:, :, :, None] * decay.reshape(b, n, G, H // G, Q, Q)
             ).astype(dtype)
    y = jnp.einsum("bngrts,bnsgrp->bntgrp", pairs, xs.reshape(heads + (P,)),
                   preferred_element_type=f32)

    # each chunk's own state at its end, then the carry over the chunks
    to_end = jnp.exp(a[:, :, -1:] - a)                           # [b, n, Q, H]
    own = jnp.einsum(
        "bnsgrp,bnsgk->bngrpk",
        (xs.astype(f32) * to_end[..., None]).astype(dtype).reshape(heads + (P,)),
        Bc, preferred_element_type=f32).reshape(b, n, H, P, N)
    whole = jnp.exp(a[:, :, -1])                                 # [b, n, H]

    def carry(S, inputs):
        d, own_c = inputs
        return d[..., None, None] * S + own_c, S                 # emits S before

    S0 = jnp.zeros((b, H, P, N), f32) if S0 is None else S0.astype(f32)
    S, before = jax.lax.scan(
        carry, S0, (jnp.moveaxis(whole, 1, 0), jnp.moveaxis(own, 1, 0)))
    before = jnp.moveaxis(before, 0, 1)                          # [b, n, H, P, N]

    # what came before the chunk, decayed down to token t
    across = jnp.einsum("bntgk,bngrpk->bntgrp", Cc,
                        before.astype(dtype).reshape(b, n, G, H // G, P, N),
                        preferred_element_type=f32)
    y = y + across * jnp.exp(a).reshape(heads)[..., None]
    return y.reshape(b, n * Q, H, P)[:, :L], S
