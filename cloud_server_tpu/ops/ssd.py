"""The Mamba-2 recurrence (state-space duality) in the two forms the paged
server runs, which must agree.

For head i with rate A_i (a trained model's is negative: a decay; neither
form below needs the sign), step dt_i[t] >= 0, input x_i[t] (P,)
and the group's B[t], C[t] (N,):

    S_i[t] = exp(dt_i[t] A_i) S_i[t-1] + dt_i[t] * outer(x_i[t], B[t])
    y_i[t] = S_i[t] C[t]

`ssd_chunked` runs a row's W tokens in chunks of `chunk`: inside a chunk
the outputs are one masked matrix product over the chunk's tokens (the
"dual" quadratic form), between chunks the state is handed on, so a row
enters with a state and leaves with one. `ssd_step` is the recurrence
itself for one token a row. Both hold the state in float32 and every
product with it at the highest matmul precision. A position with dt = 0
neither decays the state nor adds to it: that is how a row's padding past
its real width is kept out (`models/mixer.py` masks dt).

The `D x` skip term, the gate and the norm are the mixer's, not the
scan's.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

_HI = lax.Precision.HIGHEST
F32 = jnp.float32


def ssd_chunked(x, dt, a, b, c, state, chunk: int):
    """x (B, W, H, P); dt (B, W, H) float32, 0 at padding; a (H,) float32;
    b, c (B, W, G, N), head h reading group h // (H / G); state
    (B, H, P, N) float32. Returns (y (B, W, H, P) float32, state'
    (B, H, P, N) float32): state' is `state` advanced by the positions
    whose dt is not 0."""
    bsz, w, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    r = h // g
    q = min(chunk, w)
    pad = -w % q
    if pad:
        x, b, c = (jnp.pad(t, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for t in (x, b, c))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
    nc = (w + pad) // q
    x = x.reshape(bsz, nc, q, g, r, p)
    b = b.reshape(bsz, nc, q, g, n)
    c = c.reshape(bsz, nc, q, g, n)
    dt = dt.astype(F32).reshape(bsz, nc, q, g, r)
    # log-decay up to and including each position of its chunk
    cum = jnp.cumsum(dt * a.reshape(g, r), axis=2)
    total = cum[:, :, -1]                                  # (B, nc, G, R)
    # inside a chunk: y[i] += sum_{j <= i} exp(cum[i] - cum[j]) (C[i].B[j])
    #                          dt[j] x[j]
    seg = cum[:, :, :, None] - cum[:, :, None, :]          # (B,nc,i,j,G,R)
    tri = jnp.tril(jnp.ones((q, q), bool))[None, None, :, :, None, None]
    decay = jnp.exp(jnp.where(tri, seg, -jnp.inf))
    cb = jnp.einsum("bcign,bcjgn->bcijg", c, b,
                    preferred_element_type=F32)
    mix = decay * cb[..., None] * dt[:, :, None]           # (B,nc,i,j,G,R)
    y = jnp.einsum("bcijgr,bcjgrp->bcigrp", mix.astype(x.dtype), x,
                   preferred_element_type=F32)
    # what a chunk's own tokens leave in the state at its end
    to_end = jnp.exp(total[:, :, None] - cum) * dt         # (B,nc,q,G,R)
    local = jnp.einsum("bcjgr,bcjgrp,bcjgn->bcgrpn", to_end, x.astype(F32),
                       b.astype(F32), precision=_HI)
    # between chunks: the state each chunk enters with
    s = state.astype(F32).reshape(bsz, g, r, p, n)
    enters = []
    for k in range(nc):
        enters.append(s)
        s = jnp.exp(total[:, k])[..., None, None] * s + local[:, k]
    enters = jnp.stack(enters, axis=1)                     # (B,nc,G,R,P,N)
    y = y + jnp.exp(cum)[..., None] * jnp.einsum(
        "bcign,bcgrpn->bcigrp", c.astype(F32), enters, precision=_HI)
    return (y.reshape(bsz, nc * q, h, p)[:, :w],
            s.reshape(bsz, h, p, n))


def ssd_step(x, dt, a, b, c, state, live=None):
    """One token a row: x (B, H, P); dt (B, H) float32; a (H,); b, c
    (B, G, N); state (B, H, P, N) float32; `live` (B,) bool: a row that is
    not live keeps its state bit for bit (its y is then of the state as it
    was). Returns (y (B, H, P) float32, state'). All elementwise in
    float32: the state is read once and written once."""
    h, g = x.shape[1], b.shape[1]
    bh = jnp.repeat(b.astype(F32), h // g, axis=1)         # (B, H, N)
    ch = jnp.repeat(c.astype(F32), h // g, axis=1)
    dt = dt.astype(F32)
    new = (jnp.exp(dt * a)[:, :, None, None] * state
           + (dt[:, :, None] * x.astype(F32))[..., None]
           * bh[:, :, None, :])
    if live is not None:
        new = jnp.where(live[:, None, None, None], new, state)
    return jnp.sum(new * ch[:, :, None, :], axis=-1), new
