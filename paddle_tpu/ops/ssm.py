"""The Mamba-2 state-space mixer's inner parts, in plain ``jax.numpy`` /
``lax``: the causal depthwise convolution with its tail, the selective
state-space recurrence in three forms, the gated group norm.

The recurrence, a head h of ``P`` channels and a state of ``N`` columns
(``x_t [P]``, ``dt_t > 0``, ``A < 0``, ``B_t, C_t [N]`` of the head's
group, ``H [P, N]`` float32)::

    H_t = exp(dt_t A) H_{t-1} + dt_t (x_t (x) B_t)
    y_t = H_t C_t

- ``ssd_sequential``: that, position by position (``lax.scan``). The
  form the other two are held to in the tests.
- ``ssd_chunked``: a prompt at once (the SSD form): within a chunk of
  ``L`` positions the ``[L, L]`` decay-weighted ``C B^T`` product a
  head, across chunks the carried ``H``; a ``lax.scan`` over chunks,
  not positions.
- ``ssd_step``: one position a row on the carried state, a decode
  round's form: one pass over ``H`` (read, update, reduce against C).

A position whose ``dt`` is 0 changes nothing: ``exp(0) = 1`` keeps H and
``0 * (x (x) B)`` adds nothing. A right-padded prompt's pad positions
are given ``dt = 0`` by the caller, so the state handed on is the state
after the LAST REAL position. Everything inside is float32 at
``Precision.HIGHEST``: the products here are a thousandth of a layer's
work and the state is carried over thousands of steps.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["causal_conv", "conv_step", "conv_tail", "ssd_sequential",
           "ssd_chunked", "ssd_step", "gated_group_norm"]

_HI = lax.Precision.HIGHEST


def causal_conv(c, w, b):
    """Depthwise causal convolution over positions, zeros before
    position 0, then silu. c [B, S, D]; w [D, K]; b [D] or None.
    ``out_t = silu(b + sum_j w[:, j] c_{t-(K-1)+j})``, float32 inside."""
    K, S = w.shape[1], c.shape[1]
    cp = jnp.pad(c.astype(jnp.float32), ((0, 0), (K - 1, 0), (0, 0)))
    wf = w.astype(jnp.float32)
    out = sum(cp[:, j:j + S] * wf[:, j] for j in range(K))
    if b is not None:
        out = out + b.astype(jnp.float32)
    return jax.nn.silu(out).astype(c.dtype)


def conv_tail(c, lengths, K: int):
    """The last ``K - 1`` REAL inputs of each row, ``[B, K - 1, D]``:
    positions ``lengths[b] - (K - 1) .. lengths[b] - 1``, zeros where
    that lies before position 0. ``lengths`` None: every position is
    real."""
    B, S, _ = c.shape
    cp = jnp.pad(c, ((0, 0), (K - 1, 0), (0, 0)))
    if lengths is None:
        return cp[:, S:]
    at = jnp.asarray(lengths, jnp.int32)[:, None] \
        + jnp.arange(K - 1, dtype=jnp.int32)[None]          # [B, K - 1]
    return jnp.take_along_axis(cp, at[:, :, None], axis=1)


def conv_step(tail, c, w, b):
    """One position a row: tail [B, (K - 1) * D] (the K - 1 inputs before
    it, oldest first, side by side: a ``[B, K - 1, D]`` array tiles
    badly and XLA answers with layout copies), c [B, D]. Returns (out
    [B, D], the new tail)."""
    D, K = w.shape
    wf = w.astype(jnp.float32)
    win = [tail[:, j * D:(j + 1) * D] for j in range(K - 1)] \
        + [c.astype(tail.dtype)]
    out = sum(win[j].astype(jnp.float32) * wf[:, j] for j in range(K))
    if b is not None:
        out = out + b.astype(jnp.float32)
    return jax.nn.silu(out).astype(c.dtype), jnp.concatenate(win[1:], 1)


def _by_head(m, heads: int):
    """[..., G, N] of the groups -> [..., heads, N]: head h reads group
    ``h // (heads // G)``."""
    return jnp.repeat(m, heads // m.shape[-2], axis=-2)


def ssd_sequential(x, dt, A, Bm, Cm, H0=None):
    """x [B, S, nh, P]; dt [B, S, nh] (positive, 0 on a pad position); A
    [nh]; Bm, Cm [B, S, G, N]. Returns (y [B, S, nh, P] float32, the
    state after the last position [B, nh, P, N] float32)."""
    B, S, nh, P = x.shape
    f = jnp.float32
    H = jnp.zeros((B, nh, P, Bm.shape[-1]), f) if H0 is None else H0
    xs = (x.astype(f), dt.astype(f), _by_head(Bm.astype(f), nh),
          _by_head(Cm.astype(f), nh))

    def step(H, t):
        xt, dtt, Bt, Ct = t
        y, H = ssd_step(H, xt, dtt, A, Bt, Ct)
        return H, y

    H, y = lax.scan(step, H, tuple(jnp.swapaxes(a, 0, 1) for a in xs))
    return jnp.swapaxes(y, 0, 1), H


def ssd_step(H, x, dt, A, Bm, Cm):
    """One position a row on the carried state. H [B, nh, P, N] float32;
    x [B, nh, P]; dt [B, nh]; Bm, Cm [B, G, N] (or [B, nh, N]). One pass
    over H: the update and the product against C are elementwise and a
    reduce, so XLA fuses them."""
    nh = x.shape[1]
    f = jnp.float32
    dt = dt.astype(f)
    a = jnp.exp(dt * A.astype(f))                               # [B, nh]
    Bh, Ch = _by_head(Bm.astype(f), nh), _by_head(Cm.astype(f), nh)
    H = a[:, :, None, None] * H + (dt[:, :, None] * x.astype(f)
                                   )[..., None] * Bh[:, :, None, :]
    return (H * Ch[:, :, None, :]).sum(-1), H


def ssd_chunked(x, dt, A, Bm, Cm, chunk: int, H0=None
                ) -> Tuple[jax.Array, jax.Array]:
    """``ssd_sequential`` a chunk of ``chunk`` positions at a time. A
    length that is no multiple of the chunk is padded with ``dt = 0``
    positions, which change nothing."""
    B, S, nh, P = x.shape
    G, N = Bm.shape[-2:]
    f = jnp.float32
    L = min(int(chunk), S)
    pad = -S % L
    if pad:
        x, dt, Bm, Cm = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),)
                                 * (a.ndim - 2)) for a in (x, dt, Bm, Cm))
    nc = (S + pad) // L
    x = x.astype(f).reshape(B, nc, L, nh, P)
    dt = dt.astype(f).reshape(B, nc, L, nh)
    Bc = Bm.astype(f).reshape(B, nc, L, G, N)
    Cc = Cm.astype(f).reshape(B, nc, L, G, N)
    la = dt * A.astype(f)                      # log decay a position, <= 0
    cum = jnp.cumsum(la, axis=2)               # inclusive, inside a chunk
    xd = x * dt[..., None]                     # dt_s x_s
    # inside a chunk: y_t = sum_{s<=t} exp(cum_t - cum_s) (C_t.B_s) xd_s
    cb = jnp.einsum("bclgn,bcsgn->bcgls", Cc, Bc, precision=_HI)
    ch = jnp.swapaxes(cum, 2, 3)                           # [B, nc, nh, L]
    seg = ch[..., :, None] - ch[..., None, :]              # [B,nc,nh,t,s]
    tri = jnp.tril(jnp.ones((L, L), bool))
    decay = jnp.where(tri, jnp.exp(jnp.where(tri, seg, 0.0)), 0.0)
    w = jnp.repeat(cb, nh // G, axis=2) * decay
    y = jnp.einsum("bchls,bcshp->bclhp", w, xd, precision=_HI)
    # what a chunk adds to the state by its end, and its whole decay
    to_end = jnp.exp(cum[:, :, -1:, :] - cum)              # [B,nc,L,nh]
    add = jnp.einsum("bclh,bclhp,bclhn->bchpn", to_end, xd,
                     _by_head(Bc, nh), precision=_HI)
    whole = jnp.exp(cum[:, :, -1, :])                      # [B, nc, nh]

    def carry(H, c):
        add_c, whole_c = c
        return whole_c[:, :, None, None] * H + add_c, H    # H ENTERING c

    H = jnp.zeros((B, nh, P, N), f) if H0 is None else H0.astype(f)
    H, entering = lax.scan(carry, H, (jnp.swapaxes(add, 0, 1),
                                      jnp.swapaxes(whole, 0, 1)))
    entering = jnp.swapaxes(entering, 0, 1)                # [B,nc,nh,P,N]
    y = y + jnp.einsum("bclhn,bchpn,bclh->bclhp", _by_head(Cc, nh),
                       entering, jnp.exp(cum), precision=_HI)
    return y.reshape(B, nc * L, nh, P)[:, :S], H


def gated_group_norm(y, z, w, groups: int, eps: float):
    """``g = y * silu(z)``, then each of ``groups`` equal runs of the last
    dim divided by its own root-mean-square, times ``w``. Float32
    inside; returns ``z``'s type."""
    f = jnp.float32
    g = y.astype(f) * jax.nn.silu(z.astype(f))
    shape = g.shape
    g = g.reshape(shape[:-1] + (groups, shape[-1] // groups))
    g = g * lax.rsqrt(jnp.mean(g * g, -1, keepdims=True) + eps)
    return (g.reshape(shape) * w.astype(f)).astype(z.dtype)
