"""Attention over a set of keys that a learned INDEX chooses, per query:
the index scores, the exact selection, and the prefill form.

A layer of this kind keeps beside K and V one small INDEX KEY a position
(``ik``, ``di`` wide). A query at position ``t`` carries ``Hi`` index
queries ``iq`` and a weight a head ``iw``; its score of position ``s`` is

    I[t, s] = sum_j iw[t, j] * relu(iq[t, j] . ik[s]),        s <= t

and it attends to the ``topk`` positions of largest ``I`` alone (ties to
the lower position), to all of them while ``t + 1 <= topk``. The set is
EXACT: ``keep_topk`` finds the k-th largest score of a row by a search
over the bits of its float32 pattern (counting, no sort, no
``approx_max_k``: an approximate set is another result), and where
several scores equal it, the lowest positions among them by a second
search over the bits of the position.

``sparse_causal_attention`` is the prefill form over positions
``0..S-1``. The program does not grow with S squared: rows are grouped
in TIERS ``[a, 2a)`` (the first is ``[0, topk)``, whose rows keep every
earlier key and skip the index), a tier is ONE ``lax.map`` over its
blocks of rows, its index scores and selection run against the keys
``[0, 2a)`` (static), and the attention of a block of rows is a
``lax.fori_loop`` over the key blocks up to its diagonal with a running
maximum and sum, so no ``[H, S, S]`` array exists and the work above the
diagonal is the tier's slack alone (a third, at most).

Products take bf16 operands where the model's type is bf16 and
accumulate in float32; the sum over the index heads, the comparison and
the selection are float32.
"""
from __future__ import annotations

import contextlib
import threading

import jax
import jax.numpy as jnp
from jax import lax

from ..observability import annotate as _annotate

__all__ = ["index_scores", "keep_topk", "sparse_causal_attention",
           "kept_mask", "select_rows", "count_kept", "collect_selection",
           "selection_sink"]

_NEG = -1e30
_selection = threading.local()


@contextlib.contextmanager
def collect_selection():
    """While open on this thread, every selecting layer's PREFILL-form
    forward appends its kept set, ``[B, S, S]`` bool (row t, key s), to
    the list this yields, in layer order: a check's reading of what the
    program's model selected (an S-squared array a layer: not for a
    timed program)."""
    _selection.kept = kept = []
    try:
        yield kept
    finally:
        _selection.kept = None


def selection_sink():
    """The open collection's list (a layer appends to it), else None."""
    return getattr(_selection, "kept", None)


def index_scores(iq, ik, iw, head_block=None):
    """``I`` of the module docstring: iq [B, S, Hi, di] against
    ik [B, M, di] with weights iw [B, S, Hi] -> [B, S, M] float32, no
    mask applied. ``head_block``: sum the index heads that many at a
    time (a divisor of Hi), so that the products of all ``Hi`` heads,
    [B, Hi, S, M] float32, never stand side by side (64 heads x 512 rows
    x 8,192 keys are 1 GiB)."""
    Hi = iq.shape[2]
    if head_block and head_block < Hi:
        n = Hi // head_block
        heads = lambda a: jnp.moveaxis(
            a.reshape(a.shape[:2] + (n, head_block) + a.shape[3:]), 2, 0)

        def some(acc, qw):
            return acc + index_scores(qw[0], ik, qw[1]), None

        return lax.scan(some, jnp.zeros(
            iq.shape[:2] + ik.shape[1:2], jnp.float32),
            (heads(iq), heads(iw)))[0]
    s = jnp.einsum("bqjd,bmd->bjqm", iq, ik,
                   preferred_element_type=jnp.float32)
    w = jnp.swapaxes(iw.astype(jnp.float32), 1, 2)[..., None]
    return jnp.sum(jnp.maximum(s, 0.0) * w, axis=1)


def _sortable(x):
    """float32 -> uint32 whose unsigned order is the floats' order."""
    u = lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    return jnp.where(u >> 31 != 0, ~u, u | jnp.uint32(0x80000000))


def keep_topk(scores, valid, k: int):
    """The ``k`` largest of each row of ``scores [..., M]`` among the
    entries ``valid`` (bool, same shape) allows, ties to the lower
    index; every valid entry where a row has ``k`` or fewer. Returns the
    bool mask. Exact: a row keeps ``min(valid entries, k)``."""
    M = scores.shape[-1]
    if k >= M:
        return valid
    # invalid entries read 0, under every float (-inf reads 0x007fffff)
    key = jnp.where(valid, _sortable(scores), jnp.uint32(0))

    def count(hit):
        return jnp.sum(hit.astype(jnp.int32), axis=-1, keepdims=True)

    def value_bits(i, t):
        # two bits a pass: one read of the keys serves three counts
        shift = (30 - 2 * i).astype(jnp.uint32)
        c1, c2, c3 = (t | (jnp.uint32(n) << shift) for n in (1, 2, 3))
        return jnp.where(
            count(key >= c3) >= k, c3, jnp.where(
                count(key >= c2) >= k, c2, jnp.where(
                    count(key >= c1) >= k, c1, t)))

    # the largest t with k or more keys at or above it: the k-th largest
    # key (0 where the row holds fewer, and then every valid key is over)
    t = lax.fori_loop(0, 16, value_bits,
                      jnp.zeros(scores.shape[:-1] + (1,), jnp.uint32))
    over = key > t
    tie = valid & (key == t)
    need = k - count(over)             # of the ties, the lowest positions
    pos = lax.broadcasted_iota(jnp.int32, scores.shape, scores.ndim - 1)
    bits = max(int(M).bit_length(), 1)

    def first_ties():
        def pos_bit(i, c):
            cand = c | (jnp.int32(1) << (bits - 1 - i))
            return jnp.where(count(tie & (pos < cand)) <= need, cand, c)

        # the largest c with no more than ``need`` ties under it
        c = lax.fori_loop(0, bits, pos_bit, jnp.zeros_like(need))
        return tie & (pos < c)

    # the second search only where some row has more ties than it needs
    ties = lax.cond(jnp.any(count(tie) > need), first_ties, lambda: tie)
    return (over | ties) & valid


def select_rows(iq, iw, index_keys, off, topk: int, scope: str):
    """A decode step's selection: the kept positions [B, S, M] of S new
    rows at ``off[b]..`` against the index keys [B, M, di] of positions
    0..M-1 (a row's gathered pages, or its static cache). ``scope``: the
    layer's, for the index and the selection in the device trace."""
    S, M = iq.shape[1], index_keys.shape[1]
    with _annotate(f"{scope}.index"):
        sc = index_scores(iq, index_keys, iw)
    with _annotate(f"{scope}.select"):
        qpos = off[:, None] + jnp.arange(S, dtype=jnp.int32)[None]
        seen = jnp.arange(M, dtype=jnp.int32)[None, None] \
            <= qpos[:, :, None]
        return keep_topk(sc, seen, topk)


def count_kept(keep, off, topk: int, counts):
    """A step's device counter with this call's rows added to its last
    two slots: the rows whose kept count is not ``min(t + 1, topk)``,
    and the rows (``keep`` [B, S, M] at positions ``off[b]..``)."""
    t = off[:, None] + jnp.arange(keep.shape[1], dtype=jnp.int32)[None]
    wrong = keep.sum(-1, dtype=jnp.int32) != jnp.minimum(t + 1, topk)
    return counts.at[-2:].add(jnp.stack(
        [wrong.sum(dtype=jnp.int32), jnp.int32(wrong.size)]))


def _tiers(S: int, topk: int, block: int):
    """[(first row, end row, selects)]: rows under ``topk`` (whole
    blocks of them) keep everything; then tiers that double."""
    start = min(topk // block * block, S)
    out = [(0, start, False)] if start else []
    a, b = start, max(2 * start, block)
    while a < S:
        b = min(b, S)
        out.append((a, b, True))
        a, b = b, 2 * b
    return out


def kept_mask(iq, ik, iw, topk: int, block: int = 512, scopes=None,
              head_block=None):
    """The kept sets of positions 0..S-1 alone, [B, S, S] bool (row t,
    key s): what ``sparse_causal_attention`` selects, tier by tier, for a
    caller that attends by a kernel of its own
    (``ops/pallas/kept_attention.py``). An S-squared array of one byte
    an entry a layer, and no ``[heads, S, S]`` one. ``scopes``: names
    for the index and the selection in the device trace."""
    B, S0 = iq.shape[:2]
    block = min(block, S0)
    pad = -S0 % block
    if pad:
        rows = lambda a: jnp.pad(a, ((0, 0), (0, pad))
                                 + ((0, 0),) * (a.ndim - 2))
        iq, ik, iw = map(rows, (iq, ik, iw))
    S = S0 + pad
    names = scopes or ("index", "select")
    parts = []
    for a, b, selects in _tiers(S, topk, block):
        kpos = jnp.arange(b, dtype=jnp.int32)[None, None]

        def rows_block(i, a=a, E=b, selects=selects, kpos=kpos):
            r0 = a + i * block
            qpos = (r0 + jnp.arange(block, dtype=jnp.int32))[None, :, None]
            keep = jnp.broadcast_to(kpos <= qpos, (B, block, E))
            if selects:
                with _annotate(names[0]):
                    sc = index_scores(
                        lax.dynamic_slice_in_dim(iq, r0, block, 1),
                        ik[:, :E],
                        lax.dynamic_slice_in_dim(iw, r0, block, 1),
                        head_block)
                with _annotate(names[1]):
                    keep = keep_topk(sc, keep, topk)
            return jnp.pad(keep, ((0, 0), (0, 0), (0, S - E)))

        res = lax.map(rows_block, jnp.arange((b - a) // block,
                                             dtype=jnp.int32))
        parts.append(jnp.swapaxes(res, 0, 1).reshape(B, b - a, S))
    mask = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
    return mask[:, :S0, :S0]


def sparse_causal_attention(q, k, v, iq, ik, iw, scale: float, topk: int,
                            block: int = 512, scopes=None,
                            want_mask: bool = False, head_block=None):
    """q [B, S, H, D] against k [B, S, KV, D] and v [B, S, KV, Dv] at
    positions 0..S-1, row t attending to the keys ``keep_topk`` chooses
    among ``s <= t`` by the index (iq [B, S, Hi, di], ik [B, S, di],
    iw [B, S, Hi]). Returns [B, S, H, Dv] in q's type; with
    ``want_mask`` also the kept set [B, S, S] bool (a check's reading:
    it IS an S-squared array). ``scopes``: names for the three parts
    (index, select, attend) in the device trace. ``head_block``: as
    ``index_scores``."""
    B, S0, H, D = q.shape
    KV, Dv = k.shape[2], v.shape[-1]
    G = H // KV
    block = min(block, S0)
    pad = -S0 % block
    if pad:
        rows = lambda a: jnp.pad(a, ((0, 0), (0, pad))
                                 + ((0, 0),) * (a.ndim - 2))
        q, k, v, iq, ik, iw = map(rows, (q, k, v, iq, ik, iw))
    S = S0 + pad
    names = scopes or ("index", "select", "attend")
    q5 = q.reshape(B, S, KV, G, D)
    outs, masks = [], []
    for a, b, selects in _tiers(S, topk, block):
        E = b                                   # keys this tier can see
        kpos = jnp.arange(E, dtype=jnp.int32)[None, None]

        def rows_block(i, a=a, E=E, selects=selects, kpos=kpos):
            r0 = a + i * block
            qpos = (r0 + jnp.arange(block, dtype=jnp.int32))[None, :, None]
            keep = jnp.broadcast_to(kpos <= qpos, (B, block, E))
            if selects:
                with _annotate(names[0]):
                    sc = index_scores(
                        lax.dynamic_slice_in_dim(iq, r0, block, 1),
                        ik[:, :E],
                        lax.dynamic_slice_in_dim(iw, r0, block, 1),
                        *((head_block,) if head_block else ()))
                with _annotate(names[1]):
                    keep = keep_topk(sc, keep, topk)
            qb = lax.dynamic_slice_in_dim(q5, r0, block, 1)

            def key_block(c, carry):
                m, l, acc = carry
                c0 = c * block
                kb = lax.dynamic_slice_in_dim(k, c0, block, 1)
                vb = lax.dynamic_slice_in_dim(v, c0, block, 1)
                kp = lax.dynamic_slice_in_dim(keep, c0, block, 2)
                s = jnp.einsum("bqkgd,bmkd->bkgqm", qb, kb,
                               preferred_element_type=jnp.float32) * scale
                kp = kp[:, None, None]
                s = jnp.where(kp, s, _NEG)
                m_new = jnp.maximum(m, s.max(-1, keepdims=True))
                p = jnp.where(kp, jnp.exp(s - m_new), 0.0)
                corr = jnp.exp(m - m_new)
                l = l * corr + p.sum(-1, keepdims=True)
                acc = acc * corr + jnp.einsum(
                    "bkgqm,bmkd->bkgqd", p.astype(v.dtype), vb,
                    preferred_element_type=jnp.float32)
                return m_new, l, acc

            with _annotate(names[2]):
                m0 = jnp.full((B, KV, G, block, 1), _NEG, jnp.float32)
                l0 = jnp.zeros((B, KV, G, block, 1), jnp.float32)
                a0 = jnp.zeros((B, KV, G, block, Dv), jnp.float32)
                # up to the diagonal block and no further
                _, l, acc = lax.fori_loop(0, r0 // block + 1, key_block,
                                          (m0, l0, a0))
                o = jnp.transpose(acc / l, (0, 3, 1, 2, 4)).reshape(
                    B, block, H, Dv).astype(q.dtype)
            if want_mask:
                return o, jnp.pad(keep, ((0, 0), (0, 0), (0, S - E)))
            return o

        res = lax.map(rows_block, jnp.arange((b - a) // block,
                                             dtype=jnp.int32))
        o = res[0] if want_mask else res
        outs.append(jnp.swapaxes(o, 0, 1).reshape(B, b - a, H, Dv))
        if want_mask:
            masks.append(jnp.swapaxes(res[1], 0, 1).reshape(B, b - a, S))
    out = (outs[0] if len(outs) == 1
           else jnp.concatenate(outs, axis=1))[:, :S0]
    if not want_mask:
        return out
    mask = masks[0] if len(masks) == 1 else jnp.concatenate(masks, axis=1)
    return out, mask[:, :S0, :S0]
