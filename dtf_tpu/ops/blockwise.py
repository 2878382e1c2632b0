"""Online-softmax blockwise attention — the shared math core.

One accumulation rule serves three consumers:
  - `ops.flash_attention` (Pallas TPU kernel + plain-JAX fallback),
  - `parallel.ring_attention` (the same rule where "blocks" are the
    K/V shards rotating around the 'seq' mesh axis via ppermute),
  - tests (against `mha_reference`).

The rule (Milakov & Gimelshein online softmax, as used by
flash/blockwise/ring attention): carry running row-max ``m``, running
denominator ``l`` and un-normalized output ``o`` across K/V blocks;
each block rescales the carry by ``exp(m_old - m_new)``.  Masked
positions contribute additive ``NEG_INF`` bias, never a post-hoc
where — so fully-masked blocks are numerically inert.

Internal layout is [batch, heads, seq, head_dim] ("BHSD"): the
einsums then contract over the minor-most dims, which XLA maps
straight onto the MXU.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

# Large-but-finite mask bias: keeps exp() exactly 0 for masked entries
# while avoiding the -inf - -inf = nan trap when an entire row of a
# block is masked.
NEG_INF = -1e30


def block_accumulate(o, m, l, q, k, v, scale: float, bias=None):
    """Fold one K/V block into the (o, m, l) carry.

    Shapes (BHSD layout):
      q [.., Sq, D]   k, v [.., Sk, D]
      o [.., Sq, D]   m, l [.., Sq]
      bias broadcastable to [.., Sq, Sk] (additive, NEG_INF = masked)

    Returns the updated (o, m, l).  ``o`` stays un-normalized; divide by
    ``l`` after the last block.
    """
    s = jnp.einsum("...qd,...kd->...qk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if bias is not None:
        s = s + bias
    return fold_scores(o, m, l, s, v)


def fold_scores(o, m, l, s, v):
    """:func:`block_accumulate` past its scores: fold ``s`` [.., Sq, Sk]
    (f32, scaled and biased) and the block's ``v`` into the carry — for a
    caller whose score is not ONE product of q and k."""
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    # m_new can be NEG_INF only while every block so far was fully
    # masked; clamp the subtrahend so exp() sees finite arguments.
    m_safe = jnp.maximum(m_new, NEG_INF)
    p = jnp.exp(s - m_safe[..., None])
    corr = jnp.exp(m - m_safe)
    l_new = l * corr + jnp.sum(p, axis=-1)
    # P·V runs at the operands' native precision with f32 accumulation:
    # when v is bf16 (the TPU kernel path), p is cast DOWN so the MXU
    # sees bf16×bf16 (full rate) — the standard flash-attention trade.
    # f32 callers (oracle, ring attention) are bit-for-bit unchanged.
    o_new = o * corr[..., None] + jnp.einsum(
        "...qk,...kd->...qd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32)
    return o_new, m_new, l_new


def finalize(o, l):
    """Normalize the accumulated output; fully-masked rows become 0."""
    denom = jnp.where(l == 0.0, 1.0, l)
    return o / denom[..., None]


def log_sum_exp(m, l):
    """The carry's log-sum-exp, ``m + log l``; a row that saw nothing
    (``m`` NEG_INF, ``l`` 0) reads NEG_INF."""
    return jnp.maximum(m, NEG_INF) + jnp.log(jnp.where(l == 0.0, 1.0, l))


def causal_bias(q_pos, k_pos):
    """Additive causal mask from absolute positions.

    q_pos [Sq], k_pos [Sk] → [Sq, Sk] with 0 where k may be attended
    (k_pos <= q_pos) and NEG_INF elsewhere.
    """
    return jnp.where(q_pos[:, None] >= k_pos[None, :], 0.0, NEG_INF)


def _to_bhsd(x):
    return jnp.swapaxes(x, -3, -2)


def mha_reference(q, k, v, *, causal: bool = False,
                  scale: Optional[float] = None):
    """Plain O(S²)-memory attention, the numerical ground truth.

    q, k, v: [batch, seq, heads, head_dim]; returns same shape/dtype
    as q's compute in float32 then cast back.
    """
    orig_dtype = q.dtype
    scale = scale if scale is not None else 1.0 / jnp.sqrt(q.shape[-1])
    qt, kt, vt = _to_bhsd(q), _to_bhsd(k), _to_bhsd(v)
    s = jnp.einsum("...qd,...kd->...qk", qt.astype(jnp.float32),
                   kt.astype(jnp.float32)) * scale
    if causal:
        s = s + causal_bias(jnp.arange(q.shape[-3]), jnp.arange(k.shape[-3]))
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("...qk,...kd->...qd", p, vt.astype(jnp.float32))
    return _to_bhsd(out).astype(orig_dtype)


def blockwise_attention(q, k, v, *, causal: bool = False,
                        scale: Optional[float] = None,
                        block_k: int = 512,
                        q_offset=0, k_offset=0):
    """Memory-efficient attention: scans K/V in blocks of ``block_k``.

    q, k, v: [batch, seq, heads, head_dim].  ``q_offset``/``k_offset``
    are the absolute positions of q[.., 0, ..] and k[.., 0, ..] — this
    is what lets ring attention reuse the function on rotating shards
    whose global position differs from their local index.  Offsets may
    be traced scalars.

    Differentiable (the scan is reverse-mode differentiable; memory is
    O(S·block_k) forward, with block K/V saved per step for the
    backward pass).
    """
    orig_dtype = q.dtype
    sq, sk = q.shape[-3], k.shape[-3]
    scale = scale if scale is not None else 1.0 / jnp.sqrt(q.shape[-1])
    block_k = min(block_k, sk)
    num_blocks, rem = divmod(sk, block_k)
    if rem:
        raise ValueError(f"kv length {sk} not divisible by block_k {block_k}")

    qt = _to_bhsd(q).astype(jnp.float32)
    kt = _to_bhsd(k).astype(jnp.float32)
    vt = _to_bhsd(v).astype(jnp.float32)
    # stack K/V blocks on a leading scan axis
    kb = kt.reshape(*kt.shape[:-2], num_blocks, block_k, kt.shape[-1])
    kb = jnp.moveaxis(kb, -3, 0)
    vb = vt.reshape(*vt.shape[:-2], num_blocks, block_k, vt.shape[-1])
    vb = jnp.moveaxis(vb, -3, 0)

    q_pos = q_offset + jnp.arange(sq)
    o0 = jnp.zeros_like(qt)
    m0 = jnp.full(qt.shape[:-1], NEG_INF, jnp.float32)
    l0 = jnp.zeros(qt.shape[:-1], jnp.float32)

    def body(carry, blk):
        o, m, l, i = carry
        kblk, vblk = blk
        bias = None
        if causal:
            k_pos = k_offset + i * block_k + jnp.arange(block_k)
            bias = causal_bias(q_pos, k_pos)
        o, m, l = block_accumulate(o, m, l, qt, kblk, vblk, scale, bias)
        return (o, m, l, i + 1), None

    (o, m, l, _), _ = jax.lax.scan(body, (o0, m0, l0, jnp.int32(0)), (kb, vb))
    return _to_bhsd(finalize(o, l)).astype(orig_dtype)
