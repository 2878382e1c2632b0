"""Flash attention for TPU — Pallas forward kernel + blockwise backward.

Why a hand-written kernel when XLA fuses everything else (SURVEY.md
§2.4 — the reference's equivalent layer is cuDNN): naive attention
materializes the [S, S] score matrix in HBM, so at long context the op
is HBM-bound.  The Pallas kernel keeps each [block_q, block_k] score
tile in VMEM, carries the online-softmax state (ops.blockwise math) in
registers/VMEM, and only ever writes the [S, D] output — turning an
O(S²) HBM traffic op into O(S·D).

Grid: (batch·heads, Sq/block_q); each program streams K/V through VMEM
in block_k slices.  The backward has two formulations, both
recomputing probabilities per tile from the saved log-sum-exp (the
standard flash trade: extra FLOPs for O(S²) less HBM traffic):

- **fused** (`_dfused_kernel`, the default where its [Sq, D] f32 dq
  scratch fits VMEM — seq ≤ 4096 at d 128): dq, dk, dv from ONE
  traversal of the tile space — 5 tile matmuls and one softmax
  recompute per tile vs the split pair's 7 and two.  Measured r5,
  flagship step [16, 2048, 6, 128]: 235.2 → 218-223 ms (+5-8%
  tokens/s, mfu_model 0.561 → 0.59-0.605), isolated bwd 3.99 → 3.31 ms.
- **split** (`_dq_kernel` + `_dkdv_kernel`, longer sequences): dq
  streaming K/V; dk+dv streaming Q/dO — single writer per output
  tile, no atomics, VMEM capped at the block size regardless of
  sequence length: seq 32k compiles and runs (fwd 7.2 ms at
  [1, 32768, 4, 128]) where a resident-K/V formulation exceeds scoped
  VMEM from seq 8k.

`_blockwise_bwd` (plain JAX, same math) remains as the portable oracle
both are tested against (fused ≡ split ≡ oracle,
test_pallas_fused_bwd_matches_split).  The backward does 2.5× the
forward's FLOPs; its kernels accumulate in f32 scratch and store in the
native dtype.  On the chip these kernels run in the
`gpt13b-train-zero-x4` cell: `flash_attention_roofline` 48.48 %
(ledger, PR 43); the kernels alone at other shapes are not measured on
this installation.  All kernels stream their long-axis
operands through VMEM one block per sequential grid step — carries
live in VMEM scratch.

Causal masking is diagonal-only: blocks the diagonal never crosses run
a mask-free accumulate (no iota/compare/select per element), and only
straddling blocks pay the masking VPU work — measured ~10% off the
fwd kernel at [16, 2048, 6, 128].

The d_head-64 penalty (GPT-2's 12×64 layout against the flagship's
6×128 at identical parameters) is intrinsic MXU
geometry, not a kernel gap — matmul cost conserves output_tiles ×
ceil(contraction/128) passes under every head-packing construction,
and 2× heads means 2× softmax score elements.  Its size is not
measured on this installation.

`flash_forward` is a second, forward-only entry for serving (a prefill
chunk over keys expanded from a latent cache): values of a width of their
own, a part of the score whose key all heads share, a live key count the
kernel prefetches, `(o, lse)` out and optionally in.  `flash_attention`
does not go through it and lowers as it did.

On non-TPU backends `flash_attention` transparently falls back to the
differentiable `ops.blockwise.blockwise_attention` (same math), so the
API is portable and testable on the CPU mesh.  Pass
``use_pallas="interpret"`` to force the kernel through the Pallas
interpreter on CPU (used by tests to validate the kernel itself).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dtf_tpu.ops import blockwise as bw

# 1024 measured fastest for the streaming kernel on v5e (block sweep
# at seq 8k: 1024² ≈ 10.5 ms vs 512² ≈ 16 ms — fewer grid steps, same
# capped VMEM; 2048-blocks exceed scoped VMEM and fail to compile).
# Re-swept r4 at the flagship step shape [16,2048,6,128] under the
# loop-differenced protocol (pre-scratch-store kernels — relative
# ordering is what the sweep establishes): 1024² f+b 5.40 ms vs
# 512×1024 6.11, 1024×512 6.43, 512² 7.19, 256×1024 7.63, 256² 15.3 —
# every compilable alternative loses 13-180%, confirming the default;
# a bwd-only sweep agreed (1024² 2.7 ms vs 512×1024 5.0, 1024×512
# 5.1).  Both sweeps predate the scratch-store kernels — the relative
# ordering, not the absolute times, is what they establish
DEFAULT_BLOCK_Q = 1024
DEFAULT_BLOCK_K = 1024
# flash_forward's blocks: the same 1024 x 1024, swept again with the carry
# and the second product in the kernel (v5e, a chunk of 2,048 x 32 heads
# over 8k / 32k keys, docs/pr53_latent_chunk_sweep.jsonl's `tune` lines):
# 1024 x 1024 4.17 / 14.64 ms, 512 x 1024 4.36 / 15.31, 1024 x 512 5.02 /
# 18.26, 512 x 512 5.47 / 21.08, 2048 x 512 6.09 / 22.02; 2048 x 1024 runs
# out of VMEM
CHUNK_BLOCK_K = 1024

# base-2 softmax folding (bwd kernels): exp(x) lowers to
# exp2(x·log2 e), so folding log2 e into the score scale deletes one
# per-element VPU multiply from the recompute (measured neutral on
# v5e flagship shapes — see _dq_kernel)
_LOG2E = 1.4426950408889634


# ---------------------------------------------------------------------------
# Pallas forward kernel
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, oacc_ref, m_ref,
                l_ref, *, scale, causal):
    """Grid (BH, Sq/block_q, Sk/block_k): one K/V block per step.

    K/V stream through VMEM one [block_k, D] tile at a time (the r2
    kernel held the FULL [Sk, D] K and V per program, which sat at the
    ~16 MB scoped-VMEM edge from seq 8k and failed outright beyond).
    The online-softmax carry (un-normalized o in f32, running max m,
    denominator l) lives in VMEM scratch that persists across the
    sequential k grid dimension — never touching HBM.  The final
    (o, lse) are written on the last live k step.

    Inputs stay in their native dtype (bf16 in production): the MXU
    multiplies bf16×bf16 with f32 accumulation at full rate, and for
    bf16 inputs the products are exact in f32 — upcasting first only
    slowed the matmuls (measured ~20 vs ~70 TFLOP/s on v5e).
    """
    block_q = q_ref.shape[0]
    block_k = k_ref.shape[0]
    num_kv = pl.num_programs(2)
    iq = pl.program_id(1)
    jk = pl.program_id(2)

    @pl.when(jk == 0)
    def _init():
        oacc_ref[...] = jnp.zeros_like(oacc_ref)
        m_ref[...] = jnp.full_like(m_ref, bw.NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    live = (jk * block_k <= (iq + 1) * block_q - 1) if causal else True
    # blocks entirely at-or-below the diagonal need no mask at all —
    # the per-element iota/compare/select VPU work only runs on blocks
    # the diagonal actually crosses
    straddles = (jk * block_k + block_k - 1 > iq * block_q) if causal \
        else False

    def _accumulate(bias):
        o, m, l = bw.block_accumulate(
            oacc_ref[...], m_ref[...][:, 0], l_ref[...][:, 0],
            q_ref[...], k_ref[...], v_ref[...], scale, bias)
        oacc_ref[...] = o
        m_ref[...] = m[:, None]
        l_ref[...] = l[:, None]

    @pl.when(live & jnp.logical_not(straddles) if causal else live)
    def _compute_unmasked():
        _accumulate(None)

    if causal:
        @pl.when(live & straddles)
        def _compute_masked():
            q_pos = iq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, 1), 0)
            k_pos = jk * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (1, block_k), 1)
            _accumulate(jnp.where(q_pos >= k_pos, 0.0, bw.NEG_INF))

    if causal:
        j_last = jnp.minimum(
            num_kv - 1, jax.lax.div((iq + 1) * block_q - 1, block_k))
    else:
        j_last = num_kv - 1

    @pl.when(jk == j_last)
    def _finalize():
        o = oacc_ref[...]
        m = m_ref[...][:, 0]
        l = l_ref[...][:, 0]
        o_ref[...] = bw.finalize(o, l).astype(o_ref.dtype)
        lse = (jnp.maximum(m, bw.NEG_INF)
               + jnp.log(jnp.where(l == 0.0, 1.0, l)))
        lse_ref[...] = lse[:, None]  # [block_q, 1]; see out_specs note


def _pallas_forward(q, k, v, scale, causal, block_q, block_k, interpret):
    """q, k, v: [BH, S, D] → (o [BH, Sq, D], lse [BH, Sq])."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    grid = (bh, sq // block_q, sk // block_k)
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((None, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, block_q, d), lambda b, i, j: (b, i, 0)),
            # lse kept 3-D [BH, Sq, 1]: TPU lowering requires the last
            # two block dims to tile (8, 128) or equal the array dims;
            # (block_q, 1) satisfies that where a 1-D (block_q,) cannot.
            pl.BlockSpec((None, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, sq, 1), jnp.float32),
        ],
        # f32 online-softmax carry, on-chip only: persists across the
        # sequential k grid dimension, re-initialized at jk == 0
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(q, k, v)
    o, lse = out
    return o, lse[..., 0]


# ---------------------------------------------------------------------------
# Forward only, serving's own: a prefill chunk's queries against keys
# EXPANDED from a latent cache (ops.paged_attention.latent_chunk_attention)
# ---------------------------------------------------------------------------

def _chunk_fwd_kernel(*refs, scale, causal, live, carried, shared, masked):
    """Grid (B, H, Sq/block_q, Sk/block_k): :func:`_fwd_kernel`'s traversal
    and carry, for values of a width of their own and, ``shared``, a score
    of TWO products — a head's own ``q . k`` plus ``q_shared . k_shared``,
    the second key ONE a token for every head of the row (a latent cache's
    rotary key: never copied a head).  ``live``: the first ref is ``kv_len``
    [B] (scalar prefetch), row ``b`` sees keys ``< kv_len[b]``: blocks at and
    past it are skipped (their copies too: the index maps stay on the last
    live block), the block it cuts is masked.  ``carried``: the carry
    starts from an earlier call's ``(o, lse)`` — ``o`` normalised is the
    un-normalised sum at ``m = lse, l = 1`` — so a caller that walks the
    keys in blocks of its own merges nothing outside the kernel.
    ``masked``: a ref after the carry holds the tile's MEMBERSHIP
    [block_q, block_k], or [block_q / tile, block_k / block, tile, block]
    (non-zero: attended); a query sees a key the other rules show it AND
    its membership names, so no block is whole."""
    refs = list(refs)
    len_ref = refs.pop(0) if live else None
    q_ref = refs.pop(0)
    qs_ref = refs.pop(0) if shared else None
    k_ref = refs.pop(0)
    ks_ref = refs.pop(0) if shared else None
    v_ref = refs.pop(0)
    o_in_ref, lse_in_ref = (refs.pop(0), refs.pop(0)) if carried else (
        None, None)
    member_ref = refs.pop(0) if masked else None
    o_ref, lse_ref, oacc_ref, m_ref, l_ref = refs
    block_q, block_k = q_ref.shape[0], k_ref.shape[0]
    iq, jk = pl.program_id(2), pl.program_id(3)

    @pl.when(jk == 0)
    def _init():
        if carried:
            oacc_ref[...] = o_in_ref[...]
            m_ref[...] = lse_in_ref[...]
            l_ref[...] = jnp.ones_like(l_ref)
        else:
            oacc_ref[...] = jnp.zeros_like(oacc_ref)
            m_ref[...] = jnp.full_like(m_ref, bw.NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)

    # run: the block holds a key some query of the tile sees; whole: every
    # query sees every key of it (no mask: see _fwd_kernel)
    run, whole = jnp.bool_(True), jnp.bool_(not masked)
    if causal:
        run &= jk * block_k <= (iq + 1) * block_q - 1
        whole &= jk * block_k + block_k - 1 <= iq * block_q
    if live:
        n = len_ref[pl.program_id(0)]
        run &= jk * block_k < n
        whole &= (jk + 1) * block_k <= n

    def _accumulate(seen):
        dims = (((1,), (1,)), ((), ()))
        s = jax.lax.dot_general(q_ref[...], k_ref[...], dims,
                                preferred_element_type=jnp.float32)
        if shared:
            s = s + jax.lax.dot_general(qs_ref[...], ks_ref[...], dims,
                                        preferred_element_type=jnp.float32)
        s = s * scale
        if seen is not None:
            s = s + jnp.where(seen, 0.0, bw.NEG_INF)
        o, m, l = bw.fold_scores(oacc_ref[...], m_ref[...][:, 0],
                                 l_ref[...][:, 0], s, v_ref[...])
        oacc_ref[...] = o
        m_ref[...] = m[:, None]
        l_ref[...] = l[:, None]

    @pl.when(run & whole)
    def _compute_unmasked():
        _accumulate(None)

    @pl.when(run & jnp.logical_not(whole))
    def _compute_masked():
        k_pos = jk * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_k), 1)
        seen = jnp.bool_(True)
        if masked:
            named = member_ref[...]
            if named.ndim == 4:         # tiles of queries x blocks of keys
                named = jnp.concatenate(
                    [named[:, i].reshape(block_q, -1)
                     for i in range(named.shape[1])], axis=1)
            seen &= named.astype(jnp.int32) != 0
        if causal:
            seen &= k_pos <= iq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, 1), 0)
        if live:
            seen &= k_pos < n
        _accumulate(seen)

    @pl.when(jk == pl.num_programs(3) - 1)
    def _finalize():
        o_ref[...] = bw.finalize(oacc_ref[...], l_ref[...][:, 0]
                                 ).astype(o_ref.dtype)
        lse_ref[...] = bw.log_sum_exp(m_ref[...], l_ref[...])


# VMEM a call states as its limit where it is masked or its values are
# wider than a lane tile: beside the narrow call's blocks (the compiler's 16
# MiB default holds those) a [1024, 1024] membership tile, its int32 image
# and the mask of it, or a carry of twice the width — which alone compiles
# under the default and INSIDE a serve body, beside kernels that state 64
# MiB, is refused for 64.17 MiB of scoped VMEM unless it states a limit too
# (tests/test_tpu_lowering.py, GLM-5.2's chunk body)
_WIDE_VMEM_BYTES = 64 * 2 ** 20


def flash_forward(q, k, v, *, q_shared=None, k_shared=None, scale: float,
                  causal: bool = False, kv_len=None, carry=None,
                  member=None, name: str = "flash_fwd_chunk",
                  use_pallas=None):
    """Attention forward and nothing else (no gradient is defined), for
    serving.  Heads-major: q [B, H, Sq, D], k [B, H, Sk, D], v
    [B, H, Sk, Dv] (``Dv`` need not be ``D``), and optionally a part of
    the score all heads of a row share one key for: q_shared [B, H, Sq, E],
    k_shared [B, Sk, E]; ``score = (q . k + q_shared . k_shared) * scale``.

    ``causal``: query ``i`` sees keys ``j <= i`` (a chunk against itself).
    ``kv_len`` [B] int32: row ``b`` sees keys ``j < kv_len[b]`` — traced, so
    one compile serves every count; dead blocks cost a grid step and no
    copy.  ``member`` (non-zero: attended): a query's OWN choice of keys,
    the same for every head — it sees a key the two rules above show it AND
    ``member`` names — a row a query [B, Sq, Sk], or in tiles of queries and
    blocks of keys [B, Sq / tile, Sk / block, tile, block] (whole tiles and
    blocks a block of the grid: one DMA each, no relayout).  ``carry``:
    ``(o, lse)`` of an earlier call over OTHER keys of the same queries;
    the result is then the attention over both sets.  ``name``: the
    kernel's, on the device's timeline.

    Returns ``(o [B, H, Sq, Dv] float32, lse [B, H, Sq, 1] float32)``, a
    row that saw nothing as ``(0, NEG_INF)``.  ``use_pallas`` as
    :func:`flash_attention`: off the TPU the same arithmetic in plain JAX
    through ``ops.blockwise``, the keys as one block."""
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    b, h, sq, _ = q.shape
    sk, dv = k.shape[2], v.shape[-1]
    if not use_pallas:
        return _forward_plain(q, k, v, q_shared, k_shared, scale, causal,
                              kv_len, carry, member)
    block_q = math.gcd(DEFAULT_BLOCK_Q, sq)
    block_k = math.gcd(CHUNK_BLOCK_K, sk)
    live, carried = kv_len is not None, carry is not None
    shared, masked = q_shared is not None, member is not None
    num_kv = sk // block_k

    def last(b_, i, pre):
        """The last block of keys the tile ``i`` of row ``b_`` sees."""
        j = num_kv - 1
        if causal:
            j = jnp.minimum(j, ((i + 1) * block_q - 1) // block_k)
        if live:
            j = jnp.minimum(j, jnp.maximum(pre[0][b_] - 1, 0) // block_k)
        return j

    def q_spec(lanes):
        return pl.BlockSpec((None, None, block_q, lanes),
                            lambda b_, h_, i, j, *pre: (b_, h_, i, 0))

    def k_spec(lanes):
        return pl.BlockSpec(
            (None, None, block_k, lanes),
            lambda b_, h_, i, j, *pre: (b_, h_,
                                        jnp.minimum(j, last(b_, i, pre)), 0))
    # (the live count is prefetched: an operand, the first, with no spec)
    operands = [jnp.asarray(kv_len, jnp.int32)] if live else []
    operands.append(q)
    in_specs = [q_spec(q.shape[-1])]
    if shared:
        operands.append(q_shared)
        in_specs.append(q_spec(q_shared.shape[-1]))
    operands.append(k)
    in_specs.append(k_spec(k.shape[-1]))
    if shared:
        operands.append(k_shared)
        in_specs.append(pl.BlockSpec(
            (None, block_k, k_shared.shape[-1]),
            lambda b_, h_, i, j, *pre: (
                b_, jnp.minimum(j, last(b_, i, pre)), 0)))
    operands.append(v)
    in_specs.append(k_spec(dv))
    aliases = {}
    if carried:
        # the carry is updated in place: a caller's loop over steps of keys
        # holds ONE (o, lse), not a copy of it a step
        aliases = {len(operands): 0, len(operands) + 1: 1}
        operands += [carry[0], carry[1]]
        in_specs += [q_spec(dv), q_spec(1)]
    if masked:
        operands.append(member)
        if member.ndim == 3:
            in_specs.append(pl.BlockSpec(
                (None, block_q, block_k),
                lambda b_, h_, i, j, *pre: (
                    b_, i, jnp.minimum(j, last(b_, i, pre)))))
        else:
            tile, mb = member.shape[3:]
            in_specs.append(pl.BlockSpec(
                (None, block_q // tile, block_k // mb, tile, mb),
                lambda b_, h_, i, j, *pre: (
                    b_, i, jnp.minimum(j, last(b_, i, pre)), 0, 0)))
    return pl.pallas_call(
        functools.partial(_chunk_fwd_kernel, scale=scale, causal=causal,
                          live=live, carried=carried, shared=shared,
                          masked=masked),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=int(live),
            grid=(b, h, sq // block_q, num_kv),
            in_specs=in_specs,
            out_specs=[q_spec(dv), q_spec(1)],
            scratch_shapes=[pltpu.VMEM((block_q, dv), jnp.float32),
                            pltpu.VMEM((block_q, 1), jnp.float32),
                            pltpu.VMEM((block_q, 1), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((b, h, sq, dv), jnp.float32),
                   jax.ShapeDtypeStruct((b, h, sq, 1), jnp.float32)],
        compiler_params=(pltpu.CompilerParams(
            vmem_limit_bytes=_WIDE_VMEM_BYTES) if masked or dv > 128
            else None),
        input_output_aliases=aliases,
        interpret=use_pallas == "interpret",
        name=name,
    )(*operands)


def _forward_plain(q, k, v, q_shared, k_shared, scale, causal, kv_len,
                   carry, member):
    """:func:`flash_forward` in plain JAX: the keys as ONE block of the
    online softmax (``ops.blockwise``)."""
    sq, sk = q.shape[2], k.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32)
    if q_shared is not None:
        s = s + jnp.einsum("bhqe,bke->bhqk", q_shared, k_shared,
                           preferred_element_type=jnp.float32)
    s = s * scale
    k_pos = jnp.arange(sk, dtype=jnp.int32)
    seen = jnp.ones((1, 1, sq, sk), bool)
    if causal:
        seen &= k_pos <= jnp.arange(sq, dtype=jnp.int32)[:, None]
    if kv_len is not None:
        seen &= k_pos < kv_len[:, None, None, None]
    if member is not None:
        seen &= (member != 0)[:, None]
    if carry is None:
        o = jnp.zeros(q.shape[:3] + v.shape[-1:], jnp.float32)
        m = jnp.full(q.shape[:3], bw.NEG_INF, jnp.float32)
        l = jnp.zeros(q.shape[:3], jnp.float32)
    else:
        o, m = carry[0], carry[1][..., 0]
        l = jnp.ones_like(m)
    o, m, l = bw.fold_scores(o, m, l, s + jnp.where(seen, 0.0, bw.NEG_INF),
                             v)
    return bw.finalize(o, l), bw.log_sum_exp(m, l)[..., None]


# ---------------------------------------------------------------------------
# Pallas backward kernels
#
# Two kernels, the standard flash-attention split:
#   dq:    grid (BH, Sq/block_q) — each program owns one dq tile and
#          streams K/V blocks (same traversal as the forward).
#   dk/dv: grid (BH, Sk/block_k) — each program owns one dk+dv tile and
#          streams Q/dO blocks.  No atomics, no cross-program
#          accumulation: every output tile has exactly one writer.
# Probabilities are recomputed from the saved LSE per tile in VMEM
# (the flash trade: O(S²) HBM traffic never happens).  delta =
# rowsum(dO·O) is a cheap [BH, Sq] contraction done in plain JAX.
# Under causal masking each program skips the dead triangle
# (dq: K blocks past the diagonal; dk/dv: Q blocks before it).
# ---------------------------------------------------------------------------

def _bwd_tile(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *,
              scale, masked, iq, jk, block_q, block_k):
    """One (q-block, k-block) tile of the flash backward recompute —
    the SINGLE copy of the numerics shared by the split dq, split
    dk/dv, and fused kernels (each applies its own accumulator updates
    to the returned tensors).  Native-dtype operands with f32
    accumulation (see _fwd_kernel); base-2 softmax recompute (see
    _dq_kernel's historical note: folding log2 e into the scale turns
    exp into a raw exp2 — lse arrives base-2 as lse3); diagonal-only
    masking.  Returns (p, do, q, k, ds)."""
    q = q_ref[...]
    k = k_ref[...]
    v = v_ref[...]
    do = do_ref[...]
    lse = lse_ref[...][:, 0]
    delta = delta_ref[...][:, 0]
    s2 = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32
                             ) * (scale * _LOG2E)
    if masked:
        q_pos = iq * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, 1), 0)
        k_pos = jk * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_k), 1)
        s2 = jnp.where(q_pos >= k_pos, s2, bw.NEG_INF)
    p = jnp.exp2(s2 - lse[:, None])   # [bq, bk]
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = (p * (dp - delta[:, None]) * scale).astype(q.dtype)
    return p, do, q, k, ds


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dqacc_ref, *, scale, causal):
    """Grid (BH, Sq/block_q, Sk/block_k): K/V stream one block per step
    (same capped-VMEM pattern as the forward); the dq tile accumulates
    in f32 VMEM scratch across the sequential k dimension and stores
    once, in the output's native dtype, on the last step — a bf16
    output never materializes f32 gradients in HBM.  The previous form
    (f32 output refs + astype outside the kernel) moved ~0.9 GB/layer
    of extra gradient bytes; measured same-session A/B: flagship step
    238.6 → 231.9 ms (+2.9% tokens/s), micro bwd-only 7.8 → 5.8 ms at
    [2, 8192, 8, 128]."""
    block_q = q_ref.shape[0]
    block_k = k_ref.shape[0]
    iq = pl.program_id(1)
    jk = pl.program_id(2)

    @pl.when(jk == 0)
    def _init():
        dqacc_ref[...] = jnp.zeros_like(dqacc_ref)

    live = (jk * block_k <= (iq + 1) * block_q - 1) if causal else True
    # diagonal-only masking (see _fwd_kernel): blocks the diagonal does
    # not cross skip the per-element mask entirely
    straddles = (jk * block_k + block_k - 1 > iq * block_q) if causal \
        else False

    def _tile(masked):
        # the base-2 recompute historically lived here: folding
        # log2(e) into the scale the per-element multiply already pays
        # turns exp() (exp2 + a multiply) into a raw exp2 — the lse
        # conversion is per-ROW.  Strictly fewer VPU ops; measured
        # NEUTRAL end-to-end on v5e at the flagship shapes (the bwd is
        # not multiply-bound there) — kept because it can only help on
        # shapes/chips where the VPU is the constraint.  The numerics
        # are now single-sourced in _bwd_tile.
        _, _, _, k, ds = _bwd_tile(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
            scale=scale, masked=masked, iq=iq, jk=jk,
            block_q=block_q, block_k=block_k)
        dqacc_ref[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(live & jnp.logical_not(straddles) if causal else live)
    def _tile_unmasked():
        _tile(False)

    if causal:
        @pl.when(live & straddles)
        def _tile_masked():
            _tile(True)

    # unconditional (dead causal blocks still step the grid): the tile
    # is complete once the last k block has streamed past
    @pl.when(jk == pl.num_programs(2) - 1)
    def _store():
        dq_ref[...] = dqacc_ref[...].astype(dq_ref.dtype)


def _dkdv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref,
                 dv_ref, dkacc_ref, dvacc_ref, *, scale, causal, block_q,
                 block_k):
    """Grid (BH, Sk/block_k, Sq/block_q): the Pallas pipeline streams
    one [block_q] slice of Q/dO/lse/delta per step (never the full
    sequence in VMEM — the 2-D formulation VMEM-OOMed at seq 8k), and
    dk/dv accumulate in f32 VMEM scratch across the sequential q-grid
    dimension, storing native-dtype outputs once on the last step
    (see _dq_kernel)."""
    iq = pl.program_id(2)
    jk = pl.program_id(1)

    @pl.when(iq == 0)
    def _init():
        dkacc_ref[...] = jnp.zeros_like(dkacc_ref)
        dvacc_ref[...] = jnp.zeros_like(dvacc_ref)

    # causal: q blocks strictly above the diagonal contribute nothing
    live = ((iq + 1) * block_q - 1 >= jk * block_k) if causal else True
    # diagonal-only masking (see _fwd_kernel)
    straddles = (jk * block_k + block_k - 1 > iq * block_q) if causal \
        else False

    def _tile(masked):
        p, do, q, _, ds = _bwd_tile(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
            scale=scale, masked=masked, iq=iq, jk=jk,
            block_q=block_q, block_k=block_k)
        dvacc_ref[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dkacc_ref[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(live & jnp.logical_not(straddles) if causal else live)
    def _tile_unmasked():
        _tile(False)

    if causal:
        @pl.when(live & straddles)
        def _tile_masked():
            _tile(True)

    @pl.when(iq == pl.num_programs(2) - 1)
    def _store():
        dk_ref[...] = dkacc_ref[...].astype(dk_ref.dtype)
        dv_ref[...] = dvacc_ref[...].astype(dv_ref.dtype)


def _dfused_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dk_ref, dv_ref, dqacc_ref, dkacc_ref,
                   dvacc_ref, *, scale, causal, block_q, block_k):
    """Single-pass backward: dq, dk, dv from ONE traversal of the
    (q-block × k-block) tile space — the S and dP recomputes happen
    once per tile instead of once in each of the split kernels (5 tile
    matmuls vs the split pair's 7, and half the exp2 softmax-recompute
    VPU work).

    Grid (BH, Sk/block_k, Sq/block_q): dk/dv accumulate per k tile in
    block-sized f32 scratch across the inner q dimension (exactly the
    split _dkdv_kernel pattern); dq — whose accumulation runs across
    the OUTER k dimension, where block scratch can't carry it — lives
    in a FULL-SEQUENCE [Sq, D] f32 VMEM scratch, zeroed on the first k
    step and sliced per q tile.  That scratch is what bounds the
    kernel: Sq·D·4 bytes of VMEM (1 MB at the flagship 2048×128), so
    _pallas_backward gates the fused path on _FUSED_DQ_SCRATCH_MAX and
    falls back to the split kernels for longer sequences.  The dq
    output tile is written on EVERY visit with the current partial sum
    (defined value per flush; the sequentially-last flush carries the
    complete sum) — see the store-site comment."""
    iq = pl.program_id(2)
    jk = pl.program_id(1)
    num_q = pl.num_programs(2)

    @pl.when(jk == 0)
    def _init_dq_slice():
        dqacc_ref[pl.dslice(iq * block_q, block_q), :] = jnp.zeros(
            (block_q, dqacc_ref.shape[1]), jnp.float32)

    @pl.when(iq == 0)
    def _init_dkdv():
        dkacc_ref[...] = jnp.zeros_like(dkacc_ref)
        dvacc_ref[...] = jnp.zeros_like(dvacc_ref)

    live = ((iq + 1) * block_q - 1 >= jk * block_k) if causal else True
    # diagonal-only masking (see _fwd_kernel)
    straddles = (jk * block_k + block_k - 1 > iq * block_q) if causal \
        else False

    def _tile(masked):
        p, do, q, k, ds = _bwd_tile(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
            scale=scale, masked=masked, iq=iq, jk=jk,
            block_q=block_q, block_k=block_k)
        dvacc_ref[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dkacc_ref[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dqacc_ref[pl.dslice(iq * block_q, block_q), :] += (
            jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32))

    @pl.when(live & jnp.logical_not(straddles) if causal else live)
    def _tile_unmasked():
        _tile(False)

    if causal:
        @pl.when(live & straddles)
        def _tile_masked():
            _tile(True)

    @pl.when(iq == num_q - 1)
    def _store_dkdv():
        dk_ref[...] = dkacc_ref[...].astype(dk_ref.dtype)
        dv_ref[...] = dvacc_ref[...].astype(dv_ref.dtype)

    # dq tile iq is complete once the last k block has passed (under
    # causal masking contributions beyond the diagonal were dead).
    # The store is UNCONDITIONAL: the output block is revisited once
    # per outer k step, and Pallas may flush its VMEM buffer to HBM on
    # every revisit — writing the current partial sum each visit means
    # every flush carries a defined value and the final (sequentially
    # last) flush carries the complete one, instead of relying on
    # earlier flushes of an unwritten buffer being harmlessly
    # overwritten (r5 high-effort review; measured step-neutral).
    dq_ref[...] = dqacc_ref[
        pl.dslice(iq * block_q, block_q), :].astype(dq_ref.dtype)


# The fused kernel's [Sq, D] f32 dq scratch must fit VMEM next to the
# streamed tiles and the [block_q, block_k] score intermediates.
# The 2 MB gate (seq 4096 at d 128) is measured on both sides (r5):
# at seq 4096 the production step compiles and runs fused (128.3k
# tokens/s, mfu_model 0.603; jit-step, scan-wrapped grad-accum, and
# bare-call forms all verified on-chip — one micro-probe fori_loop
# harness hits a Mosaic compile failure there, a harness artifact, not
# a production path); at seq 8192 a forced fused arm (4 MB scratch,
# 512-q blocks) measures WORSE than the split kernels (isolated bwd
# 8.99 vs 8.66 ms) — the scratch squeezes the pipeline, so longer
# sequences keep the split streaming formulation.
_FUSED_DQ_SCRATCH_MAX = 2 * 1024 * 1024

# Fused-kernel q-block sweep, recorded because the obvious conclusion
# was wrong: ISOLATED loop-differenced bwd at [96, 2048, 128] measures
# 512×1024 at 1.74-1.81 ms vs 1024² at 2.54-3.31 (1024×512 4.71,
# 512² 2.98, 256×1024 3.17) — but the FULL flagship training step is
# block-q-neutral (2× runs each, same process: 147.0-147.2k tokens/s
# at 512 vs 147.2-147.6k at 1024).  The serialized micro loop amplifies
# pipeline-ramp effects the real step (bwd sandwiched between the
# block's matmuls, operands arriving from fusions) doesn't see.  The
# kernel therefore keeps the shared 1024² default — one fewer special
# case, chosen on the step-level evidence.


def _pallas_backward(q, k, v, o, lse, do, scale, causal, block_q, block_k,
                     interpret, fused=None):
    """All arrays [BH, S, D] (lse [BH, Sq]); returns (dq, dk, dv).

    ``fused``: None = auto (single-pass kernel when the [Sq, D] f32 dq
    scratch fits _FUSED_DQ_SCRATCH_MAX); True/False = force (tests pin
    both paths against each other and the oracle)."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)               # [BH, Sq, 1]
    # pre-converted to base 2 for the kernels' exp2 softmax recompute
    # (the natural-log lse itself is the public residual contract)
    lse3 = lse[..., None] * _LOG2E

    if fused is None:
        fused = sq == sk and sq * d * 4 <= _FUSED_DQ_SCRATCH_MAX
    if fused:
        bq = block_q
        dq, dk, dv = pl.pallas_call(
            functools.partial(_dfused_kernel, scale=scale, causal=causal,
                              block_q=bq, block_k=block_k),
            grid=(bh, sk // block_k, sq // bq),
            in_specs=[
                pl.BlockSpec((None, bq, d), lambda b, j, i: (b, i, 0)),
                pl.BlockSpec((None, block_k, d), lambda b, j, i: (b, j, 0)),
                pl.BlockSpec((None, block_k, d), lambda b, j, i: (b, j, 0)),
                pl.BlockSpec((None, bq, d), lambda b, j, i: (b, i, 0)),
                pl.BlockSpec((None, bq, 1), lambda b, j, i: (b, i, 0)),
                pl.BlockSpec((None, bq, 1), lambda b, j, i: (b, i, 0)),
            ],
            out_specs=[
                pl.BlockSpec((None, bq, d), lambda b, j, i: (b, i, 0)),
                pl.BlockSpec((None, block_k, d), lambda b, j, i: (b, j, 0)),
                pl.BlockSpec((None, block_k, d), lambda b, j, i: (b, j, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
                jax.ShapeDtypeStruct((bh, sk, d), k.dtype),
                jax.ShapeDtypeStruct((bh, sk, d), v.dtype),
            ],
            scratch_shapes=[pltpu.VMEM((sq, d), jnp.float32),
                            pltpu.VMEM((block_k, d), jnp.float32),
                            pltpu.VMEM((block_k, d), jnp.float32)],
            interpret=interpret,
            name="flash_bwd_fused",
        )(q, k, v, do, lse3, delta)
        return dq, dk, dv

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal),
        grid=(bh, sq // block_q, sk // block_k),
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((None, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((None, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, block_q, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, d),
                               lambda b, i, j: (b, i, 0)),
        # native output dtype: accumulation lives in the f32 scratch,
        # so a bf16 dq never round-trips f32 gradients through HBM
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dq",
    )(q, k, v, do, lse3, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_dkdv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k),
        grid=(bh, sk // block_k, sq // block_q),
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((None, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((None, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((None, block_q, d), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((None, block_q, 1), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((None, block_q, 1), lambda b, j, i: (b, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((None, block_k, d), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sk, d), k.dtype),
            jax.ShapeDtypeStruct((bh, sk, d), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dkdv",
    )(q, k, v, do, lse3, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# Blockwise backward (plain JAX, O(S·block) memory) — portable oracle
# ---------------------------------------------------------------------------

def _blockwise_bwd(q, k, v, o, lse, do, scale, causal, block_k):
    """Standard flash-attention backward, scanning K/V blocks.

    All arrays [BH, S, D] (lse [BH, Sq]) in float32.
    """
    sq, sk = q.shape[1], k.shape[1]
    num_blocks = sk // block_k
    delta = jnp.sum(do * o, axis=-1)                      # [BH, Sq]
    q_pos = jnp.arange(sq)

    kb = jnp.moveaxis(k.reshape(-1, num_blocks, block_k, k.shape[-1]), 1, 0)
    vb = jnp.moveaxis(v.reshape(-1, num_blocks, block_k, v.shape[-1]), 1, 0)

    def body(carry, blk):
        dq, j = carry
        kblk, vblk = blk                                   # [BH, bk, D]
        s = jnp.einsum("bqd,bkd->bqk", q, kblk) * scale
        if causal:
            k_pos = j * block_k + jnp.arange(block_k)
            s = s + bw.causal_bias(q_pos, k_pos)
        p = jnp.exp(s - lse[..., None])                    # [BH, Sq, bk]
        dv = jnp.einsum("bqk,bqd->bkd", p, do)
        dp = jnp.einsum("bqd,bkd->bqk", do, vblk)
        ds = p * (dp - delta[..., None]) * scale
        dq = dq + jnp.einsum("bqk,bkd->bqd", ds, kblk)
        dk = jnp.einsum("bqk,bqd->bkd", ds, q)
        return (dq, j + 1), (dk, dv)

    (dq, _), (dk_b, dv_b) = jax.lax.scan(
        body, (jnp.zeros_like(q), jnp.int32(0)), (kb, vb))
    dk = jnp.moveaxis(dk_b, 0, 1).reshape(k.shape)
    dv = jnp.moveaxis(dv_b, 0, 1).reshape(v.shape)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom_vjp plumbing + public API
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, scale, causal, block_q, block_k, interpret,
           fused=None):
    o, _ = _pallas_forward(q, k, v, scale, causal, block_q, block_k,
                           interpret)
    return o


def _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret,
               fused=None):
    o, lse = _pallas_forward(q, k, v, scale, causal, block_q, block_k,
                             interpret)
    # named for selective remat (models/transformer.py remat_policy
    # "dots"): the backward needs these residuals, and without the tags
    # a policy that saves only dot_generals would re-run this whole
    # forward kernel inside the backward pass (q/k/v recompute from the
    # saved qkv projection for free; o/lse are the expensive part)
    o = checkpoint_name(o, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    return o, (q, k, v, o, lse)


def _flash_bwd(scale, causal, block_q, block_k, interpret, fused, res, do):
    q, k, v, o, lse = res
    # already native-dtype: the kernels accumulate in f32 scratch and
    # store in the inputs' dtypes
    return _pallas_backward(q, k, v, o, lse, do, scale, causal,
                            block_q, block_k, interpret, fused=fused)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, *, causal: bool = False,
                    scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    use_pallas=None, fused_bwd=None):
    """Multi-head attention, flash-style.  q, k, v: [B, S, H, D].

    ``block_q``/``block_k``: None = auto (the measured-fastest default,
    shrunk via gcd to divide the sequence — any seq length that worked
    before keeps working); explicit values must divide the sequence.

    ``use_pallas``: None = auto (Pallas on TPU, blockwise-JAX
    elsewhere); True/False = force; "interpret" = Pallas interpreter
    (CPU kernel validation).

    ``fused_bwd``: None = auto (single-pass backward kernel when its
    [Sq, D] dq scratch fits VMEM — see _dfused_kernel); True/False =
    force (benches A/B the two formulations).
    """
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    scale = float(scale) if scale is not None else 1.0 / (q.shape[-1] ** 0.5)
    if not use_pallas:
        bk = block_k if block_k is not None else math.gcd(
            DEFAULT_BLOCK_K, k.shape[1])
        return bw.blockwise_attention(q, k, v, causal=causal, scale=scale,
                                      block_k=bk)

    interpret = use_pallas == "interpret"
    b, sq, h, d = q.shape
    sk = k.shape[1]
    auto_q = block_q is None
    auto_k = block_k is None
    if block_q is None:
        block_q = math.gcd(DEFAULT_BLOCK_Q, sq)
    if block_k is None:
        block_k = math.gcd(DEFAULT_BLOCK_K, sk)
    block_q = max(min(block_q, sq), 1)
    block_k = max(min(block_k, sk), 1)
    # Odd seq lengths (not a multiple of 8) gcd-shrink below the TPU
    # (8, 128) tile minimum.  A block equal to the full array dim is
    # the one sub-8 shape Mosaic accepts (block == array dims), so
    # auto-selection falls back to a single whole-sequence block —
    # bounded by the scores-tile VMEM budget below; larger odd lengths
    # raise with the pad advice.
    _SCORES_ELEMS_MAX = 2 * 1024 * 1024  # 8 MB f32 of ~16 MB VMEM
    if not interpret:
        if auto_q and block_q < 8 and sq * block_k <= _SCORES_ELEMS_MAX:
            block_q = sq
        if auto_k and block_k < 8 and block_q * sk <= _SCORES_ELEMS_MAX:
            block_k = sk
    sub8_ok = lambda bq, bk: (bq >= 8 or bq == sq) and (bk >= 8 or bk == sk)
    if not interpret and not sub8_ok(block_q, block_k):
        # DEFAULT blocks are powers of two, so the gcd auto-shrink
        # lands on a power of two: anything below 8 violates the TPU
        # (8, 128) tile rule (unless block == array dim) and would die
        # opaquely in Mosaic lowering
        raise ValueError(
            f"auto block sizes ({block_q}, {block_k}) fell below the "
            f"TPU tile minimum of 8 for seq lengths ({sq}, {sk}); pad "
            f"the sequence to a multiple of 8 or pass explicit "
            f"block_q/block_k")
    if sq % block_q or sk % block_k:
        raise ValueError(
            f"block sizes ({block_q}, {block_k}) must divide the seq "
            f"lengths ({sq}, {sk})")

    def merge(x):  # [B, S, H, D] → [B·H, S, D]
        return jnp.swapaxes(x, 1, 2).reshape(b * h, x.shape[1], d)

    o = _flash(merge(q), merge(k), merge(v), scale, causal, block_q,
               block_k, interpret, fused_bwd)
    return jnp.swapaxes(o.reshape(b, h, sq, d), 1, 2)
