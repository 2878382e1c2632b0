"""Decoder-only transformer LM — the long-context workload.

No reference equivalent (the reference is vision-only, SURVEY.md §5.7);
this is the model family that exercises the framework's first-class
long-context machinery: the Pallas flash-attention kernel
(`ops.flash_attention`) on a single chip, and ring attention over the
'seq' mesh axis (`parallel.ring_attention`) when the sequence dimension
is sharded (`--seq_parallelism N`).

Design (TPU-first):
  - pre-LN blocks, GELU MLP — everything fuses into the two MXU matmuls
    per sublayer under XLA.
  - causal attention via the flash kernel: O(S·D) HBM traffic instead
    of an [S, S] score matrix.
  - `seq_axis` set ⇒ the module is running *inside* `shard_map` with
    its sequence dimension sharded: attention switches to the K/V ring
    (ICI neighbor exchange overlapped with compute) and position
    embeddings are offset by the shard's global position.
  - `model_axis` set ⇒ Megatron-style tensor parallelism: qkv and fc1
    are column-parallel (heads / ff dim sharded — the param arrays this
    module receives inside shard_map are the local shards), out and fc2
    are row-parallel with a `psum` forward; `tp_region` (identity
    forward, psum backward) guards each region entry so upstream
    LayerNorm/embedding gradients stay correct.  Composes freely with
    the seq ring (heads never communicate during attention).
  - optional `remat` wraps each block in `jax.checkpoint`, trading
    FLOPs for HBM (the standard long-context memory lever).
  - `remat_policy="dots"` is the selective variant: matmul outputs and
    the flash-attention output stay saved (no MXU work is recomputed),
    only LayerNorm/GELU/bias-add intermediates recompute in the
    backward.  A cheaper *memory* lever than full remat, and not a
    speed lever when memory fits: XLA:TPU materializes the recomputed
    elementwise ops rather than fusing them into consuming matmul
    operands (its tokens/s are not measured on this installation).  Both
    remat flavors exist for larger batches, more optimizer state, or
    smaller HBM.

Use `param_partition_specs(params)` for the per-leaf PartitionSpecs
that shard a full (replicated-shape) param tree onto the 'model' axis.
"""

from __future__ import annotations

from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from dtf_tpu.ops.flash_attention import flash_attention
from dtf_tpu.ops.paged_attention import (expand_kv_heads,
                                         latent_chunk_attention,
                                         paged_attention_auto,
                                         paged_chunk_attention, write_pages)
from dtf_tpu.parallel.collectives import tp_psum, tp_region
from dtf_tpu.parallel.ring_attention import ring_attention


def remat_policy(name: str):
    """Named jax.checkpoint policies for the transformer families.

    "dots": save every dot_general result plus the flash-attention
    output (tagged `attn_out` in CausalSelfAttention) — nothing the MXU
    produced is recomputed; everything elementwise (LayerNorm, GELU,
    bias adds, residual sums) is, fused into the backward kernels."""
    if name == "dots":
        cp = jax.checkpoint_policies
        return cp.save_from_both_policies(
            cp.checkpoint_dots,
            # attn_out: the kernel output as seen by the block;
            # flash_out/flash_lse: the custom_vjp residuals named inside
            # ops.flash_attention._flash_fwd — without them the policy
            # would re-run the flash forward in the backward pass
            cp.save_only_these_names("attn_out", "flash_out", "flash_lse"))
    raise ValueError(f"unknown remat_policy {name!r}; choose 'dots'")


def paged_cache_attention(module, q, k, v, cache_index, block_table, *,
                          flash_prefill: bool = False,
                          window_pages: Optional[int] = None,
                          window: Optional[int] = None,
                          scale: Optional[float] = None,
                          value_lanes: Optional[int] = None,
                          one_row: bool = False, walks: bool = False,
                          expand=None):
    """Write-then-attend against the shared page pool — what every
    decoder family's attention does with the paged cache, called from
    inside the attention module's ``@nn.compact`` body (``module`` owns
    the pool variables and names ``kv_page_size``, ``kv_pool_pages``,
    ``use_pallas``).

    ``v`` None is the LATENT cache: ``k`` [B, S, W] is the one row a
    token that every query head attends (q [B, S, Hq, W], absorbed), its
    first ``value_lanes`` lanes are the value, ``scale`` the score's; one
    pool ``[P, page, W]``, written once and read once a call, every chunk
    (the first too) through the paged kernel; returns
    [B, S, Hq, value_lanes].  ``expand`` ``(kv_b [value_lanes, Hq, nope +
    Dv], nope)``: the call attends EXPANDED instead
    (``ops.paged_attention.latent_chunk_attention``) — q [B, S, Hq, nope +
    rope] is then each head's own query, not absorbed, and the result
    [B, S, Hq, Dv]; the pool is written as ever.

    q [B, S, Hq, Dh]; k, v [B, S, Hkv, Dh] with ``Hq`` a multiple of
    ``Hkv`` (grouped-query heads: query head ``i`` reads KV head
    ``i // (Hq // Hkv)``); k already carries its positions (a rotary
    family rotates it before this call: the pool holds what is
    attended).  ``window`` (static, tokens; None = the whole history) is
    the layer's own attention window.

    ``one_row``: K and V of a head share ONE pool row, ``[k | v]`` — one
    pool ``[P, page, Hkv, 2 * Dh]`` a layer, for heads half a lane tile
    wide (64: two pools of 64-lane rows would each be stored in 128 lanes,
    twice the HBM and twice the DMA of every page).  ``walks``: a
    continuation chunk over K and V pools attends through the walk
    (``ops.paged_attention.paged_chunk_attention``) — the caller's
    decision, taken where its heads and window are known
    (``GroupedQueryAttention.walks``; ``CausalSelfAttention``'s heads are
    not grouped and never walk)."""
    s = q.shape[1]
    # paged cache: one shared pool per K/V, sized by the module
    # attrs (NOT by the init call's shapes — admission capacity
    # is a pool property, not a per-slot reservation)
    pool_shape = (module.kv_pool_pages, module.kv_page_size) + k.shape[2:]
    aligned = s > 1 and s % module.kv_page_size == 0
    if v is None:
        paged_latent = module.variable(
            "cache", "paged_latent", jnp.zeros, pool_shape, k.dtype)
        if module.is_initializing():
            return jnp.zeros(q.shape[:-1] + (
                value_lanes if expand is None
                else expand[0].shape[-1] - expand[1],), q.dtype)
        paged_latent.value = write_pages(
            paged_latent.value, k, block_table, cache_index,
            page_aligned=aligned)
        if expand is not None:
            return latent_chunk_attention(
                q, k, expand[0], paged_latent.value, block_table,
                cache_index, rank=value_lanes, nope=expand[1], scale=scale,
                use_pallas=module.use_pallas)
        return paged_attention_auto(
            q, paged_latent.value, None, block_table, cache_index,
            window_pages=window_pages, use_pallas=module.use_pallas,
            scale=scale, value_lanes=value_lanes)

    def flash(k, v):
        return flash_attention(q, *expand_kv_heads(k, v, q.shape[2]),
                               causal=True, use_pallas=module.use_pallas)

    if one_row:
        paged_kv = module.variable(
            "cache", "paged_kv", jnp.zeros,
            pool_shape[:-1] + (2 * k.shape[-1],), k.dtype)
        if module.is_initializing():
            return flash(k, v)
        paged_kv.value = write_pages(
            paged_kv.value, jnp.concatenate([k, v], -1), block_table,
            cache_index, page_aligned=aligned)
        if flash_prefill and (window is None or s <= window):
            return flash(k, v)
        return paged_attention_auto(
            q, paged_kv.value, None, block_table, cache_index,
            window_pages=window_pages, use_pallas=module.use_pallas,
            window=window)
    paged_key = module.variable(
        "cache", "paged_key", jnp.zeros, pool_shape, k.dtype)
    paged_value = module.variable(
        "cache", "paged_value", jnp.zeros, pool_shape, v.dtype)
    if module.is_initializing():
        # init trace: only the pool variables' shapes matter,
        # but keep the math valid (plain causal attention)
        return flash(k, v)
    # write-then-attend (a query sees its own chunk's keys).
    # Prefill chunks (S a page multiple; page-aligned starts by
    # engine construction) scatter whole pages; decode steps
    # (S = 1) scatter single token rows
    paged_key.value = write_pages(
        paged_key.value, k, block_table, cache_index,
        page_aligned=aligned)
    paged_value.value = write_pages(
        paged_value.value, v, block_table, cache_index,
        page_aligned=aligned)
    if flash_prefill and (window is None or s <= window):
        # first prefill chunk (cache_index == 0, engine
        # invariant): there is no prefix to gather — the
        # chunk IS the whole attended history, plain causal
        # self-attention through the flash kernel at
        # O(S·D) HBM traffic instead of an [S, L] gather
        # (a chunk no longer than the window sees all of itself)
        return flash(k, v)
    if walks:
        # a continuation chunk whose rows a KV head fill a tile of the
        # flash forward: its pages walked through that kernel, a page
        # read once a tile (ops.paged_attention.paged_chunk_attention)
        return paged_chunk_attention(
            q, k, v, paged_key.value, paged_value.value, block_table,
            cache_index, use_pallas=module.use_pallas)
    # paged_attention_auto: the Pallas flash-decode
    # kernel on TPU (default-on — each row's live pages
    # streamed from the pool as stored, ids from the
    # block table in-kernel; no gathered window, the
    # loop ends at the row's own last page), the
    # gather oracle elsewhere.  window_pages (STATIC,
    # decode.py computes it from the chunk's start)
    # trims the GATHER path to the pages the chunk can
    # actually see: continuation-chunk attention costs
    # O(S · progress), so total prefill work is
    # O(prompt²/2) regardless of the pool's logical
    # capacity.  None (the decode step) attends the
    # full per-slot window — lengths vary per row
    return paged_attention_auto(
        q, paged_key.value, paged_value.value,
        block_table, cache_index,
        window_pages=window_pages,
        use_pallas=module.use_pallas, window=window)


class CausalSelfAttention(nn.Module):
    num_heads: int
    dtype: Any = jnp.float32
    seq_axis: Optional[str] = None   # set when seq dim is mesh-sharded
    model_axis: Optional[str] = None  # set when heads are mesh-sharded
    use_pallas: Any = None           # None=auto; False forces blockwise-JAX
    # serving: maintain a KV cache ('cache' collection) and attend
    # incrementally — see TransformerLM.decode.  The cache is a SHARED
    # page pool [kv_pool_pages, kv_page_size, H, Dh] per K/V plus a
    # caller-owned block table — see TransformerLM.kv_page_size and
    # ops.paged_attention for the layout/invariants
    decode: bool = False
    kv_page_size: Optional[int] = None
    kv_pool_pages: Optional[int] = None

    @nn.compact
    def __call__(self, x, cache_index=None, block_table=None,
                 flash_prefill: bool = False,
                 window_pages: Optional[int] = None):
        b, s, d = x.shape
        head_dim = d // self.num_heads
        heads = self.num_heads
        if self.decode and self.seq_axis is not None:
            # checked before the ring touches the (unbound) axis.
            # model_axis DOES compose with decode: serving tensor
            # parallelism shards heads (and the KV page pool's head
            # dim) over 'model' — the attention math is per-head, so
            # each shard decodes its local heads and the row-parallel
            # out projection psums exactly as in training
            raise ValueError(
                "decode mode (KV cache) does not compose with seq_axis "
                "sharding (ring attention)")
        if self.model_axis is not None:
            x = tp_region(x, self.model_axis)
            # lax.psum of a Python scalar is the static axis size, so
            # the local head count is a concrete feature dim
            mp = jax.lax.psum(1, self.model_axis)
            if heads % mp:
                raise ValueError(
                    f"num_heads {heads} not divisible by "
                    f"model_parallelism {mp}")
            heads //= mp
        qkv = nn.DenseGeneral((3, heads, head_dim), dtype=self.dtype,
                              name="qkv")(x)
        q, k, v = (qkv[..., i, :, :] for i in range(3))  # [B, S, Hloc, Dh]
        if self.decode:
            if cache_index is None or block_table is None:
                raise ValueError("decode mode needs cache_index [B] "
                                 "and block_table [B, M], both int32")
            o = paged_cache_attention(
                self, q, k, v, cache_index, block_table,
                flash_prefill=flash_prefill, window_pages=window_pages)
        elif self.seq_axis is not None:
            # sequence-parallel: K/V rotate around the 'seq' ring; every
            # query still attends to the full global sequence
            o = ring_attention(q, k, v, axis_name=self.seq_axis, causal=True)
        else:
            o = flash_attention(q, k, v, causal=True,
                                use_pallas=self.use_pallas)
        # tag for remat_policy="dots": the Pallas kernel's output is not
        # a dot_general, so checkpoint_dots alone would recompute the
        # whole flash forward in the backward pass — saving it by name
        # keeps the policy's "no MXU recompute" property
        o = checkpoint_name(o, "attn_out")
        o = o.reshape(b, s, -1)
        # row-parallel: each shard contributes its heads' slice; no bias
        # (a replicated bias would be summed mp times by the psum)
        out = nn.Dense(d, dtype=self.dtype, use_bias=False, name="out")(o)
        if self.model_axis is not None:
            # g operator: sum forward, identity backward (a raw psum
            # would scale cotangents by mp under shard_map AD)
            out = tp_psum(out, self.model_axis)
        return out


class Block(nn.Module):
    num_heads: int
    d_ff: int
    dtype: Any = jnp.float32
    seq_axis: Optional[str] = None
    model_axis: Optional[str] = None
    use_pallas: Any = None
    decode: bool = False
    kv_page_size: Optional[int] = None
    kv_pool_pages: Optional[int] = None

    @nn.compact
    def __call__(self, x, cache_index=None, block_table=None,
                 flash_prefill: bool = False,
                 window_pages: Optional[int] = None):
        d = x.shape[-1]
        h = nn.LayerNorm(dtype=self.dtype, name="ln1")(x)
        x = x + CausalSelfAttention(
            self.num_heads, dtype=self.dtype, seq_axis=self.seq_axis,
            model_axis=self.model_axis, use_pallas=self.use_pallas,
            decode=self.decode, kv_page_size=self.kv_page_size,
            kv_pool_pages=self.kv_pool_pages,
            name="attn")(h, cache_index, block_table, flash_prefill,
                         window_pages)
        h = nn.LayerNorm(dtype=self.dtype, name="ln2")(x)
        d_ff = self.d_ff
        if self.model_axis is not None:
            h = tp_region(h, self.model_axis)
            mp = jax.lax.psum(1, self.model_axis)
            if d_ff % mp:
                raise ValueError(
                    f"d_ff {d_ff} not divisible by model_parallelism {mp}")
            d_ff //= mp
        h = nn.Dense(d_ff, dtype=self.dtype, name="fc1")(h)  # column
        h = nn.gelu(h)
        h = nn.Dense(d, dtype=self.dtype, use_bias=False, name="fc2")(h)  # row
        if self.model_axis is not None:
            h = tp_psum(h, self.model_axis)  # g operator (see attn)
        return x + h


def rows_at(x, pos):
    """x [B, S, d], pos [B] int32 -> [B, 1, d]: row ``pos[b]`` of each
    ``x[b]`` (clamped into the call, as ``dynamic_slice`` clamps).  What a
    model's final norm and head take when handed ``head_pos``: both are a
    position's own, so one row in is that row's logits out.

    The row is read as a sum over S under a mask (exact: one term and
    zeros), not as a ``dynamic_slice``: the TPU compiler moves a slice back
    through the residual adds and keeps the last layers' [S, d] products
    alive to the program's end for it (seven of 16.8e6 B in a 1,024-token
    chunk of width 4,096); a reduction ends the last layer's product as the
    norms' sums of squares end every other layer's."""
    pos = jnp.clip(pos, 0, x.shape[1] - 1)
    hit = jnp.arange(x.shape[1])[None, :, None] == pos[:, None, None]
    return jnp.sum(jnp.where(hit, x, 0), axis=1, keepdims=True)


class TransformerLM(nn.Module):
    """Next-token LM.  __call__(tokens [B, S] int32, train) -> logits
    [B, S, vocab] (f32 — softmax precision, like the ResNets' fp32
    softmax cast, reference resnet_model.py:385-388)."""

    vocab_size: int
    num_layers: int = 12
    d_model: int = 512
    num_heads: int = 8
    d_ff: int = 2048
    max_seq_len: int = 2048
    dtype: Any = jnp.float32
    seq_axis: Optional[str] = None
    model_axis: Optional[str] = None
    # column-parallel lm_head over `model_axis`: this module then
    # returns LOCAL logits [B, S, V/mp] and the loss must be the
    # collective softmax CE (train.loop.sharded_cross_entropy) — the
    # full [B, S, V] logits never materialize (Megatron's
    # vocab-parallel output layer)
    shard_vocab: bool = False
    use_pallas: Any = None
    remat: bool = False
    # None = save everything jax's autodiff wants (plain remat if
    # `remat`); "dots" = selective remat per the module docstring
    remat_policy: Optional[str] = None
    # Serving mode (serve/decode.py Decoder drives this): every
    # attention keeps a SHARED [kv_pool_pages, kv_page_size, H, Dh] page
    # pool per K/V in the 'cache' collection, and __call__ takes
    # `cache_index` [B] int32 — the per-row write offset (each request's
    # current length, which is what makes slot-based continuous batching
    # possible) — and `block_table` [B, M] int32 (the engine-allocated
    # page ids mapping each row's logical positions into the pool —
    # ops.paged_attention has the layout and the scratch-page
    # invariant), plus `flash_prefill` (static bool: the chunk starts at
    # position 0, so attention runs causal-only through the flash kernel
    # with no gather).  HBM scales with tokens in flight, not
    # num_slots × max_seq_len.  Composes with model_axis (serving tensor
    # parallelism: heads + KV pool sharded over 'model', run inside
    # shard_map); incompatible with seq_axis sharding and shard_vocab.
    # decode=True requires both page fields.
    # `head_pos` [B] int32 (a prefill chunk's sampled offset within the
    # call): the final norm and the head run on that one position a row
    # and the logits are [B, 1, vocab].
    decode: bool = False
    kv_page_size: Optional[int] = None
    kv_pool_pages: Optional[int] = None

    @nn.compact
    def __call__(self, tokens, train: bool = False, cache_index=None,
                 block_table=None, flash_prefill: bool = False,
                 window_pages: Optional[int] = None, head_pos=None):
        del train  # no dropout/BN: LN only, same train/eval behavior
        b, s_local = tokens.shape
        x = nn.Embed(self.vocab_size, self.d_model, dtype=self.dtype,
                     name="embed")(tokens)
        # learned positions; under seq sharding each shard takes its
        # global slice of the table
        pos_table = self.param(
            "pos_embed", nn.initializers.normal(0.02),
            (self.max_seq_len, self.d_model))
        if self.decode:
            if self.shard_vocab:
                raise ValueError("decode mode does not compose with "
                                 "shard_vocab (single-device serving)")
            if cache_index is None:
                raise ValueError("decode mode needs cache_index [B] int32")
            if self.kv_page_size is None or self.kv_pool_pages is None:
                raise ValueError(
                    "decode mode needs kv_page_size and kv_pool_pages "
                    "(the KV page pool's shape)")
            # per-row global positions; clamp so a padded prefill chunk
            # can't index past the table (those rows' logits are unused)
            pos_idx = jnp.minimum(
                cache_index[:, None] + jnp.arange(s_local)[None, :],
                self.max_seq_len - 1)
            pos = jnp.take(pos_table, pos_idx, axis=0)  # [B, S, d]
        else:
            offset = 0
            if self.seq_axis is not None:
                offset = jax.lax.axis_index(self.seq_axis) * s_local
            pos = jax.lax.dynamic_slice_in_dim(pos_table, offset, s_local)
        x = x + pos.astype(self.dtype)

        block = Block
        if self.remat_policy is not None:
            block = nn.remat(Block, policy=remat_policy(self.remat_policy))
        elif self.remat:
            block = nn.remat(Block)
        for i in range(self.num_layers):
            x = block(self.num_heads, self.d_ff, dtype=self.dtype,
                      seq_axis=self.seq_axis, model_axis=self.model_axis,
                      use_pallas=self.use_pallas, decode=self.decode,
                      kv_page_size=self.kv_page_size,
                      kv_pool_pages=self.kv_pool_pages,
                      name=f"block{i}")(x, cache_index, block_table,
                                        flash_prefill, window_pages)
        if head_pos is not None:
            x = rows_at(x, head_pos)
        x = nn.LayerNorm(dtype=self.dtype, name="ln_f")(x)
        vocab = self.vocab_size
        if self.shard_vocab and self.model_axis is not None:
            mp = jax.lax.psum(1, self.model_axis)
            if vocab % mp:
                raise ValueError(
                    f"vocab_size {vocab} not divisible by "
                    f"model_parallelism {mp}")
            vocab //= mp
            # x is fully replicated here (the last block exited through
            # tp_psum) but its cotangent arrives vocab-shard-partial —
            # the f operator restores the full upstream gradient
            x = tp_region(x, self.model_axis)
        logits = nn.Dense(vocab, dtype=self.dtype, name="lm_head")(x)
        return logits.astype(jnp.float32)


def param_partition_specs(params, model_axis: str,
                          shard_vocab: bool = False):
    """PartitionSpec tree sharding a full TransformerLM param tree onto
    the tensor-parallel axis: qkv kernel/bias on the head dim, fc1
    kernel/bias on the ff dim, out/fc2 kernels on their input (row)
    dim, and (with ``shard_vocab``) the lm_head on its vocab (column)
    dim; everything else replicated."""
    from jax.sharding import PartitionSpec as P

    from dtf_tpu.models.partition import partition_specs

    def rule(keys, last, leaf):
        if "qkv" in keys:
            # kernel [d, 3, H, Dh] / bias [3, H, Dh]: shard H
            return (P(None, None, model_axis, None) if last == "kernel"
                    else P(None, model_axis, None))
        if "fc1" in keys:
            # kernel [d, ff] / bias [ff]: shard ff
            return (P(None, model_axis) if last == "kernel"
                    else P(model_axis))
        if ("out" in keys or "fc2" in keys) and last == "kernel":
            return P(model_axis, None)   # row-parallel input dim
        if shard_vocab and "lm_head" in keys:
            # kernel [d, V] / bias [V]: shard V (column-parallel)
            return (P(None, model_axis) if last == "kernel"
                    else P(model_axis))
        return P()

    return partition_specs(params, rule)
