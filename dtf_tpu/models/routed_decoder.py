"""Decoder-only LM whose layers follow a per-layer description and whose
MLP is a dropless routed-expert layer — a family that is served
(``serve/decode.py``, ``serve/engine.py``), not trained, in this repo.

What a layer is comes from fields, all plain values a configuration file
can carry.  Pre-norm RMSNorm blocks (``norm_unit_offset``: every norm
scales by 1 + its learned vector), no bias on any projection, an output
head of its own (``tie_head``: the embedding matrix itself).  There is no
position table: a position is the row's
``cache_index`` plus the offset in the chunk, so ``max_seq_len`` bounds
only what the serving engine admits.

The fields are read in ONE place, ``RoutedDecoderLM.layer_specs()``, which
describes every layer (:class:`LayerSpec`): its mixer's KIND with that
kind's class's arguments by name, and its MLP.  The kinds (``_KINDS``) are
``attention`` (whole heads), ``summary`` (whole heads under
``summary_window``), ``latent`` (latent attention), ``indexed_latent``
(latent attention under an ``indexer``) — the four a configuration's
``layer_mixer`` calls ``attention``; which one follows from
``kv_lora_rank``, ``summary_window`` and ``indexer`` — and ``short_conv``,
``linear_delta``, ``sparse_block``, ``lightning``.  The block builds the
kind's module from the description, the counts go by it (``COUNTS``), and
the form a call's static shape chooses is the kind's own to answer
(``GroupedQueryAttention.walks``, ``LatentAttention.expands``): the layer
goes by the answer where it attends and the model asks the same question to
name a count.

**Attention kind.**  ``kv_lora_rank`` None — whole heads: ``num_heads``
query heads share ``num_kv_heads`` KV heads of ``head_dim`` (grouped-query
attention: query head ``i`` reads KV head ``i // (num_heads //
num_kv_heads)``), and two tuples, one entry a layer, say

  ``layer_window[l]``  True: the layer attends the last ``window``
                       positions up to the query's own; False: the whole
                       history.
  ``layer_rope[l]``    True: rotate-half rotary positions (``rope_theta``)
                       on q and k, applied to k BEFORE it is written to
                       the cache; False: no positional signal at all.

The cache is K and V pools ``[P, page, Hkv, Dh]``.  ``rotary_dim`` set:
only the first that many lanes of a head turn, the others carry no
position; ``attention_output_gate``: the query projection carries a gate a
(head, lane) — columns ``[q | k | v | gate]`` — and the heads' outputs are
multiplied by its sigmoid before ``out``; ``qk_norm``'s two norms follow
``norm_unit_offset``.  ``kv_lora_rank`` set —
latent attention (:class:`LatentAttention`) in every ATTENTION layer, over
the whole history: the cache is ONE pool ``[P, page, W]`` a layer whose row is a
token's normed latent and its one rotary key (``kv_lora_rank +
qk_rope_head_dim`` values in ``latent_row_lanes`` stored lanes), read once
a call for scores and values; in decode mode a step attends ABSORBED
through the paged kernel and a chunk long enough to repay it EXPANDED —
its own keys, and the pages under its start, through ``kv_b`` once and a
flash forward (:class:`LatentAttention`).  ``q_lora_rank`` None beside it
projects the queries directly; ``q_head_norm`` norms each query head
before the rotation; ``attention_head_gate`` scales a head's output by a
sigmoid gate.

``summary_window`` set (whole heads, every layer of the ``full`` kind) — a
WINDOW OF EXACT KEYS BESIDE SUMMARIES: a query attends the exact keys of
its own window of that many positions, aligned to its multiples, and one
learned summary a ``summary_chunk`` positions of every window before it
(``ops/window_summary.py``: ``a_m = softmax_m(k_m . phi)``, ``k~ = sum a_m
k_m + mu``, ``v~ = sum a_m v_m``, ``summary_phi`` and ``summary_mu`` [Hkv,
Dh] a layer).  The summaries are rows of the SAME K and V pools and a
row's table is COMPACT: the summaries' pages first, then the open window's,
so the attended rows are a prefix of the table and the paged write, the
paged kernel and a chunk's causal rule run on ``compact_index(position)``
unchanged, while RoPE keeps the true position.  The caller closes a window
(``RoutedDecoderLM.close_windows``, a program of its own: one
``window_compact`` call a layer) before the first write into the next one;
a row then grows by the summaries' pages a window, not by the window's.

``indexer`` set (latent attention, every layer) — LEARNED SPARSE ATTENTION:
``layer_indexer[l]`` is ``full`` — the layer has a lightning indexer with
parameters of its own (:class:`LightningIndexer`, ``ops/index_select.py``:
index queries off the query latent, ONE index key a token in a pool leaf
of its own, ``index_key``) and its queries attend the ``top`` rows it
scores highest — or ``shared``: the layer attends over the choice of the
nearest ``full`` layer below.  That choice (``chosen``) is the one value
that flows BETWEEN layers: a block takes it and hands it on.

**Mixer kind.**  ``layer_mixer[l]`` (one entry a layer; shorter tuples
repeat) is ``attention`` — the kind above — or ``short_conv``
(:class:`ShortConv`): a double-gated depthwise causal convolution of
``conv_taps`` taps, no attention and no pages.  What a request carries
through such a layer is the last ``conv_taps - 1`` inputs of the filter,
and it RIDES THE PAGE TABLE: one leaf ``[P, (conv_taps - 1) * d]`` a layer
in the ``"cache"`` collection, indexed by page id like every pool, whose
entry for page ``p`` is the running state at the newest token written in
``p`` — a full page's entry is the snapshot at its end.  A call reads its
carry from the entry of the page that holds position ``cache_index - 1``
and writes the entry of every page it touches, so page copies, prefix
sharing and migration carry the state as they carry K and V; what they
cannot do is REPLAY a token on a copied page (the entry is already past
it: ``carries_state``, which the engine reads).  Such a model's call
takes ``last_pos`` [B]: the offset of each row's last real token, where
a tail-padded final chunk's entry is taken.  K and V of a head exactly
half a lane tile wide (64) share one pool row ``[k | v]``: the layout
follows from ``head_dim`` and is nobody's to choose;
``qk_norm``: RMSNorm over each head of q and of k, a learned scale each,
before the rotation.

The third kind, ``linear_delta`` (:class:`LinearDelta`,
``ops/linear_state.py``): delta-rule linear attention with a decay a
channel — ``linear_heads`` heads whose state is a ``linear_head_dim x
linear_head_dim`` MATRIX each, rewritten by every token, behind three
short filters of ``linear_conv_taps`` taps.  Its state rides the page
table by the same contract, in two leaves a layer: ``linear_state`` ``[P,
H, D, D]`` (the matrices, in ``dtype``) and ``conv_state`` ``[P,
(taps - 1) * 3 * H * D]``.  A matrix entry is a thousand times a filter
entry, so a page must be LARGE for an entry a page to be affordable (pages
of 512-2,048 tokens, where a page of tokens weighs what a state weighs):
the serving engine's page size is the deployment's to choose and nothing
here fixes it.  A decode step advances a row's matrices in the kernel
``linear_state_decode`` (the pool aliased in place; it takes the token's
``q``, ``k``, ``beta k``, ``exp(a)`` and ``v`` as ROWS ``[B, H, D]`` and
returns ``o`` so: a head's two reductions are one MXU product of its
matrix AS STORED against the bfloat16 pieces of ``exp(a) k`` and ``exp(a)
q``, float32 arithmetic whose form follows the pool's dtype), a chunk of
whole pages through the blocked form, the full forward outside decode
mode through the token-by-token recurrence.  The GATED form of the same
layer is three fields: ``linear_decay`` ``head`` — the log decay is ONE
SCALAR a head, ``-exp(a_log) * softplus(h W_a + dt_bias)``, no floor, ``[b
| a]`` one projection; ``linear_key_heads`` — fewer key heads than value
heads, value head ``i`` reading the q and k of key head ``i //
(linear_heads // linear_key_heads)`` (the projection, the filter and the
``conv_state`` entry are ``2 * key + value`` channels wide, not ``3 n``);
``linear_gate`` ``silu`` — the gate's input ``z`` rides the one projection
``[q | k | v | z]`` and ``silu(z)`` multiplies the plain-weight norm.  The
state forms and the kernel take a decay a channel and a key row a value
head: such a layer hands them the broadcast decay and the repeated rows, so
its decode step is the same kernel under the same name.  Mixers of
different kinds stand
beside EITHER attention kind: ``layer_mixer`` decides a layer,
``kv_lora_rank`` what its attention layers are (``short_conv`` alone still
wants whole heads).

Two further kinds, each with its sizes in ONE tuple.  ``sparse_block``
(:class:`SparseBlockAttention`, ``sparse``; ``ops/block_select.py``):
grouped-query attention — normed q and k, no positions, an output gate —
that reads a SUBSET of the row's cache, chosen query by query: past
``dense_len`` the first block, the window's blocks and the ``top`` best of
the others by the query heads' scores against POOLED keys, a third cache
leaf (``pooled_key``, one row a ``stride`` tokens); at or under it
everything.  The decode step copies the chosen blocks alone (a table ``[B,
Hkv, W]`` of blocks that are parts of a page).  A chunk gives every token
its own choice as MEMBERSHIP — a small integer a (query, KV head, unit of
blocks) — and a tile of queries streams the blocks its queries read once,
each query masking what it did not choose (``paged_tile_attention``).
``lightning`` (:class:`LightningAttention`, ``lightning``):
linear attention with a constant decay a head and no write gate — the
no-erase forms of ``ops/linear_state.py`` — whose matrix state is
``linear_delta``'s leaf under the same contract.  ``mup`` scales the
embedding, every residual branch and the final hidden rows.

**MLP kind.**  The first ``num_dense_layers`` layers have a dense gated
MLP of ``dense_width`` and no router.  The others route: ``num_experts``
gated experts of ``expert_width`` (``activation`` relu | silu) of which
every token takes its ``experts_per_token`` best, by ``routing``

  ``softmax_topk``   the largest router logits, softmax over those;
  ``sigmoid_bias``   scores ``sigmoid(logits)``; the choice is the largest
                     of score + a learned bias, the weights the chosen
                     scores WITHOUT it over their sum (plus
                     ``routing_sum_eps``) times ``routed_scale``

from the norm ``router_input`` names (``pre_attention``: the layer's
first norm, before attention runs; ``post_attention``: the second), and
add a shared expert of ``shared_expert_width`` (0: none) for every token
(``shared_expert_gate``: its output times ``sigmoid(h2 w)``, ONE scalar a
token).  ``route_groups`` > 1 puts a GROUP LIMIT on the ``sigmoid_bias``
choice: the
experts are that many runs of consecutive ids, a group's score is the sum
of its 2 largest biased scores, and a token chooses its
``experts_per_token`` within its ``route_groups_kept`` best groups.
``experts_held`` (first id, count) tells the expert layer WHICH experts
this device holds of the ``num_experts`` the router still chooses among
(one chip's share of a layer that several chips hold together): its
weights are those experts' alone, a chosen pair whose expert is absent is
dropped before the grouped matmul and adds nothing, and nothing stands in
for the absent devices or their exchange — the layer's output is this
device's partial sum plus the shared expert.

The layer, for ``x [S, d]``:

  1. ``h = RMSNorm(x)``                       (router here: pre_attention)
  2. ``x += attention(h)``   (kind above; the paged cache is
     ``models.transformer.paged_cache_attention``, shared with
     ``CausalSelfAttention``) or ``x += short_conv(h)``
  3. ``h2 = RMSNorm(x)``                      (router here: post_attention)
  4. dense: ``x += down(act(gate h2) * up h2)``; routed: ``x +=
     shared(h2) + sum_e w_e * down_e(act(gate_e h2) * up_e h2)`` — every
     chosen (token, expert) pair is computed: no capacity, nothing dropped
     (:func:`routed_experts`).

Parameters live in ``param_dtype`` (bfloat16 for serving: an f32 copy of
the experts would not fit beside the cache, and a per-step cast re-reads
every weight; the score bias alone is float32); matmuls take their inputs
in ``dtype`` and accumulate in f32; the residual stream, the norms, the
router, softmax and the combine are f32.  The f32 stream is what keeps
the routing stable: with a bf16 stream the k-th and (k+1)-th router
logits trade places against the f32 reference often enough that those
flips alone were 0.008-0.019 of the 0.014-0.029 the served logits read
against it (v5e, 12 seeds, whole heads), and it costs [tokens, d_model]
words a layer beside 755e6 bytes of experts.

Every apply also yields counts, summed over layers, in the ``"stats"``
collection when the caller makes it mutable: the serving engine puts them
on its spans when tracing is on, under ``call_stats_names`` of the call's
shape (an expanded chunk's ``latent_tokens_expanded`` where a step has
``latent_tokens_read``).  What a model counts — the names in order and the
arithmetic, side by side — is its row of ``COUNTS``, by what its attention
layers read; ``stats_names`` looks it up.  Under ``experts_held`` the count
``assignments`` is the pairs COMPUTED HERE (their expert is held), and
``experts_touched`` / ``expert_load_max`` run over the held experts.
"""

from __future__ import annotations

import collections
import math
from typing import Any, NamedTuple, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from dtf_tpu.models.transformer import paged_cache_attention, rows_at
from dtf_tpu.ops import (block_select, index_select, linear_state,
                         window_summary)
from dtf_tpu.ops.flash_attention import flash_attention
from dtf_tpu.ops.paged_attention import (cached_attention, expand_kv_heads,
                                         chunk_rows_walked, chunk_walks,
                                         gather_pages,
                                         latent_chunk_attention,
                                         latent_expands,
                                         latent_rows_expanded,
                                         latent_sparse_attention,
                                         latent_sparse_chunk,
                                         latent_sparse_decode,
                                         paged_attention_auto,
                                         paged_block_attention,
                                         paged_tile_attention, tile_keys,
                                         write_pages)

# what ``"stats"/"counts"`` holds, in order: with whole heads, and with the
# latent cache (its one row a token, summed over rows and layers)
STATS = ("assignments", "experts_touched", "expert_load_max",
         "kv_tokens_read_global", "kv_tokens_read_window")
LATENT_STATS = STATS[:3] + ("latent_tokens_read",)
# with a window of exact keys beside summaries (``summary_window``): the
# cache rows of each sort the call's queries attend, summed over rows and
# layers (the windows closed before the call are the caller's to count: it
# launches them)
SUMMARY_STATS = STATS[:3] + ("kv_exact_rows_read", "kv_summary_rows_read")
# with short-convolution layers beside attention: the tokens those layers
# mixed, and the rows whose state entry went to a page of their own (not
# the scratch page), both summed over the short-convolution layers
STATE_STATS = STATS + ("conv_tokens", "state_rows_advanced")
# with delta-rule linear-attention layers: the tokens those layers mixed
# and the rows whose entries went to a page of their own, as above
LINEAR_STATS = ("linear_tokens", "state_rows_advanced")
# with block-sparse attention layers (``sparse_block``), summed over the
# call's real queries: the 64-token blocks a (query, KV head, layer) could
# see and those it reads (all of them at or under ``dense_len``, the forced
# and the chosen past it), the pooled keys a (query, layer) scores to
# choose, and the queries (once, not a layer) that took the dense path
SPARSE_STATS = ("kv_blocks_visible", "kv_blocks_read", "pooled_keys_scored",
                "rows_dense_path")
# ... and, last of such a model's counts, what the IMPLEMENTATION copied
# where the counts above say what the model reads: the blocks a chunk's
# tiles streamed, summed over (tile of queries, KV head, sparse layer) — a
# tile copies a unit of blocks once if any of its queries reads in it, so
# against the blocks its tiles could see this says how often the skip
# engages; 0 on a decode step and on a chunk at or under ``dense_len``
STREAMED_STATS = ("kv_blocks_streamed",)
# with a lightning indexer (``indexer``) over the latent cache, summed over
# the call's real queries: the index keys a (query, ``full`` layer) scores
# to choose (none while it sees ``top`` rows or fewer: it chooses nothing),
# the cache rows a (query, layer) could attend and those it does — COUNTED
# from the membership the layer went by, not reckoned from positions —
# (over ALL the layers, ``shared`` ones too), and the queries (once, not a
# layer) that attend everything they see
INDEX_STATS = ("index_keys_scored", "latent_rows_visible",
               "latent_rows_selected", "rows_dense_path")
MIXERS = ("attention", "short_conv", "linear_delta", "sparse_block",
          "lightning")
# the kinds whose cache leaf is a running state entry a page
STATE_MIXERS = ("short_conv", "linear_delta", "lightning")

# VMEM the grouped product's blocks may take: the compiler's scoped limit
# on the v5e is 16 MiB — compiled for it, a tile of (64, 2048, 1792) is
# 15.92 MiB of blocks and passes; (320, 896, 2048) is refused for 16.21 MiB,
# which is what :func:`gmm_blocks_bytes` counts to the KiB.  A compiler
# that counts more fails ``tests/test_tpu_lowering.py``'s compile for the
# v5e of that tile, not a served chunk
_GMM_VMEM = 16 * 2 ** 20 - 2 ** 16


def gmm_blocks_bytes(tm: int, tk: int, tn: int) -> int:
    """VMEM the grouped product's blocks take at a tile: the bf16 rows
    ``[tm, tk]`` and weights ``[tk, tn]`` and the f32 result ``[tm, tn]``
    double-buffered, the f32 accumulator, and the store's mask of the
    group's own rows, a byte an element."""
    return 2 * (tm * tk * 2 + tk * tn * 2 + tm * tn * 4) + tm * tn * 5


# rows a group from which a call is arithmetic and not a weight stream alone
# (LFM2's chunks of 1,024 tokens and more; no other cell's call reaches it)
_GMM_ARITHMETIC_FROM = 128


def gmm_tile(pairs: int, groups: int, k: int, n: int):
    """The tile ``(tm, tk, tn)`` of the grouped product of ``pairs`` sorted
    rows with ``groups`` weights of ``[k, n]``: a function of the call's
    static shape.  The measurements are in :func:`routed_experts`'
    docstring.

    Under ``_GMM_ARITHMETIC_FROM`` rows a group the call streams weights
    and the sweep found every tile within 2 % of every other: it keeps the
    tile every call had before the rule, row tiles of 128 and the first of
    1280 / 768 / 512 / 256 / 128 columns that divides ``n``, so such a
    call's kernel is the one measured since PR 27.  From there on the
    kernel's reads of its rows count — once a column tile, seven times at
    512 of 3,584 columns — so ``tn`` is the widest multiple of 128 dividing
    ``n`` whose blocks fit ``_GMM_VMEM``, beside row tiles of 64 where
    those of 128 leave no room for it (no row tile from 64 to 512 beat
    another by more than 4 % at one ``tn`` with the rows as they lie).
    The contraction stays whole, so that a group of several row tiles
    reads its weights once, and is halved only while no column tile fits
    beside it."""
    if pairs < _GMM_ARITHMETIC_FROM * groups:
        return 128, k, next(c for c in (1280, 768, 512, 256, 128, n)
                            if n % c == 0)
    columns = ([c for c in range(n, 0, -128) if n % c == 0]
               if n % 128 == 0 else [n])

    def widest(tm, tk):
        # beside a long contraction the compiler keeps more than
        # ``gmm_blocks_bytes`` counts: compiled for the v5e, (128, 6144,
        # 512) is 15.81 MiB by that count and is refused, for 16.16 in
        # the expert layer and for 16.95 alone (PR 49: 16 experts of 6144
        # x 4096) — 15.50 of double-buffered blocks, which is exactly what
        # the compiler reports where those alone pass 16, and the rest
        # accumulator and the body's temporaries.  Their rule is not
        # known; this GUARD, half a byte a (row, contraction) element, is
        # fitted to that one refusal and moves no call of a contraction
        # up to 2,560 (``tests/test_gmm_tile.py``); the compile of both
        # GLM bodies in ``tests/test_tpu_lowering.py`` holds it to the
        # compiler
        return next((c for c in columns
                     if gmm_blocks_bytes(tm, tk, c) <= _GMM_VMEM
                     and gmm_blocks_bytes(tm, tk, c) + tm * tk // 2
                     <= 16 * 2 ** 20), 0)

    tk = k
    while not widest(64, tk) and tk % 256 == 0:
        tk //= 2                        # no column tile fits beside it
    # row tiles of 128 unless those of 64 let a wider column tile fit
    tm = 64 if widest(64, tk) > widest(128, tk) else 128
    return tm, tk, widest(tm, tk) or columns[-1]


def rms_norm(x, scale, eps: float, unit_offset: bool = False):
    """``x / rms(x) * scale`` in f32, returned in x's dtype;
    ``unit_offset``: the learned vector is the scale's distance from 1
    (``* (1 + scale)``, initialised at zeros)."""
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    normed = x32 * jax.lax.rsqrt(var + eps)
    scale = scale.astype(jnp.float32)
    return (normed * (1.0 + scale if unit_offset else scale)
            ).astype(x.dtype)


def rotate_half_rope(x, positions, theta: float):
    """Rotary positions over the whole head, rotate-half pairing:
    element ``i`` pairs with ``i + D/2``, both turn by ``pos *
    theta**(-2i/D)``.  x [B, S, H, D], positions [B, S]; f32 inside."""
    d = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = positions.astype(jnp.float32)[..., None] * inv_freq  # [B,S,D/2]
    cos = jnp.cos(angle)[:, :, None, :]
    sin = jnp.sin(angle)[:, :, None, :]
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., :d // 2], x32[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def interleaved_rope(x, positions, theta: float):
    """Rotary positions over the last axis, interleaved pairing: the pair
    is ``(x[2i], x[2i+1])``, turned by ``pos * theta**(-2i/D)``.  x
    [B, S, ..., D], positions [B, S]; f32 inside."""
    d = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = positions.astype(jnp.float32)[..., None] * inv_freq  # [B,S,D/2]
    angle = angle.reshape(angle.shape[:2] + (1,) * (x.ndim - 3) + (d // 2,))
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x32 = x.astype(jnp.float32).reshape(x.shape[:-1] + (d // 2, 2))
    x1, x2 = x32[..., 0], x32[..., 1]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape).astype(x.dtype)


def route(h, w_router, k: int, score_bias=None, routed_scale: float = 1.0,
          sum_eps: float = 0.0, groups: int = 1, groups_kept: int = 1):
    """(expert ids [T, k], weights [T, k] f32) of the ``k`` largest router
    logits a token; softmax over the chosen (softmax over all, then
    renormalised over the chosen, is the same numbers).  f32 at full
    matmul precision: a rounding that flips the k-th choice moves the
    token's output by a whole expert, not by an ulp.

    ``score_bias`` [E] set is the ``sigmoid_bias`` rule instead: scores
    ``s = sigmoid(logits)``; the choice is the ``k`` largest of ``s +
    score_bias``; the weights are the chosen experts' ``s`` WITHOUT the
    bias, divided by their sum (plus ``sum_eps``: a published rule adds
    1e-6 there) and multiplied by ``routed_scale``.  ``groups`` > 1 limits
    that choice: the experts are ``groups`` runs of consecutive ids, a
    group's score is the sum of its 2 largest ``s + score_bias``, and only
    the experts of the ``groups_kept`` best groups can be chosen."""
    logits = jnp.einsum("td,de->te", h.astype(jnp.float32),
                        w_router.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    if score_bias is not None:
        scores = jax.nn.sigmoid(logits)
        biased = scores + score_bias.astype(jnp.float32)
        if groups > 1:
            t, e = biased.shape
            best2, _ = jax.lax.top_k(biased.reshape(t, groups, e // groups),
                                     2)
            _, kept = jax.lax.top_k(jnp.sum(best2, -1), groups_kept)
            open_ = jnp.zeros((t, groups), bool).at[
                jnp.arange(t)[:, None], kept].set(True)
            biased = jnp.where(jnp.repeat(open_, e // groups, axis=1),
                               biased, -jnp.inf)
        _, idx = jax.lax.top_k(biased, k)
        chosen = jnp.take_along_axis(scores, idx, axis=-1)
        total = jnp.sum(chosen, -1, keepdims=True)
        if sum_eps:
            total = total + sum_eps
        return idx, chosen / total * routed_scale
    vals, idx = jax.lax.top_k(logits, k)
    return idx, jax.nn.softmax(vals, axis=-1)


ACTIVATIONS = {"relu": jax.nn.relu, "silu": jax.nn.silu}


def gated_mlp(x, w_gate_up, w_down, activation: str = "silu"):
    """``down(act(gate x) * up x)`` for every row: x [T, d], w_gate_up
    [d, 2f] (gate columns first), w_down [f, d]; f32 accumulation, f32
    out.  The dense layers' MLP and the shared expert (XLA's matmuls)."""
    f = w_down.shape[0]
    h = jnp.einsum("td,df->tf", x, w_gate_up,
                   preferred_element_type=jnp.float32)
    h = (ACTIVATIONS[activation](h[:, :f]) * h[:, f:]).astype(x.dtype)
    return jnp.einsum("tf,fd->td", h, w_down,
                      preferred_element_type=jnp.float32)


def routed_experts(x, idx, weights, w_gate_up, w_down, *, use_pallas=None,
                   activation: str = "relu", held=None):
    """The dropless expert layer: ``y[t] = sum_j weights[t, j] *
    down_e(act(gate_e x[t]) * up_e x[t])`` with ``e = idx[t, j]``
    (``activation``: ``relu`` or ``silu``).

    x [T, d]; idx, weights [T, k]; w_gate_up [E, d, 2f] (gate columns
    first); w_down [E, f, d].  Returns (y [T, d] f32, rows per expert
    [E] int32).

    The T·k (token, expert) pairs are sorted by expert and go through
    two grouped matrix products at static shapes — a pair costs its own
    FLOPs, an expert with no pair costs nothing, and no pair is ever
    dropped: at a decode step this is a stream of the touched experts'
    weights, at a prefill chunk 1/E·k of the dense "every expert on every
    token" FLOPs.  ``use_pallas``: None = auto (the Pallas grouped matmul
    on TPU, ``jax.lax.ragged_dot`` elsewhere), True, "interpret", False.
    On the v5e at 64 experts of 2560 x 768: 0.91 against 1.10 ms a layer
    at 16 tokens, 1.59 against 2.99 ms at 512 (Pallas against
    ragged_dot).

    The kernel's tile is :func:`gmm_tile`'s, from the call's shape.
    Measured on one v5e chip, 2026-09-30 (``tools/gmm_sweep.py``, its
    lines kept in ``docs/pr42_gmm_sweep.jsonl``: ms a layer, the two
    products alone on the host's clock, group sizes a uniform router's
    draw); before = row tiles of 128 and the first of 1280/768/512 columns
    that divides; best = the sweep's best tile of each product:

    =======================  ======  ======  ====  =====================
    experts x K x f          pairs   before  best  the rule's tile
    =======================  ======  ======  ====  =====================
    32 x 2048 x 1792            384  1.10    1.09  as before: 128, K, 512
    (LFM2: 12, 128, 192,       4096  1.88    1.69  64, K, 1792 (gate/up),
    256 rows an expert)        6144  2.16    1.95  128, K, 1024 (down):
    ..                         8192  2.43    2.21  1.71, 1.95, 2.21 ms
    64 x 2560 x 768              96  0.88    0.87  as before
    (SmallThinker)             6144  1.77    1.71  as before
    256 x 2048 x 768            192  1.92    1.85  as before
    (JoyAI)                   16384  5.13    4.69  as before
    128 held x 2560 x 768       768  1.71    1.71  as before
    (Ling; a quarter held)     8192  2.34    2.33  as before
    =======================  ======  ======  ====  =====================

    The kernel reads its rows once a column tile, so a long chunk's rows
    went through seven times at 512 of 3,584 columns; where a call streams
    weights the tile only sets the number of grid steps — and the size of
    the kernel's unrolled code, which every start of a body pays: with
    the widest tile in every call LFM2's nine bodies loaded in 33 s for 23
    and ``setup_s`` rose 13 %.  So a call under 128 rows a group keeps the
    tile of before.  What that leaves: JoyAI's 256 groups at one column
    tile a product for two and four (0.70 -> 0.63 ms the down product at
    192 pairs, 2.06 -> 1.71 at 16,384; its cell served 3 % more in the one
    pair run so) — not taken here: its ``setup_s`` was not read warm.

    What the rows' tile cannot cure: with 256 rows an expert the chunk's
    two products take 2.2 ms where FLOPs and bytes allow 0.92, because a
    row tile of 128 (or 64) feeds the MXU that many rows a weight load and
    every group's edge tiles are computed for both neighbours.  A row
    tile a group (288 rows, every group starting at a multiple of it)
    took the two products to 1.32 ms under a uniform draw (the layer 2.67
    -> 2.11) — and lost under a served one: the busiest expert of a chunk
    holds twice the mean, so no row tile fits the groups, the padded rows
    cost the activation and the gathers their bytes (at the worst case's
    rows the layer read 2.59 for 2.67), and the cell served 1 % fewer
    tokens.  Row tiles of 64 or 128 beside groups padded to them gave
    what the rows as they lie give (1.42 ms the gate/up product either
    way), so they lie as sorted.

    ``held`` (first id, count): the weights are those of the experts
    ``first .. first + count - 1`` alone — this device's share of a layer
    whose router still chooses among all of them.  A pair whose expert is
    not held sorts behind every held one and lies outside every group of
    the grouped products, which therefore never compute it; it adds
    nothing to ``y``, and ``rows per expert`` [count] counts the pairs
    computed here."""
    t, k = idx.shape
    num_experts, _, f2 = w_gate_up.shape
    f = f2 // 2
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    flat = idx.reshape(-1)
    if held is not None:
        local = flat - held[0]
        here = (local >= 0) & (local < num_experts)
        flat = jnp.where(here, local, num_experts)
    order = jnp.argsort(flat)                   # pairs, expert by expert
    token = order // k
    if held is None:
        sizes = jnp.bincount(flat, length=num_experts).astype(jnp.int32)
    else:
        sizes = jnp.bincount(flat, length=num_experts + 1
                             )[:num_experts].astype(jnp.int32)
    xs = x[token]                               # [T·k, d]

    if use_pallas:
        from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm
        interpret = use_pallas == "interpret"
        xs = jnp.pad(xs, ((0, -(t * k) % 128), (0, 0)))     # whole row tiles

        def grouped(lhs, rhs):
            tile = gmm_tile(t * k, *rhs.shape)
            # the tile rides the op's name into the compiled body's text,
            # where the cost ledger's entry reads its kernels from
            with jax.named_scope("gmm_%dx%dx%d" % tile):
                return gmm(lhs, rhs, sizes, jnp.float32, tile,
                           interpret=interpret)
    else:
        def grouped(lhs, rhs):
            return jax.lax.ragged_dot(lhs, rhs, sizes,
                                      preferred_element_type=jnp.float32)

    h = grouped(xs, w_gate_up)
    h = (ACTIVATIONS[activation](h[:, :f]) * h[:, f:]).astype(x.dtype)
    y = grouped(h, w_down)
    # back to the pairs' own order: a gather by the inverse permutation,
    # then the k weighted rows of a token are summed in f32
    inverse = jnp.zeros_like(order).at[order].set(
        jnp.arange(t * k, dtype=order.dtype))
    y = y[inverse].reshape(t, k, -1)
    if held is not None:
        # a row no group covered holds whatever the product left there
        y = jnp.where(here.reshape(t, k, 1), y, 0.0)
    return jnp.sum(y * weights[..., None], axis=1), sizes


def routed_experts_dense(x, idx, weights, w_gate_up, w_down,
                         activation: str = "relu"):
    """The oracle of :func:`routed_experts`: every expert on every token,
    masked by the routing weights."""
    num_experts, _, f2 = w_gate_up.shape
    f = f2 // 2
    t = x.shape[0]
    full = jnp.zeros((t, num_experts), jnp.float32).at[
        jnp.arange(t)[:, None], idx].add(weights)
    h = jnp.einsum("td,edf->etf", x, w_gate_up,
                   preferred_element_type=jnp.float32)
    h = (ACTIVATIONS[activation](h[..., :f]) * h[..., f:]).astype(x.dtype)
    y = jnp.einsum("etf,efd->etd", h, w_down,
                   preferred_element_type=jnp.float32)
    return jnp.einsum("etd,te->td", y, full)


def _normal(stddev):
    return nn.initializers.normal(stddev)


def _norm_init(unit_offset: bool):
    """A norm's learned vector starts at the identity: ones, or zeros where
    it is the scale's distance from 1."""
    return nn.initializers.zeros if unit_offset else nn.initializers.ones


def _clipped_normal(scale):
    """``clip(N(0, 1), -1, 1) * scale``."""
    def init(key, shape, dtype=jnp.float32):
        return (jnp.clip(jax.random.normal(key, shape, jnp.float32), -1.0,
                         1.0) * scale).astype(dtype)
    return init


def _need_table(module, cache_index, block_table):
    if module.kv_page_size is None:
        raise ValueError("decode mode needs kv_page_size and kv_pool_pages")
    if cache_index is None or block_table is None:
        raise ValueError("decode mode needs cache_index [B] and block_table "
                         "[B, M], both int32")


class GroupedQueryAttention(nn.Module):
    num_heads: int
    num_kv_heads: int
    head_dim: int
    window: Optional[int]           # None: the whole history
    rope_theta: Optional[float]     # None: no positions
    dtype: Any
    param_dtype: Any
    use_pallas: Any = None
    decode: bool = False
    kv_page_size: Optional[int] = None
    kv_pool_pages: Optional[int] = None
    qk_norm_eps: Optional[float] = None     # set: per-head norms of q, k
    # (window, chunk): exact keys of the query's own aligned window beside
    # one learned summary a chunk of every closed one (ops/window_summary)
    summary: Optional[Tuple[int, int]] = None
    # the first rotary_dim lanes of a head turn, the others carry no
    # position (None: the whole head)
    rotary_dim: Optional[int] = None
    # the query projection carries a gate a (head, lane) as well: the
    # heads' outputs are multiplied by its sigmoid before ``out``
    output_gate: bool = False
    qk_norm_unit_offset: bool = False   # q_norm, k_norm scale by 1 + w
    # the scale q_norm and k_norm START at (1: the identity).  Under random
    # weights a gain of 1 leaves the scores' spread at 1 and a softmax over
    # thousands of keys near flat; a served model's is not
    qk_norm_gain: float = 1.0

    @property
    def one_row(self) -> bool:
        """K and V of a head share ONE pool row ``[k | v]``, all of it
        payload: a K or V row of half a lane tile is stored in a whole one
        (two pools of 64 lanes do not even lower on the TPU; narrower
        heads are sizes of the CPU's tests alone)."""
        return 2 * self.head_dim == _LANES

    @nn.nowrap
    def walks(self, s: int) -> bool:
        """Whether a decode-mode CONTINUATION call of ``s`` queries a row
        attends through the walk over its K and V pools
        (``ops.paged_attention.chunk_walks``: by the call's shape, the
        layer's heads and its window); a compact table's rows (``summary``)
        are the paged kernel's.  ``__call__`` goes by it and the model
        names a count by it (``RoutedDecoderLM.layers_walking``)."""
        return (self.decode and self.summary is None and chunk_walks(
            s, self.num_heads, self.num_kv_heads, window=self.window,
            pools=1 if self.one_row else 2))

    @nn.compact
    def __call__(self, h, positions, cache_index=None, block_table=None,
                 flash_prefill: bool = False,
                 window_pages: Optional[int] = None):
        b, s, d = h.shape
        hq, hkv, dh = self.num_heads, self.num_kv_heads, self.head_dim
        if self.summary is not None:
            if self.one_row or self.window is not None:
                raise ValueError(
                    "summaries go in K and V pools of their own beside the "
                    "window's tokens: no [k | v] rows (head_dim 64) and no "
                    "sliding window in such a layer")
            init = _clipped_normal(dh ** -0.5)
            phi = self.param("summary_phi", init, (hkv, dh),
                             self.param_dtype)
            mu = self.param("summary_mu", init, (hkv, dh), self.param_dtype)
        # columns [q | k | v] and, with an output gate, [.. | gate]
        gated = hq * dh if self.output_gate else 0
        w_qkv = self.param("qkv", _normal(0.02),
                           (d, (hq + 2 * hkv) * dh + gated),
                           self.param_dtype)
        w_out = self.param("out", _normal(0.02), (hq * dh, d),
                           self.param_dtype)
        qkv = jnp.einsum("bsd,dn->bsn", h.astype(self.dtype),
                         w_qkv.astype(self.dtype),
                         preferred_element_type=jnp.float32)
        if self.output_gate:
            qkv, gate = (qkv[..., :(hq + 2 * hkv) * dh],
                         qkv[..., (hq + 2 * hkv) * dh:])
        qkv = qkv.astype(self.dtype)
        q = qkv[..., :hq * dh].reshape(b, s, hq, dh)
        k = qkv[..., hq * dh:(hq + hkv) * dh].reshape(b, s, hkv, dh)
        v = qkv[..., (hq + hkv) * dh:].reshape(b, s, hkv, dh)
        if self.qk_norm_eps is not None:
            offset = self.qk_norm_unit_offset
            init = nn.initializers.constant(self.qk_norm_gain - float(offset))
            q = rms_norm(q, self.param("q_norm", init, (dh,),
                                       self.param_dtype), self.qk_norm_eps,
                         offset)
            k = rms_norm(k, self.param("k_norm", init, (dh,),
                                       self.param_dtype), self.qk_norm_eps,
                         offset)
        if self.rope_theta is not None:
            r = self.rotary_dim
            if r is None:
                q = rotate_half_rope(q, positions, self.rope_theta)
                k = rotate_half_rope(k, positions, self.rope_theta)
            else:
                q, k = (jnp.concatenate(
                    [rotate_half_rope(x[..., :r], positions,
                                      self.rope_theta), x[..., r:]], -1)
                    for x in (q, k))
        if self.decode:
            _need_table(self, cache_index, block_table)
            if self.summary is not None:
                # the attended rows are a prefix of the table in table
                # order: summaries first, then the open window's tokens
                cache_index = window_summary.compact_index(cache_index,
                                                           *self.summary)
            o = paged_cache_attention(
                self, q, k, v, cache_index, block_table,
                flash_prefill=flash_prefill, window_pages=window_pages,
                window=self.window, one_row=self.one_row,
                walks=self.walks(s))
        else:
            # the whole sequence at once (tests, the toy): a plain mask
            summaries = None
            if self.summary is not None and s >= self.summary[1]:
                # a query's own aligned window exactly, and one summary a
                # whole chunk of the windows before it
                window, chunk = self.summary
                whole = s // chunk * chunk
                summaries = window_summary.chunk_summaries(
                    k[:, :whole], v[:, :whole], phi, mu, chunk)
                k = jnp.concatenate([k, summaries[0]], axis=1)
                v = jnp.concatenate([v, summaries[1]], axis=1)
            kr, vr = expand_kv_heads(k, v, hq)
            i = jnp.arange(s)
            mask = i[None, :] <= i[:, None]
            if self.window is not None:
                mask &= i[None, :] > i[:, None] - self.window
            if summaries is not None:
                first = i // window * window
                mask = jnp.concatenate(
                    [mask & (i[None, :] >= first[:, None]),
                     jnp.arange(whole // chunk)[None, :] * chunk
                     < first[:, None]], axis=1)
            o = cached_attention(q, kr, vr,
                                 jnp.broadcast_to(mask, (b,) + mask.shape))
        o = o.reshape(b, s, hq * dh)
        if self.output_gate:
            o = (o * jax.nn.sigmoid(gate)).astype(self.dtype)
        return jnp.einsum("bsn,nd->bsd", o, w_out.astype(self.dtype),
                          preferred_element_type=jnp.float32)


def _carry_page(cache_index, block_table, page: int):
    """[B] the page whose state entry is a row's carry: the one that holds
    position ``cache_index - 1``."""
    return jnp.take_along_axis(
        block_table, (jnp.maximum(cache_index - 1, 0) // page)[:, None],
        axis=1)[:, 0]


def _entry_ends(last_pos, b: int, s: int, page: int):
    """[B, n] the offset in a call of ``s`` tokens at which each of its
    ``n`` pages' state entry is taken: the page's last token or the row's
    last real one (``last_pos``; None: ``s - 1``), whichever is first."""
    last = (jnp.full((b,), s - 1, jnp.int32) if last_pos is None
            else last_pos.astype(jnp.int32))
    return jnp.minimum(
        (jnp.arange(max(s // page, 1), dtype=jnp.int32)[None, :] + 1)
        * min(page, s) - 1, last[:, None])


def _entry_pages(cache_index, ends, block_table, page: int):
    """[B, n] the page ids those entries go to."""
    where = jnp.minimum((cache_index[:, None] + ends) // page,
                        block_table.shape[1] - 1)
    return jnp.take_along_axis(block_table, where, axis=1)


class ShortConv(nn.Module):
    """The double-gated short convolution: ``[B | C | z] = h W_in``
    (three blocks of ``d``); ``u = B * z``; ``c_t = sum_j w_j * u_{t - (L
    - 1) + j}`` with ``w`` [d, L] one filter a channel (depthwise), causal,
    ``u`` zero before the sequence; returns ``(C * c) W_out``.  No
    activation and no bias.

    The two gates and the tap sum are f32; ``u`` is rounded to ``dtype``
    before the taps, in every form, because that is what the state holds:
    a chunk's carry and a chunk's own tokens are the same numbers, and
    every chunking of a prompt gives the same ``c``.

    Decode mode (see the module's docstring): the state entry of a page is
    ``[u_{t-L+2} | ... | u_t]`` (``(L - 1) * d`` values, oldest first) at
    the newest token ``t`` written in it.  Returns (output, rows whose
    entry went to a page other than the scratch page)."""
    taps: int
    dtype: Any
    param_dtype: Any
    decode: bool = False
    kv_page_size: Optional[int] = None
    kv_pool_pages: Optional[int] = None

    @nn.compact
    def __call__(self, h, cache_index=None, block_table=None, last_pos=None):
        b, s, d = h.shape
        keep = self.taps - 1
        w_in = self.param("in_proj", _normal(0.02), (d, 3 * d),
                          self.param_dtype)
        w_out = self.param("out_proj", _normal(0.02), (d, d),
                           self.param_dtype)
        w = self.param("taps", _normal(0.02), (d, self.taps),
                       self.param_dtype).astype(jnp.float32)
        bcz = jnp.einsum("bsd,dn->bsn", h.astype(self.dtype),
                         w_in.astype(self.dtype),
                         preferred_element_type=jnp.float32)
        gate_c = bcz[..., d:2 * d]
        u = (bcz[..., :d] * bcz[..., 2 * d:]).astype(self.dtype)
        carry = jnp.zeros((b, keep, d), self.dtype)
        advanced = jnp.zeros((), jnp.int32)
        if self.decode:
            _need_table(self, cache_index, block_table)
            state = self.variable("cache", "conv_state", jnp.zeros,
                                  (self.kv_pool_pages, keep * d), self.dtype)
        if self.decode and not self.is_initializing():
            page = self.kv_page_size
            if s > 1 and s % page:
                raise ValueError(
                    f"a call of {s} tokens is neither one token nor whole "
                    f"pages of {page}: a page it crossed would keep a "
                    f"stale state entry")
            before = _carry_page(cache_index, block_table, page)
            carry = jnp.where((cache_index > 0)[:, None, None],
                              state.value[before].reshape(b, keep, d), carry)
        full = jnp.concatenate([carry, u], axis=1)          # [B, keep+S, d]
        c = sum(w[:, j] * full[:, j:j + s].astype(jnp.float32)
                for j in range(self.taps))
        if self.decode and not self.is_initializing():
            # the entry of every page of the call, taken at the page's
            # last token or at the row's last real one, whichever is first
            ends = _entry_ends(last_pos, b, s, page)
            n = ends.shape[1]
            rows = ends[:, :, None] + 1 + jnp.arange(keep,
                                                     dtype=jnp.int32)
            entries = jnp.take_along_axis(
                full, rows.reshape(b, n * keep)[:, :, None], axis=1
            ).reshape(b * n, keep * d)
            pages = _entry_pages(cache_index, ends, block_table, page)
            state.value = state.value.at[pages.reshape(-1)].set(entries)
            advanced = jnp.sum(pages[:, 0] != 0, dtype=jnp.int32)
        y = jnp.einsum("bsd,dn->bsn",
                       (gate_c * c).astype(self.dtype),
                       w_out.astype(self.dtype),
                       preferred_element_type=jnp.float32)
        return y, advanced


def _log_uniform(low: float, high: float):
    def init(key, shape, dtype=jnp.float32):
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, low,
                                          high)).astype(dtype)
    return init


def _uniform(low: float, high: float):
    def init(key, shape, dtype=jnp.float32):
        return jax.random.uniform(key, shape, jnp.float32, low,
                                  high).astype(dtype)
    return init


class LinearDelta(nn.Module):
    """Delta-rule linear attention with a decay a channel
    (``ops/linear_state.py``): ``heads`` heads of ``head_dim`` keys and
    values, no positions, no pages of history — what a request carries
    through the layer is a MATRIX a head and the last ``taps - 1`` inputs
    of three short filters.

    ``[q | k | v] = silu(conv(h W_qkv))``, ``conv`` a causal depthwise
    filter of ``taps`` taps a channel, zeros before the sequence; a head's
    ``q <- q / |q| * head_dim**-0.5``, ``k <- k / |k|``.  Log decay ``a =
    decay_floor * sigmoid(exp(a_log) * (h W_decay + dt_bias))`` in
    ``(decay_floor, 0)`` a channel, write strength ``beta = sigmoid(h
    W_beta)`` a head; state and output as ``linear_state`` defines them;
    then ``(RMSNorm_head(o) * sigmoid(h W_gate)) W_out``.

    The GATED form, by three fields.  ``decay`` ``head``: ``[b | a] = h
    W_ba`` (one projection of ``2 heads``), ``beta = sigmoid(b)`` and the
    log decay ``-exp(a_log) * softplus(a + dt_bias)``, ONE SCALAR a head
    with no floor (``decay_floor`` is not read), handed to the state forms
    broadcast over the head's channels.  ``key_heads``: ``q`` and ``k`` have
    that many heads, fewer than ``heads``; value head ``i`` reads key head
    ``i // (heads // key_heads)``, the rows repeated for the state forms;
    the filter runs over ``2 * key_heads * head_dim + heads * head_dim``
    channels and ``conv_state`` holds as many.  ``gate`` ``silu``: the one
    projection is ``[q | k | v | z]`` (parameter ``qkvz``) and the output
    is ``(RMSNorm_head(o) * silu(z)) W_out``.

    Matmuls take ``dtype`` inputs and accumulate in f32; the filters'
    inputs are rounded to ``dtype`` before the taps in every form, because
    that is what their state holds (as :class:`ShortConv`); the gates, the
    L2 norms and the state's arithmetic are f32.

    Decode mode: two leaves in the ``"cache"`` collection, each indexed by
    page id and each holding, for page ``p``, the running state at the
    newest token written in ``p`` — ``linear_state`` ``[P, heads, head_dim,
    head_dim]`` (the matrices, transposed, in ``dtype``) and
    ``conv_state`` ``[P, sublanes, (taps - 1) * channels / sublanes]``
    (``channels`` ``3 * heads * head_dim``, or the gated form's): the last
    ``taps - 1`` inputs ``[u_{t-2} | u_{t-1} | u_t]``,
    oldest first, row-major, laid out as the page's own whole tiles
    (``sublanes`` what one tile of ``dtype`` holds: 16 in bfloat16, 8 in
    float32).  One token
    goes through ``linear_state_decode`` (the kernel) or its oracle, a
    chunk of whole pages through the blocked form, with tokens past
    ``last_pos`` made no-ops on the state (``beta`` = 0, ``a`` = 0).
    Outside decode mode the token-by-token recurrence runs from zeros.
    Returns (output, rows whose entries went to a page other than the
    scratch page)."""
    heads: int
    head_dim: int
    taps: int
    decay_floor: float
    rms_eps: float
    dtype: Any
    param_dtype: Any
    use_pallas: Any = None
    decode: bool = False
    kv_page_size: Optional[int] = None
    kv_pool_pages: Optional[int] = None
    key_heads: Optional[int] = None     # None: as many as ``heads``
    decay: str = "channel"              # | head
    gate: str = "sigmoid"               # | silu

    @nn.compact
    def __call__(self, h, cache_index=None, block_table=None, last_pos=None):
        b, s, d = h.shape
        hn, dh, keep = self.heads, self.head_dim, self.taps - 1
        kh = hn if self.key_heads is None else self.key_heads
        if hn % kh:
            raise ValueError(f"{hn} value heads do not share {kh} key heads")
        if (self.decay not in ("channel", "head")
                or self.gate not in ("sigmoid", "silu")):
            raise ValueError(f"decay {self.decay!r} (channel | head), gate "
                             f"{self.gate!r} (sigmoid | silu)")
        n, kn = hn * dh, kh * dh
        c = 2 * kn + n                      # the filters' channels
        pdt = self.param_dtype
        by_head = self.decay == "head"
        # a parameter's draw follows its place in this order: the forms'
        # parameters stand where the first form's stood, so that a seed
        # gives that form the weights it always gave
        if self.gate == "silu":
            # the output gate's input z rides the one projection
            w_qkv = self.param("qkvz", _normal(0.02), (d, c + n), pdt)
        else:
            w_qkv = self.param("qkv", _normal(0.02), (d, c), pdt)
        if by_head:
            w_ba = self.param("ba", _normal(0.02), (d, 2 * hn), pdt)
        else:
            w_decay = self.param("decay", _normal(0.02), (d, n), pdt)
        if self.gate == "sigmoid":
            w_gate = self.param("gate", _normal(0.02), (d, n), pdt)
        if not by_head:
            w_beta = self.param("beta", _normal(0.02), (d, hn), pdt)
        w_out = self.param("out", _normal(0.02), (n, d), pdt)
        w = self.param("taps", _normal(0.3), (c, self.taps),
                       pdt).astype(jnp.float32)
        # f32 whatever param_dtype: they meet f32 gates.  Time constants
        # from a token to a few thousand, spread evenly in the logarithm
        a_log = self.param("a_log", _log_uniform(1.0, 2.0), (hn,),
                           jnp.float32)
        dt_bias = self.param("dt_bias", _uniform(-8.0, 0.0),
                             (hn if by_head else n,), jnp.float32)
        g_out = self.param("out_norm", nn.initializers.ones, (dh,), pdt)

        def mm(x, w_):
            return jnp.einsum("bsd,dn->bsn", x.astype(self.dtype),
                              w_.astype(self.dtype),
                              preferred_element_type=jnp.float32)
        pre = mm(h, w_qkv)
        if self.gate == "silu":
            pre, z = pre[..., :c], pre[..., c:]
        pre = pre.astype(self.dtype)
        paged = self.decode and not self.is_initializing()
        carry = jnp.zeros((b, keep, c), self.dtype)
        if self.decode:
            _need_table(self, cache_index, block_table)
            # a page's entry as that page's OWN whole tiles (as many rows
            # as one tile of ``dtype`` holds sublanes): one contiguous
            # block, which the TPU compiler's scatter writes in one op —
            # a 2-D leaf's row of this width is strided through tiles 16
            # pages share, and its scatter compiles to a serial loop of
            # dynamic-update-slice, one trip a row
            sublanes = 32 // jnp.dtype(self.dtype).itemsize
            if keep * c % sublanes:
                raise ValueError(
                    f"the filter inputs' state entry of {keep} x {c} = "
                    f"{keep * c} {jnp.dtype(self.dtype).name}"
                    f" values does not divide into the {sublanes} "
                    f"sublanes of one tile")
            entry = (sublanes, keep * c // sublanes)
            conv_state = self.variable(
                "cache", "conv_state", jnp.zeros,
                (self.kv_pool_pages,) + entry, self.dtype)
            state = self.variable(
                "cache", "linear_state", jnp.zeros,
                (self.kv_pool_pages, hn, dh, dh), self.dtype)
        if paged:
            page = self.kv_page_size
            if s > 1 and s % page:
                raise ValueError(
                    f"a call of {s} tokens is neither one token nor whole "
                    f"pages of {page}: a page it crossed would keep a "
                    f"stale state entry")
            has_carry = (cache_index > 0)[:, None, None]
            before = _carry_page(cache_index, block_table, page)
            carry = jnp.where(
                has_carry, conv_state.value[before].reshape(b, keep, c),
                carry)
        full = jnp.concatenate([carry, pre], axis=1)    # [B, keep+S, c]
        qkv = jax.nn.silu(sum(w[:, j] * full[:, j:j + s].astype(jnp.float32)
                              for j in range(self.taps)))
        q = qkv[..., :kn].reshape(b, s, kh, dh)
        k = qkv[..., kn:2 * kn].reshape(b, s, kh, dh)
        v = qkv[..., 2 * kn:].reshape(b, s, hn, dh)

        def unit(x):
            return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True)
                                     + 1e-6)
        q, k = unit(q) * dh ** -0.5, unit(k)
        if kh != hn:
            # value head i reads key head i // (hn // kh): the state forms
            # take one q and k row a value head
            q, k = (jnp.repeat(x, hn // kh, axis=2) for x in (q, k))
        if by_head:
            # one scalar a head, no floor; the state forms take a channel's
            ba = mm(h, w_ba)
            a = jnp.broadcast_to(
                (-jnp.exp(a_log) * jax.nn.softplus(ba[..., hn:] + dt_bias)
                 )[..., None], (b, s, hn, dh))
            beta = jax.nn.sigmoid(ba[..., :hn])         # [B, S, H]
        else:
            a = self.decay_floor * jax.nn.sigmoid(
                jnp.repeat(jnp.exp(a_log), dh) * (mm(h, w_decay) + dt_bias)
            ).reshape(b, s, hn, dh)
            beta = jax.nn.sigmoid(mm(h, w_beta))        # [B, S, H]
        if paged and s > 1 and last_pos is not None:
            real = (jnp.arange(s, dtype=jnp.int32)[None, :]
                    <= last_pos[:, None])
            a = jnp.where(real[..., None, None], a, 0.0)
            beta = jnp.where(real[..., None], beta, 0.0)
        advanced = jnp.zeros((), jnp.int32)
        if not paged:
            o, _ = linear_state.recurrent(q, k, v, a, beta)
        else:
            ends = _entry_ends(last_pos, b, s, page)
            pages_n = ends.shape[1]
            rows = ends[:, :, None] + 1 + jnp.arange(keep, dtype=jnp.int32)
            entries = jnp.take_along_axis(
                full, rows.reshape(b, pages_n * keep)[:, :, None], axis=1
            ).reshape((b * pages_n,) + entry)
            pages = _entry_pages(cache_index, ends, block_table, page)
            conv_state.value = conv_state.value.at[pages.reshape(-1)].set(
                entries)
            advanced = jnp.sum(pages[:, 0] != 0, dtype=jnp.int32)
            if s == 1:
                use_pallas = self.use_pallas
                if use_pallas is None:
                    use_pallas = jax.default_backend() == "tpu"
                one = (q[:, 0], k[:, 0], v[:, 0], a[:, 0], beta[:, 0],
                       block_table, cache_index)
                if use_pallas:
                    o, state.value = linear_state.linear_state_decode(
                        state.value, *one, page_size=page,
                        interpret=use_pallas == "interpret")
                else:
                    o, state.value = linear_state.paged_step(
                        state.value, *one, page_size=page)
                o = o[:, None]
            else:
                start = jnp.where(has_carry[..., None],
                                  state.value[before].astype(jnp.float32),
                                  0.0)
                o, states = linear_state.chunked(
                    q, k, v, a, beta, start,
                    block=math.gcd(page, linear_state.BLOCK),
                    emit_every=page)
                state.value = state.value.at[pages.reshape(-1)].set(
                    states.reshape((b * pages_n,) + states.shape[2:]
                                   ).astype(state.value.dtype))
        o = rms_norm(o, g_out, self.rms_eps).reshape(b, s, n)
        y = mm(o * (jax.nn.silu(z) if self.gate == "silu"
                    else jax.nn.sigmoid(mm(h, w_gate))), w_out)
        return y, advanced


class SparseBlockAttention(nn.Module):
    """Grouped-query attention that READS A SUBSET of a row's cache, chosen
    query by query through pooled keys (``ops/block_select.py``): ``q, k``
    normed a head (gains initialised at ``sizes[7]``), NO positions, a
    query at ``t`` past ``dense_len`` attends the first block, the window's
    blocks and the ``top`` best of the others a KV head, at or under it
    everything; ``out = W_o (o * sigmoid(h W_gate))``.

    ``sizes``: (block, pool, stride, top, window, init blocks, dense_len,
    q/k norm gain) — ``block_select.Sizes`` and the initialiser.

    Decode mode keeps three leaves a layer: ``paged_key`` and
    ``paged_value`` ``[P, page, Hkv, Dh]`` and ``pooled_key`` ``[P, page /
    stride, Hkv, Dh]`` (a ``kv_pool`` leaf whose rows a page are pooled
    keys, not tokens).  One token: the row's pooled pages are scored
    (kernel ``block_select``), the table of blocks ``[B, Hkv, W]`` built,
    and ``paged_flash_decode`` copies those blocks alone, a (row, KV head)
    a row of it.  A chunk gives every TOKEN its own choice, kept as packed
    membership ``[B, Hkv, S, units]`` (``block_select.chunk_members``; no
    table, no rank): the tile kernel ``paged_flash_decode_tiles`` streams,
    a tile of queries, the units any of them reads ONCE and each query
    masks what it did not choose; a chunk that ends at or under
    ``dense_len`` goes through the kernels every dense model uses (the
    first through the flash kernel).  Outside decode mode the choice is a
    mask on plain attention.  Returns ``(out, the blocks the chunk's tiles
    copied)``."""
    num_heads: int
    num_kv_heads: int
    head_dim: int
    sizes: Tuple
    rms_eps: float
    dtype: Any
    param_dtype: Any
    use_pallas: Any = None
    decode: bool = False
    kv_page_size: Optional[int] = None
    kv_pool_pages: Optional[int] = None

    @nn.compact
    def __call__(self, h, cache_index=None, block_table=None,
                 flash_prefill: bool = False,
                 window_pages: Optional[int] = None):
        b, s, d = h.shape
        hq, hkv, dh = self.num_heads, self.num_kv_heads, self.head_dim
        sizes, gain = block_select.Sizes(*self.sizes[:7]), self.sizes[7]
        pdt = self.param_dtype
        w_qkv = self.param("qkv", _normal(0.02), (d, (hq + 2 * hkv) * dh),
                           pdt)
        w_gate = self.param("gate", _normal(0.02), (d, hq * dh), pdt)
        w_out = self.param("out", _normal(0.02), (hq * dh, d), pdt)
        g_q = self.param("q_norm", nn.initializers.constant(gain), (dh,),
                         pdt)
        g_k = self.param("k_norm", nn.initializers.constant(gain), (dh,),
                         pdt)

        def mm(x, w_):
            return jnp.einsum("bsd,dn->bsn", x.astype(self.dtype),
                              w_.astype(self.dtype),
                              preferred_element_type=jnp.float32)
        qkv = mm(h, w_qkv).astype(self.dtype)
        q = rms_norm(qkv[..., :hq * dh].reshape(b, s, hq, dh), g_q,
                     self.rms_eps)
        k = rms_norm(qkv[..., hq * dh:(hq + hkv) * dh].reshape(
            b, s, hkv, dh), g_k, self.rms_eps)
        v = qkv[..., (hq + hkv) * dh:].reshape(b, s, hkv, dh)
        scale = dh ** -0.5
        o, streamed = jnp.zeros_like(q), jnp.zeros((), jnp.int32)
        if not self.decode:
            # the whole sequence at once (tests, the toy): the choice as
            # a mask [B, S, Hkv, S] on plain attention
            mask = block_select.plain_mask(q, k, sizes, scale)
            qg = q.astype(jnp.float32).reshape(b, s, hkv, hq // hkv, dh)
            sc = jnp.einsum("bqhgd,bkhd->bqhgk", qg,
                            k.astype(jnp.float32)) * scale
            sc = jnp.where(mask[:, :, :, None, :], sc, -1e30)
            o = jnp.einsum("bqhgk,bkhd->bqhgd", jax.nn.softmax(sc, -1),
                           v.astype(jnp.float32)).astype(q.dtype)
        else:
            _need_table(self, cache_index, block_table)
            page = self.kv_page_size
            sizes.check(page)
            shape = (self.kv_pool_pages, page, hkv, dh)
            keys = self.variable("cache", "paged_key", jnp.zeros, shape,
                                 self.dtype)
            values = self.variable("cache", "paged_value", jnp.zeros, shape,
                                   self.dtype)
            pooled = self.variable(
                "cache", "pooled_key", jnp.zeros,
                (self.kv_pool_pages, page // sizes.stride, hkv, dh),
                self.dtype)
            if not self.is_initializing():
                o, streamed = self._paged(
                    q, k, v, keys, values, pooled, cache_index, block_table,
                    sizes, scale, flash_prefill, window_pages)
        gate = jax.nn.sigmoid(mm(h, w_gate))
        return mm(o.reshape(b, s, hq * dh) * gate, w_out), streamed

    def _paged(self, q, k, v, keys, values, pooled, cache_index,
               block_table, sizes, scale, flash_prefill, window_pages):
        b, s, hq, dh = q.shape
        page = self.kv_page_size
        use_pallas = self.use_pallas
        if use_pallas is None:
            use_pallas = jax.default_backend() == "tpu"
        aligned = s > 1 and s % page == 0
        keys.value = write_pages(keys.value, k, block_table, cache_index,
                                 page_aligned=aligned)
        values.value = write_pages(values.value, v, block_table,
                                   cache_index, page_aligned=aligned)
        pooled.value = block_select.write_pooled(
            pooled.value, k, keys.value, block_table, cache_index, sizes)
        t = cache_index[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]

        none = jnp.zeros((), jnp.int32)
        if s == 1:
            if use_pallas:
                r = block_select.decode_scores(
                    q[:, 0], pooled.value, block_table, cache_index,
                    sizes=sizes, scale=scale,
                    interpret=use_pallas == "interpret")
            else:
                r = block_select.scores(q, pooled.value, block_table, t,
                                        sizes, scale)[:, 0]
            blocks, count = block_select.choose(r, t[:, 0], sizes)
            ids = block_select.physical(blocks, block_table, page,
                                        sizes.block)
            o = paged_block_attention(
                q[:, 0], keys.value, values.value, ids, count,
                t[:, 0] % sizes.block, block=sizes.block,
                use_pallas=use_pallas)
            return o[:, None], none
        if flash_prefill:
            if s > sizes.dense_len:
                raise ValueError(f"a first chunk of {s} tokens passes "
                                 f"dense_len {sizes.dense_len}")
            return flash_attention(q, *expand_kv_heads(k, v, hq),
                                   causal=True,
                                   use_pallas=self.use_pallas), none

        def dense():
            return paged_attention_auto(
                q, keys.value, values.value, block_table, cache_index,
                window_pages=window_pages, use_pallas=self.use_pallas), none

        def sparse():
            # every token its own choice, as membership: a tile of queries
            # streams the blocks its queries read once
            per = tile_keys(page, sizes.block) // sizes.block
            bits = block_select.chunk_members(
                q, pooled.value, block_table, t, sizes, scale, per)
            return paged_tile_attention(
                q, keys.value, values.value, block_table, cache_index, bits,
                block=sizes.block, use_pallas=use_pallas)
        return jax.lax.cond(jnp.all(cache_index + s <= sizes.dense_len),
                            dense, sparse)


def lightning_log_decay(heads: int, layer: int, depth: int):
    """[heads] the log of a head's constant decay: ``-2**(-8 (h + 1) /
    heads) * (1 - layer / (depth - 1) + 1e-5)``, ``layer`` the layer's index
    in the PUBLISHED stack of ``depth`` layers."""
    slopes = 2.0 ** (-8.0 * jnp.arange(1, heads + 1, dtype=jnp.float32)
                     / heads)
    return -slopes * (1.0 - layer / max(depth - 1, 1) + 1e-5)


class LightningAttention(nn.Module):
    """Linear attention with a CONSTANT decay a head and no write gate
    (``ops/linear_state.py``, the no-erase forms): ``q, k, v = h W``,
    ``heads`` of ``head_dim``; ``q, k`` normed a head, rotated (rotate-half,
    ``rope_theta``) at the true position; ``S_t = lambda_h S_{t-1} + k_t
    v_t^T``, ``o_t = head_dim**-0.5 S_t^T q_t``, ``lambda_h`` from
    :func:`lightning_log_decay`; ``out = W_o (RMSNorm_head(o) * sigmoid(h
    W_gate))``.

    Decode mode: ONE leaf ``linear_state`` ``[P, heads, head_dim,
    head_dim]`` (the matrices, transposed, in ``dtype``), a ``page_state``
    entry a page by :class:`LinearDelta`'s contract; one token through the
    kernel ``linear_state_decode_noerase_*`` or its oracle, a chunk of
    whole pages through the blocked form, tokens past ``last_pos`` no-ops
    (``k`` = 0, log decay 0).  Returns (output, rows whose entry went to a
    page other than the scratch page)."""
    heads: int
    head_dim: int
    layer: int
    depth: int
    rope_theta: float
    rms_eps: float
    dtype: Any
    param_dtype: Any
    use_pallas: Any = None
    decode: bool = False
    kv_page_size: Optional[int] = None
    kv_pool_pages: Optional[int] = None

    @nn.compact
    def __call__(self, h, positions, cache_index=None, block_table=None,
                 last_pos=None):
        b, s, d = h.shape
        hn, dh = self.heads, self.head_dim
        n = hn * dh
        pdt, ones = self.param_dtype, nn.initializers.ones
        w_qkv = self.param("qkv", _normal(0.02), (d, 3 * n), pdt)
        w_gate = self.param("gate", _normal(0.02), (d, n), pdt)
        w_out = self.param("out", _normal(0.02), (n, d), pdt)
        g_q = self.param("q_norm", ones, (dh,), pdt)
        g_k = self.param("k_norm", ones, (dh,), pdt)
        g_out = self.param("out_norm", ones, (dh,), pdt)

        def mm(x, w_):
            return jnp.einsum("bsd,dn->bsn", x.astype(self.dtype),
                              w_.astype(self.dtype),
                              preferred_element_type=jnp.float32)
        qkv = mm(h, w_qkv)
        q, k, v = (qkv[..., i * n:(i + 1) * n].reshape(b, s, hn, dh)
                   for i in range(3))
        q = rotate_half_rope(rms_norm(q, g_q, self.rms_eps), positions,
                             self.rope_theta) * dh ** -0.5
        k = rotate_half_rope(rms_norm(k, g_k, self.rms_eps), positions,
                             self.rope_theta)
        a = jnp.broadcast_to(
            lightning_log_decay(hn, self.layer, self.depth)[:, None],
            (b, s, hn, dh))
        advanced = jnp.zeros((), jnp.int32)
        if self.decode:
            _need_table(self, cache_index, block_table)
            state = self.variable(
                "cache", "linear_state", jnp.zeros,
                (self.kv_pool_pages, hn, dh, dh), self.dtype)
        if not self.decode or self.is_initializing():
            o, _ = linear_state.recurrent(q, k, v, a)
        else:
            page = self.kv_page_size
            if s > 1 and s % page:
                raise ValueError(
                    f"a call of {s} tokens is neither one token nor whole "
                    f"pages of {page}: a page it crossed would keep a "
                    f"stale state entry")
            if s > 1 and last_pos is not None:
                real = (jnp.arange(s, dtype=jnp.int32)[None, :]
                        <= last_pos[:, None])[..., None, None]
                a, k = jnp.where(real, a, 0.0), jnp.where(real, k, 0.0)
            ends = _entry_ends(last_pos, b, s, page)
            pages = _entry_pages(cache_index, ends, block_table, page)
            advanced = jnp.sum(pages[:, 0] != 0, dtype=jnp.int32)
            if s == 1:
                use_pallas = self.use_pallas
                if use_pallas is None:
                    use_pallas = jax.default_backend() == "tpu"
                one = (q[:, 0], k[:, 0], v[:, 0], a[:, 0], None,
                       block_table, cache_index)
                if use_pallas:
                    o, state.value = linear_state.linear_state_decode(
                        state.value, *one, page_size=page,
                        interpret=use_pallas == "interpret")
                else:
                    o, state.value = linear_state.paged_step(
                        state.value, *one, page_size=page)
                o = o[:, None]
            else:
                before = _carry_page(cache_index, block_table, page)
                start = jnp.where(
                    (cache_index > 0)[:, None, None, None],
                    state.value[before].astype(jnp.float32), 0.0)
                o, states = linear_state.chunked(
                    q, k, v, a, None, start,
                    block=math.gcd(page, linear_state.NOERASE_BLOCK),
                    emit_every=page)
                state.value = state.value.at[pages.reshape(-1)].set(
                    states.reshape((-1,) + states.shape[2:]
                                   ).astype(state.value.dtype))
        o = rms_norm(o, g_out, self.rms_eps).reshape(b, s, n)
        y = mm(o * jax.nn.sigmoid(mm(h, w_gate)), w_out)
        return y, advanced


# lanes a latent cache row is stored in: the TPU tiles the last axis by
# 128, so a row of 576 values occupies 640 in HBM however it is declared
# (Mosaic refuses a page DMA of 576 lanes); the pad lanes hold zeros
_LANES = 128


def latent_row_lanes(kv_lora_rank: int, qk_rope_head_dim: int) -> int:
    return -(-(kv_lora_rank + qk_rope_head_dim) // _LANES) * _LANES


def layer_norm(x, scale, bias, eps: float):
    """LayerNorm over the last axis in f32, returned in x's dtype."""
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, -1, keepdims=True)
    var = jnp.mean((x32 - mean) ** 2, -1, keepdims=True)
    return ((x32 - mean) * jax.lax.rsqrt(var + eps)
            * scale.astype(jnp.float32) + bias.astype(jnp.float32)
            ).astype(x.dtype)


class LightningIndexer(nn.Module):
    """The CHOOSER of a ``full`` layer of learned sparse attention
    (``ops/index_select.py``), with parameters of its own: ``heads`` index
    queries of ``head_dim`` a token off the attention's QUERY LATENT, ``q_tj
    = c_q W_q[j]``; ONE index key a token, ``k_s = LayerNorm(h W_k)``;
    rotary positions on the first ``rope_dim`` values of both; a weight a
    head, ``w_t = h W_w * heads**-0.5 * head_dim**-0.5``.  A query at ``t``
    attends the ``top`` positions with the largest ``I(t, s) = sum_j w_tj
    relu(q_tj . k_s)`` over ``s <= t`` (all of them while ``t < top``).

    Decode mode keeps the index keys as a THIRD kind of row in the pool:
    the leaf ``index_key`` ``[P, page, head_dim]`` (a ``latent_pool``: one
    row a token, all heads').  The call writes the call's keys and returns
    what the choice is made from — ``(q, w, the pool)`` — and
    :func:`indexed_latent_attention` makes it where a chunk needs one.
    Outside decode mode (and at init) it returns the choice itself: bool
    ``[B, S, S]``."""
    heads: int
    head_dim: int
    top: int
    rope_dim: int
    rope_theta: float
    rope_interleave: bool
    dtype: Any
    param_dtype: Any
    decode: bool = False
    kv_page_size: Optional[int] = None
    kv_pool_pages: Optional[int] = None
    norm_eps: float = 1e-6

    @nn.compact
    def __call__(self, h, c_q, positions, cache_index=None, block_table=None):
        b, s, d = h.shape
        hn, dh, pdt = self.heads, self.head_dim, self.param_dtype
        w_q = self.param("q", _normal(0.02), (c_q.shape[-1], hn * dh), pdt)
        w_k = self.param("k", _normal(0.02), (d, dh), pdt)
        g_k = self.param("k_norm", nn.initializers.ones, (dh,), pdt)
        b_k = self.param("k_norm_bias", nn.initializers.zeros, (dh,), pdt)
        w_w = self.param("weights", _normal(0.02), (d, hn), pdt)
        rope = interleaved_rope if self.rope_interleave else rotate_half_rope

        def mm(x, w_):
            return jnp.einsum("bsd,dn->bsn", x.astype(self.dtype),
                              w_.astype(self.dtype),
                              preferred_element_type=jnp.float32)

        def turned(x):      # [B, S, H, D]: the first rope_dim values turn
            r = self.rope_dim
            return jnp.concatenate(
                [rope(x[..., :r], positions, self.rope_theta), x[..., r:]],
                -1).astype(self.dtype)
        q = turned(mm(c_q, w_q).reshape(b, s, hn, dh))
        k = turned(layer_norm(mm(h, w_k), g_k, b_k, self.norm_eps
                              )[:, :, None])[:, :, 0]
        w = mm(h, w_w) * (hn ** -0.5 * dh ** -0.5)
        if not self.decode:
            return index_select.members(
                index_select.scores(q, w, k, positions), self.top)
        keys = self.variable("cache", "index_key", jnp.zeros,
                             (self.kv_pool_pages, self.kv_page_size, dh),
                             self.dtype)
        if self.is_initializing():
            return None
        keys.value = write_pages(
            keys.value, k, block_table, cache_index,
            page_aligned=s > 1 and s % self.kv_page_size == 0)
        return q, w, keys.value


def indexed_latent_attention(q, pool, block_table, cache_index, *, top,
                             picked, chosen, scale, value_lanes,
                             use_pallas, expand=None):
    """Latent attention of a decode-mode call over the rows each query
    CHOSE, the call's rows already in ``pool``.  ``picked`` — a ``full``
    layer's :class:`LightningIndexer` output ``(q, w, index keys)`` — makes
    the choice; None (a ``shared`` layer) takes ``chosen``, the choice of
    the nearest ``full`` layer below.  ABSORBED (``expand`` None): q
    [B, S, H, W] carried into the row's space, returns ``(o [B, S, H,
    value_lanes], chosen)``.  EXPANDED (a chunk where ``latent_expands``):
    q [B, S, H, nope + rope] as projected and rotated and ``expand`` ``(the
    call's rows [B, S, W], kv_b [value_lanes, H, nope + Dv], nope)``,
    returns ``(o [B, S, H, Dv], chosen)``.

    The choice's form follows the path.  The kernels' (a chunk of whole
    tiles, or one token, on the TPU or interpreted): tiled membership
    (``index_select.chunk_select`` / ``decode_select``) that
    ``latent_sparse_chunk`` / ``latent_sparse_decode`` mask by, and that an
    expanded chunk walks its keys under (``latent_chunk_attention``); a
    chunk no query of which sees more than ``top`` rows goes through the
    kernel every latent model uses and chooses nothing (zeros).
    Elsewhere: bool ``[B, S, L]`` on the gather oracle (absorbed) or the
    walk in plain JAX (expanded)."""
    b, s = q.shape[:2]
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if s > 1 and s % index_select.CHUNK_QUERIES:
        use_pallas = False
    t = cache_index[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]

    def expanded(member=None):
        rows, w_kvb, nope = expand
        return latent_chunk_attention(
            q, rows, w_kvb, pool, block_table, cache_index, rank=value_lanes,
            nope=nope, scale=scale, member=member, use_pallas=use_pallas)
    if not use_pallas:
        if picked is not None:
            q_i, w_i, keys = picked
            chosen = index_select.members(index_select.scores(
                q_i, w_i, gather_pages(keys, block_table), t),
                top)
        if expand is not None:
            return expanded(chosen), chosen
        return latent_sparse_attention(
            q, pool, block_table, chosen, value_lanes=value_lanes,
            scale=scale), chosen
    interpret = use_pallas == "interpret"
    if s == 1:
        if picked is not None:
            q_i, w_i, keys = picked
            chosen = index_select.decode_select(
                q_i[:, 0], w_i[:, 0], keys, block_table, cache_index,
                k=top, interpret=interpret)
        o = latent_sparse_decode(
            q[:, 0], pool, block_table, cache_index, chosen,
            value_lanes=value_lanes, scale=scale, interpret=interpret)
        return o[:, None], chosen
    blocks = index_select.member_blocks(block_table.shape[1], pool.shape[1])
    tile = index_select.CHUNK_QUERIES
    shape = (b, s // tile, blocks, tile, index_select.MEMBER_BLOCK)

    def dense():
        o = expanded() if expand is not None else paged_attention_auto(
            q, pool, None, block_table, cache_index, use_pallas=use_pallas,
            scale=scale, value_lanes=value_lanes)
        return o, (jnp.zeros(shape, jnp.int8) if picked is not None
                   else chosen)

    def sparse():
        member = chosen
        if picked is not None:
            q_i, w_i, keys = picked
            member = index_select.chunk_select(
                q_i, w_i, keys, block_table, cache_index, k=top,
                interpret=interpret)
        if expand is not None:
            return expanded(member), member
        return latent_sparse_chunk(
            q, pool, block_table, cache_index, member,
            value_lanes=value_lanes, scale=scale,
            interpret=interpret), member
    return jax.lax.cond(jnp.all(cache_index + s <= top), dense, sparse)


class LatentAttention(nn.Module):
    """Multi-head latent attention.  A token's keys and values, for every
    head, come from one latent ``c_kv`` [kv_lora_rank] (after its own
    RMSNorm) through ``kv_b``, plus ONE rotary key ``k_rope``
    [qk_rope_head_dim] that all heads share; queries come from a latent
    ``c_q`` [q_lora_rank] likewise.  ``score_h(i, j) = (q_nope_h,i .
    k_nope_h,j + q_rope_h,i . k_rope_j) / sqrt(nope + rope)``.

    The cache holds, a token a layer, the row ``[c_kv | k_rope | 0]``
    (bf16 in serving; ``latent_row_lanes`` lanes).  Decode mode takes one
    of two forms of the same product, by the call's static shape and the
    layer's widths alone (``ops.paged_attention.latent_expands``).
    ABSORBED — a decode step, and any call too short to repay an
    expansion: ``q~_h = q_nope_h kv_b[K, h]^T`` meets ``c_kv`` directly,
    the values are ``c_kv`` itself and ``kv_b[V, h]`` is applied to the
    attended sum — equal in exact arithmetic, 32 heads over one cached
    row, ``kv_b`` used as held; the cheapest form for ONE query a row
    (1,088 multiply-adds a (query, key, head) at nope / rope / v 128 / 64
    / 128 over rank 512).  EXPANDED — a prefill chunk (from 158 queries at
    those widths): a visible key goes through ``kv_b`` once a chunk and
    meets the chunk's queries at ``nope + rope + v`` = 320 a head
    (``latent_chunk_attention``: the chunk against itself causally, the
    pages under its start in a walk as long as the start asks); the rows
    are written to the pages as ever.  An ``indexer`` layer goes by the
    same rule: its decode step and a chunk too short to repay an expansion
    attend absorbed under the membership (``latent_sparse_decode``,
    ``latent_sparse_chunk``), a longer chunk EXPANDED with the membership,
    a row a query, as the mask of every block of the walk (512 lane-products
    a (query, key, head) at nope / rope / v 192 / 64 / 256, the rotary key
    in each head's row, against the absorbed 1,152).  Outside decode mode
    (tests, the toy's teacher-forced forward) K and V of every token are
    expanded from the definition.

    ``q_lora_rank`` None: the queries come from ``h`` directly (one
    projection ``q``, no query latent and no norm of one).
    ``q_head_norm``: RMSNorm over the ``nope + rope`` values of each query
    head, one learned scale, before the rotation (the key side's norm is
    ``kv_norm``: a norm on expanded keys would forbid the absorbed form).
    ``head_gate``: a head's attended output is scaled by ``sigmoid(h
    W_gate)`` of that head before ``out``.

    ``indexer`` (kind, heads, head dim, top, rotary dims) — LEARNED SPARSE
    ATTENTION over the same cache: a query attends the ``top`` rows a
    lightning indexer chose (:class:`LightningIndexer`, off this layer's
    query latent) and no other.  Kind ``full``: the layer has an indexer
    (``attn/indexer``) and chooses; ``shared``: it attends over ``chosen``,
    the choice handed in.  The call then returns ``(out, chosen)``."""
    num_heads: int
    q_lora_rank: Optional[int]
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float
    rope_interleave: bool
    rms_eps: float
    dtype: Any
    param_dtype: Any
    use_pallas: Any = None
    decode: bool = False
    kv_page_size: Optional[int] = None
    kv_pool_pages: Optional[int] = None
    q_head_norm: bool = False
    head_gate: bool = False
    indexer: Optional[Tuple] = None

    @nn.nowrap
    def expands(self, s: int) -> bool:
        """Whether a decode-mode call of ``s`` queries a row attends
        EXPANDED (``ops.paged_attention.latent_expands``: by the call's
        shape and the layer's widths).  ``__call__`` goes by it and the
        model names a count by it (``RoutedDecoderLM.latent_expanded``)."""
        r, dr = self.kv_lora_rank, self.qk_rope_head_dim
        return self.decode and latent_expands(
            s, self.num_heads, latent_row_lanes(r, dr), r,
            self.qk_nope_head_dim, dr, self.v_head_dim)

    @nn.compact
    def __call__(self, h, positions, cache_index=None, block_table=None,
                 window_pages: Optional[int] = None, chosen=None):
        b, s, d = h.shape
        hq, rq, r = self.num_heads, self.q_lora_rank, self.kv_lora_rank
        dn, dr, dv = (self.qk_nope_head_dim, self.qk_rope_head_dim,
                      self.v_head_dim)
        ones, pdt = nn.initializers.ones, self.param_dtype
        if rq is None:
            w_q = self.param("q", _normal(0.02), (d, hq * (dn + dr)), pdt)
        else:
            w_qa = self.param("q_a", _normal(0.02), (d, rq), pdt)
            g_q = self.param("q_norm", ones, (rq,), pdt)
            w_qb = self.param("q_b", _normal(0.02), (rq, hq * (dn + dr)),
                              pdt)
        w_kva = self.param("kv_a", _normal(0.02), (d, r + dr), pdt)
        g_kv = self.param("kv_norm", ones, (r,), pdt)
        w_kvb = self.param("kv_b", _normal(0.02), (r, hq * (dn + dv)), pdt)
        w_out = self.param("out", _normal(0.02), (hq * dv, d), pdt)
        rope = interleaved_rope if self.rope_interleave else rotate_half_rope

        def mm(spec, x, w):
            return jnp.einsum(spec, x.astype(self.dtype),
                              w.astype(self.dtype),
                              preferred_element_type=jnp.float32)
        if rq is None:
            q = mm("bsd,dn->bsn", h, w_q).reshape(b, s, hq, dn + dr)
        else:
            c_q = rms_norm(mm("bsd,dr->bsr", h, w_qa), g_q, self.rms_eps)
            q = mm("bsr,rn->bsn", c_q, w_qb).reshape(b, s, hq, dn + dr)
        if self.q_head_norm:
            q = rms_norm(q, self.param("q_head_norm", ones, (dn + dr,), pdt),
                         self.rms_eps)
        picked = None
        if self.indexer is not None and self.indexer[0] == "full":
            if rq is None:
                raise ValueError("the indexer's queries come off the query "
                                 "latent: q_lora_rank is needed")
            picked = LightningIndexer(
                *self.indexer[1:], self.rope_theta, self.rope_interleave,
                self.dtype, pdt, decode=self.decode,
                kv_page_size=self.kv_page_size,
                kv_pool_pages=self.kv_pool_pages, name="indexer")(
                    h, c_q, positions, cache_index, block_table)
        q_nope = q[..., :dn].astype(self.dtype)
        q_rope = rope(q[..., dn:], positions, self.rope_theta
                      ).astype(self.dtype)
        kv = mm("bsd,dr->bsr", h, w_kva)
        c_kv = rms_norm(kv[..., :r], g_kv, self.rms_eps).astype(self.dtype)
        k_rope = rope(kv[..., None, r:], positions, self.rope_theta
                      )[:, :, 0].astype(self.dtype)
        w_kvb = w_kvb.astype(self.dtype).reshape(r, hq, dn + dv)
        scale = 1.0 / ((dn + dr) ** 0.5)
        if self.decode:
            _need_table(self, cache_index, block_table)
            pad = latent_row_lanes(r, dr) - r - dr
            row = jnp.concatenate(
                [c_kv, k_rope, jnp.zeros((b, s, pad), self.dtype)], -1)
            expands = self.expands(s)
            if expands:
                # a chunk: its KEYS go through kv_b, once each, and not
                # its queries; o comes back a head's own [.., hq, dv]
                q_att = jnp.concatenate([q_nope, q_rope], -1)
            else:
                q_att = jnp.einsum("bshn,rhn->bshr", q_nope, w_kvb[..., :dn],
                                   preferred_element_type=jnp.float32
                                   ).astype(self.dtype)
                q_att = jnp.concatenate(
                    [q_att, q_rope, jnp.zeros((b, s, hq, pad), self.dtype)],
                    -1)
            if self.indexer is None:
                o = paged_cache_attention(
                    self, q_att, row, None, cache_index, block_table,
                    window_pages=window_pages, scale=scale, value_lanes=r,
                    expand=(w_kvb, dn) if expands else None)
            else:
                pool = self.variable(
                    "cache", "paged_latent", jnp.zeros,
                    (self.kv_pool_pages, self.kv_page_size, row.shape[-1]),
                    self.dtype)
                o = jnp.zeros(q_att.shape[:-1] + (dv if expands else r,),
                              self.dtype)
                if not self.is_initializing():
                    pool.value = write_pages(
                        pool.value, row, block_table, cache_index,
                        page_aligned=s > 1 and s % self.kv_page_size == 0)
                    o, chosen = indexed_latent_attention(
                        q_att, pool.value, block_table, cache_index,
                        top=self.indexer[3], picked=picked, chosen=chosen,
                        scale=scale, value_lanes=r,
                        use_pallas=self.use_pallas,
                        expand=(row, w_kvb, dn) if expands else None)
            if not expands:
                o = jnp.einsum("bshr,rhv->bshv", o, w_kvb[..., dn:],
                               preferred_element_type=jnp.float32
                               ).astype(self.dtype)
        else:
            # the whole sequence at once, from the definition
            kv_all = jnp.einsum("bsr,rhn->bshn", c_kv, w_kvb,
                                preferred_element_type=jnp.float32
                                ).astype(self.dtype)
            k = jnp.concatenate(
                [kv_all[..., :dn],
                 jnp.broadcast_to(k_rope[:, :, None], (b, s, hq, dr))], -1)
            i = jnp.arange(s)
            mask = jnp.broadcast_to(i[None, :] <= i[:, None], (b, s, s))
            if self.indexer is not None:
                chosen = mask = picked if picked is not None else chosen
            o = cached_attention(
                jnp.concatenate([q_nope, q_rope], -1), k, kv_all[..., dn:],
                mask)
        if self.head_gate:
            gate = jax.nn.sigmoid(mm(
                "bsd,dh->bsh", h, self.param("gate", _normal(0.02), (d, hq),
                                             pdt)))
            o = (o * gate[..., None]).astype(self.dtype)
        out = mm("bsn,nd->bsd", o.reshape(b, s, hq * dv), w_out)
        return out if self.indexer is None else (out, chosen)


class Mixer(NamedTuple):
    """A layer's mixer: a kind of ``_KINDS`` and that kind's class's
    constructor arguments BY NAME (all but the common ones)."""
    kind: str
    args: Tuple[Tuple[str, Any], ...]

    @property
    def cls(self):
        return _KINDS[self.kind].cls

    def arg(self, name: str):
        return dict(self.args)[name]

    def module(self, common, **more):
        """The kind's module: these arguments and those of ``common``
        (:func:`_common`'s pairs) that the class declares.  Unbound, it
        answers for its layer (``walks``, ``expands``) where nothing is
        applied."""
        return self.cls(**dict(self.args), **more, **{
            k: v for k, v in common if k in self.cls.__dataclass_fields__})


def _common(owner):
    """The arguments every mixer of a model takes, read off a block or the
    model (they call them the same), as hashable pairs."""
    return tuple((k, getattr(owner, k)) for k in (
        "rms_eps", "dtype", "param_dtype", "use_pallas", "decode",
        "kv_page_size", "kv_pool_pages"))


def _layers_that(specs, common, answer: str, s: int) -> int:
    """How many of ``specs``' mixers say ``answer(s)``, each distinct one
    asked once, as an unbound module — a kind that has no such form has no
    such method and never does."""
    asked = collections.Counter(spec.mixer for spec in specs)
    return sum(n for mixer, n in asked.items() if hasattr(mixer.cls, answer)
               and getattr(mixer.module(common, parent=None), answer)(s))


def _mixer(kind: str, **args) -> Mixer:
    return Mixer(kind, tuple(args.items()))


# a routed MLP (the module's docstring, **MLP kind**), under the model's own
# names; ``experts_held`` (first id, count) or None
Routed = collections.namedtuple("Routed", (
    "num_experts experts_per_token expert_width routing routed_scale "
    "router_bias_stddev routing_sum_eps route_groups route_groups_kept "
    "experts_held router_input shared_expert_width shared_expert_gate"))


class Mlp(NamedTuple):
    dense_width: Optional[int]      # set: a dense gated MLP, no router
    routed: Optional[Tuple]         # a ``Routed`` where that is None
    activation: str


class LayerSpec(NamedTuple):
    """What one layer is (``RoutedDecoderLM.layer_specs``).  ``window``:
    the model's sliding window in tokens whether or not THIS layer slides —
    ``kv_tokens_read_window`` is clipped by it at weight 0 too, a term the
    recorded decode bodies hold."""
    mixer: Mixer
    mlp: Mlp
    window: int


class _Kind(NamedTuple):
    cls: Any
    name: str           # the mixer's place in the layer's parameter tree
    takes: str          # of RoutedBlock's call arguments, in the class's order
    beside: Optional[str] = None    # what it returns beside the output


_HEADS = "positions cache_index block_table flash_prefill window_pages"
_LATENT = "positions cache_index block_table window_pages chosen"
_STATE = "cache_index block_table last_pos"
# every kind a layer's mixer can be.  The first four are what a
# configuration calls ``attention`` (whole heads, whole heads under
# ``summary_window``, the latent cache, the latent cache under an
# ``indexer``); the others are ``MIXERS``' own words
_KINDS = {
    "attention": _Kind(GroupedQueryAttention, "attn", _HEADS),
    "summary": _Kind(GroupedQueryAttention, "attn", _HEADS),
    "latent": _Kind(LatentAttention, "attn", _LATENT),
    "indexed_latent": _Kind(LatentAttention, "attn", _LATENT, "chosen"),
    "short_conv": _Kind(ShortConv, "conv", _STATE, "advanced"),
    "linear_delta": _Kind(LinearDelta, "linear", _STATE, "advanced"),
    "sparse_block": _Kind(
        SparseBlockAttention, "attn",
        "cache_index block_table flash_prefill window_pages", "streamed"),
    "lightning": _Kind(LightningAttention, "linear", "positions " + _STATE,
                       "advanced"),
}


class RoutedBlock(nn.Module):
    spec: LayerSpec
    rms_eps: float
    dtype: Any
    param_dtype: Any
    use_pallas: Any = None
    decode: bool = False
    kv_page_size: Optional[int] = None
    kv_pool_pages: Optional[int] = None
    norm_unit_offset: bool = False
    residual_scale: float = 1.0         # on both branches of the layer

    @nn.compact
    def __call__(self, x, positions, cache_index=None, block_table=None,
                 flash_prefill: bool = False,
                 window_pages: Optional[int] = None, last_pos=None,
                 chosen=None):
        """-> (x, rows an expert held here [E] or None for a dense layer,
        state rows advanced or None for an attention layer, the blocks a
        ``sparse_block`` layer's chunk tiles copied or None, the rows an
        ``indexer`` layer's queries chose — its own choice or the one handed
        in as ``chosen`` — or None)."""
        b, s, d = x.shape
        mlp, routed = self.spec.mlp, self.spec.mlp.routed
        pdt, offset = self.param_dtype, self.norm_unit_offset
        g1 = self.param("norm1", _norm_init(offset), (d,), pdt)
        g2 = self.param("norm2", _norm_init(offset), (d,), pdt)
        score_bias = None
        if routed is not None:
            e, f = routed.num_experts, routed.expert_width
            held = e if routed.experts_held is None else routed.experts_held[1]
            w_router = self.param("router", _normal(0.02), (d, e), pdt)
            w_gate_up = self.param("gate_up", _normal(0.02),
                                   (held, d, 2 * f), pdt)
            w_down = self.param("down", _normal(0.02), (held, f, d), pdt)
            if routed.routing == "sigmoid_bias":
                # f32 whatever param_dtype: it meets f32 scores
                score_bias = self.param(
                    "router_bias", _normal(routed.router_bias_stddev), (e,),
                    jnp.float32)

        def choose(hh):
            return route(hh.reshape(b * s, d), w_router,
                         routed.experts_per_token, score_bias,
                         routed.routed_scale, routed.routing_sum_eps,
                         routed.route_groups, routed.route_groups_kept)
        h = rms_norm(x, g1, self.rms_eps, offset)
        if routed is not None and routed.router_input == "pre_attention":
            idx, weights = choose(h)
        kind = _KINDS[self.spec.mixer.kind]
        call = dict(positions=positions, cache_index=cache_index,
                    block_table=block_table, flash_prefill=flash_prefill,
                    window_pages=window_pages, last_pos=last_pos,
                    chosen=chosen)
        attn = self.spec.mixer.module(_common(self), name=kind.name)(
            h, *(call[a] for a in kind.takes.split()))
        beside = dict(advanced=None, streamed=None, chosen=chosen)
        if kind.beside is not None:
            attn, beside[kind.beside] = attn
        if self.residual_scale != 1.0:
            attn = attn * self.residual_scale
        x = x + attn
        h2 = rms_norm(x, g2, self.rms_eps, offset).reshape(b * s, d)

        def mlp_of(name, width):        # the dense layers' and the shared
            return gated_mlp(
                h2.astype(self.dtype),
                self.param(name + "_gate_up", _normal(0.02), (d, 2 * width),
                           pdt).astype(self.dtype),
                self.param(name + "_down", _normal(0.02), (width, d),
                           pdt).astype(self.dtype),
                mlp.activation)
        sizes = None
        if routed is None:
            y = mlp_of("dense", mlp.dense_width)
        else:
            if routed.router_input != "pre_attention":
                idx, weights = choose(h2)
            y, sizes = routed_experts(h2.astype(self.dtype), idx, weights,
                                      w_gate_up.astype(self.dtype),
                                      w_down.astype(self.dtype),
                                      use_pallas=self.use_pallas,
                                      activation=mlp.activation,
                                      held=routed.experts_held)
            if routed.shared_expert_width:
                shared = mlp_of("shared", routed.shared_expert_width)
                if routed.shared_expert_gate:
                    shared = shared * jax.nn.sigmoid(jnp.einsum(
                        "td,dn->tn", h2.astype(self.dtype),
                        self.param("shared_gate", _normal(0.02), (d, 1),
                                   pdt).astype(self.dtype),
                        preferred_element_type=jnp.float32))
                y = y + shared
        if self.residual_scale != 1.0:
            y = y * self.residual_scale
        return (x + y.reshape(b, s, d), sizes, beside["advanced"],
                beside["streamed"], beside["chosen"])


# what a call hands the arithmetic of ``COUNTS``: its shape and arguments
# (``offset`` [1, S] a token's place in the call, ``live`` [B] the positions a
# row holds after it, ``page`` the page's tokens, ``itemsize`` a cached
# value's bytes), the forms its shape chose (``latent_expanded(s)``,
# ``layers_walking(s)`` — 0 on a first chunk) and what the layers handed up
_Call = collections.namedtuple("_Call", (
    "b s positions offset live last_pos cache_index block_table decode page "
    "itemsize expanded walking advanced streamed picked_rows"))


def _of(specs, kind: str):
    return [spec.mixer for spec in specs if spec.mixer.kind == kind]


def _over_real_queries(c):
    """``x -> sum of x [B, S] over the call's real queries``: not tail
    padding, not an idle row."""
    real = jnp.ones((c.b, c.s), bool)
    if c.last_pos is not None:
        real &= c.offset <= c.last_pos[:, None]
    if c.decode and c.block_table is not None:
        real &= (c.block_table[:, :1] != 0)
    return lambda x: jnp.sum(jnp.where(real, x, 0), dtype=jnp.int32)


def _sparse_counts(specs, c):
    sparse = _of(specs, "sparse_block")
    sizes = block_select.Sizes(*sparse[0].arg("sizes")[:7])
    over = _over_real_queries(c)
    own = c.positions // sizes.block + 1
    dense = c.positions + 1 <= sizes.dense_len
    pairs = len(sparse) * sparse[0].arg("num_kv_heads")
    return [pairs * over(own),
            pairs * over(jnp.where(dense, own, sizes.read)),
            len(sparse) * over(jnp.where(
                dense, 0, block_select.pooled_exist(c.positions, sizes))),
            over(dense.astype(jnp.int32)),
            len(_of(specs, "lightning")) * over(1), c.advanced, c.streamed]


def _indexed_counts(specs, c):
    indexers = [spec.mixer.arg("indexer") for spec in specs]
    over = _over_real_queries(c)
    top = indexers[0][3]
    seen = c.positions + 1
    counts = [
        sum(i[0] == "full" for i in indexers) * over(
            jnp.where(seen > top, seen, 0)),
        len(specs) * over(seen),
        # a query that sees top rows or fewer attends them all, through
        # the dense kernel where the whole chunk does (a membership of
        # zeros: nothing was chosen)
        over(jnp.where(seen <= top, len(specs) * seen, c.picked_rows)),
        over((seen <= top).astype(jnp.int32))]
    if c.expanded:
        counts.append(len(specs) * jnp.sum(latent_rows_expanded(
            c.cache_index, c.s, c.page, c.block_table.shape[1])))
    return counts


def _summary_counts(specs, c):
    window, chunk = specs[0].mixer.arg("summary")
    closed = c.positions[:, -1] // window
    exact = len(specs) * jnp.sum(c.positions[:, -1] - closed * window + 1)
    return [exact, len(specs) * jnp.sum(closed) * (window // chunk)]


def _latent_counts(specs, c):
    live = c.live
    if c.expanded:
        live = latent_rows_expanded(c.cache_index, c.s, c.page,
                                    c.block_table.shape[1])
    latent = len(_of(specs, "latent"))
    counts = [latent * jnp.sum(live)]
    if len(specs) > latent:
        # the tokens of the call that are not tail padding
        real = (c.b * c.s if c.last_pos is None
                else jnp.sum(c.last_pos.astype(jnp.int32) + 1))
        counts += [real * (len(specs) - latent), c.advanced]
    return counts


def _heads_counts(specs, c):
    heads = _of(specs, "attention")
    n_window = sum(m.arg("window") is not None for m in heads)
    counts = [(len(heads) - n_window) * jnp.sum(c.live),
              n_window * jnp.sum(jnp.minimum(
                  c.live, specs[0].window + c.s - 1))]
    if len(specs) > len(heads):
        counts += [jnp.asarray(c.b * c.s * (len(specs) - len(heads)),
                               jnp.int32), c.advanced]
    if c.walking:
        counts.append(c.walking * jnp.sum(chunk_rows_walked(
            c.cache_index, c.s, c.page, c.block_table.shape[1],
            heads[0].arg("num_kv_heads") * heads[0].arg("head_dim")
            * c.itemsize)))
    return counts


# what a model counts by what its attention layers READ, the first kind of
# these that it has a layer of (whole heads where it has none): the names
# behind ``STATS[:3]``' three expert counts, whether the state mixers' two
# counts follow them (``_counted``), and the arithmetic — ``(specs,
# call) -> the counts``, a count a KIND's layers times a sum, in the names'
# order (and, where a call's shape chose another form, in
# ``call_stats_names``')
COUNTS = {
    "sparse_block": (STATS[:3] + SPARSE_STATS + LINEAR_STATS
                     + STREAMED_STATS, False, _sparse_counts),
    "indexed_latent": (STATS[:3] + INDEX_STATS, False, _indexed_counts),
    "summary": (SUMMARY_STATS, False, _summary_counts),
    "latent": (LATENT_STATS, True, _latent_counts),
    "attention": (STATS, True, _heads_counts),
}


def _counted(specs):
    """-> (the counts' names of a model of these layers — its row's of
    ``COUNTS`` and, where the row says so, the state mixers' two — and the
    row's arithmetic)."""
    kinds = {spec.mixer.kind for spec in specs}
    names, state, arithmetic = COUNTS[
        next((k for k in COUNTS if k in kinds), "attention")]
    if state and kinds & set(STATE_MIXERS):
        names += LINEAR_STATS if "linear_delta" in kinds else STATE_STATS[-2:]
    return names, arithmetic


class RoutedDecoderLM(nn.Module):
    """``__call__(tokens [B, S] int32) -> logits [B, S, vocab]`` f32; in
    decode mode with ``cache_index`` [B], ``block_table`` [B, M] and the
    two statics of ``TransformerLM`` (``flash_prefill``,
    ``window_pages``), which ``serve.decode.Decoder`` drives alike.  With
    ``head_pos`` [B] int32 (a prefill chunk's sampled offset) the final
    norm and the head run on that one position a row: logits [B, 1,
    vocab]."""

    vocab_size: int
    num_layers: int = 4
    d_model: int = 512
    num_heads: int = 8
    num_kv_heads: int = 2
    head_dim: int = 64
    num_experts: int = 8
    experts_per_token: int = 2
    expert_width: int = 256
    window: int = 4096
    # one entry a layer (shorter tuples repeat): window|full, rope|nope
    layer_window: Tuple[bool, ...] = (False, True, True, True)
    layer_rope: Tuple[bool, ...] = (False, True, True, True)
    rope_theta: float = 10000.0
    rms_eps: float = 1e-6
    max_seq_len: int = 2048
    # attention kind.  kv_lora_rank None: whole heads (num_kv_heads x
    # head_dim, the two layer tuples above).  Set: latent attention
    # (LatentAttention) in every attention layer, whole history, rotary on the
    # qk_rope_head_dim part only; num_kv_heads, head_dim, window and the
    # layer tuples then size nothing
    q_lora_rank: Optional[int] = None
    kv_lora_rank: Optional[int] = None
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_interleave: bool = False
    # MLP kind.  The first num_dense_layers layers have a dense gated MLP
    # of dense_width and no router; the rest route (routing:
    # softmax_topk | sigmoid_bias with a learned score bias and
    # routed_scale) from the norm router_input names (pre_attention |
    # post_attention) and add a shared expert of shared_expert_width (0:
    # none) for every token; activation relu | silu for all of them
    num_dense_layers: int = 0
    dense_width: int = 0
    shared_expert_width: int = 0
    routing: str = "softmax_topk"
    routed_scale: float = 1.0
    router_bias_stddev: float = 0.0     # the score bias's initializer
    activation: str = "relu"
    router_input: str = "pre_attention"
    routing_sum_eps: float = 0.0        # added to the chosen scores' sum
    # mixer kind, one entry a layer (shorter tuples repeat): attention |
    # short_conv (ShortConv, conv_taps taps; whole heads only) |
    # linear_delta (LinearDelta, the fields below).  qk_norm:
    # RMSNorm a head of q and of k before the rotation.  tie_head: the
    # head is the embedding
    layer_mixer: Tuple[str, ...] = ("attention",)
    conv_taps: int = 3
    qk_norm: bool = False
    tie_head: bool = False
    # linear_delta layers (LinearDelta): heads of head_dim keys and values,
    # three short filters of linear_conv_taps, log decays in
    # (linear_decay_floor, 0); the matrices are stored in dtype.  They go
    # with either attention kind
    linear_heads: int = 4
    linear_head_dim: int = 64
    linear_conv_taps: int = 4
    linear_decay_floor: float = -5.0
    # latent attention's options: q_lora_rank None (above) projects the
    # queries directly; q_head_norm: RMSNorm a query head before the
    # rotation; attention_head_gate: a sigmoid gate a head on the output
    q_head_norm: bool = False
    attention_head_gate: bool = False
    # sigmoid_bias routing's group limit: the experts are route_groups runs
    # of consecutive ids, a token chooses within its route_groups_kept best
    route_groups: int = 1
    route_groups_kept: int = 1
    # (first id, count): the experts this device holds of the num_experts
    # the router chooses among (None: all).  The others' part of a layer's
    # sum is left out; nothing here stands in for it
    experts_held: Optional[Tuple[int, int]] = None
    # summary_window set (whole heads, every attention layer): a query
    # attends the exact keys of its own window of that many positions,
    # aligned to its multiples, and one learned summary a summary_chunk
    # positions of every window before it; the summaries live in the K and
    # V pools and a row's table is compact (ops/window_summary.py).
    # norm_unit_offset: every RMSNorm scales by 1 + its learned vector
    summary_window: Optional[int] = None
    summary_chunk: int = 16
    norm_unit_offset: bool = False
    # sparse_block layers (SparseBlockAttention; num_heads over num_kv_heads
    # of head_dim, no positions): (block, pool, stride, top, window, init
    # blocks, dense_len, q/k norm gain).  lightning layers
    # (LightningAttention; rope_theta): (heads, head dim, the first layer's
    # index in the published stack, the published depth) — a layer's decay
    # follows from ITS published index.  mup: (embedding scale, depth
    # scale, published depth, hidden / base width): the embedding is
    # multiplied by the first, every residual branch by depth scale /
    # sqrt(published depth), the final hidden rows divided by the last
    sparse: Optional[Tuple] = None
    lightning: Optional[Tuple] = None
    mup: Optional[Tuple] = None
    # learned sparse attention over the latent cache: indexer (heads, head
    # dim, top, rotary dims) and layer_indexer, one entry a layer — full:
    # the layer has a LightningIndexer and chooses the top rows a query
    # attends; shared: it attends over the choice of the nearest full
    # layer below, the one value that crosses layers
    indexer: Optional[Tuple] = None
    layer_indexer: Tuple[str, ...] = ()
    # the GATED forms.  linear_delta layers: linear_key_heads (None: as
    # many as linear_heads) key heads, each met by linear_heads //
    # linear_key_heads value heads' states; linear_decay channel | head —
    # head: the log decay is ONE SCALAR a head, -exp(a_log) * softplus(h
    # W_a + dt_bias), and linear_decay_floor is not read; linear_gate
    # sigmoid (a projection of its own) | silu (z, part of the one
    # projection, on a plain-weight norm).  Whole-head attention layers:
    # rotary_dim (None: the whole head) lanes of a head turn;
    # attention_output_gate: the query projection carries a gate a (head,
    # lane) whose sigmoid multiplies the heads' outputs; q_norm and k_norm
    # follow norm_unit_offset and START at qk_norm_gain (an initialiser:
    # what random weights need for a softmax that is not flat).
    # shared_expert_gate: the shared expert's output times sigmoid(h2 w),
    # one scalar a token
    linear_key_heads: Optional[int] = None
    linear_decay: str = "channel"
    linear_gate: str = "sigmoid"
    rotary_dim: Optional[int] = None
    attention_output_gate: bool = False
    shared_expert_gate: bool = False
    qk_norm_gain: float = 1.0
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    use_pallas: Any = None
    decode: bool = False
    kv_page_size: Optional[int] = None
    kv_pool_pages: Optional[int] = None
    # serve.decode.make_decode_model names it; this family has no
    # tensor-parallel layout yet
    model_axis: Optional[str] = None

    @nn.nowrap
    def layer_specs(self) -> Tuple[LayerSpec, ...]:
        """What every layer is, one :class:`LayerSpec` a layer: THE reader
        of the mixer and MLP fields above (the block, the counts and the
        forms a call's shape chooses all go by its result), and where a
        configuration that describes no layer is refused — before anything
        is traced; callable on an unbound model."""
        n = self.num_layers
        latent = self.kv_lora_rank is not None
        if not latent and self.num_heads % self.num_kv_heads:
            raise ValueError(f"num_heads {self.num_heads} is no multiple of "
                             f"num_kv_heads {self.num_kv_heads}")
        lm = tuple(self.layer_mixer)
        mixers = [lm[i % len(lm)] for i in range(n)]
        if set(mixers) - set(MIXERS):
            raise ValueError(f"layer_mixer {self.layer_mixer!r}: each one of "
                             f"{MIXERS}")
        if "short_conv" in mixers and latent:
            raise ValueError("short_conv layers go with whole heads, not "
                             "with the latent cache")
        lw, lr = tuple(self.layer_window), tuple(self.layer_rope)
        windows = [None if latent or not lw[i % len(lw)] else int(self.window)
                   for i in range(n)]
        summary = None
        if self.summary_window is not None:
            if latent or set(mixers) & set(STATE_MIXERS) or any(
                    w is not None for w in windows):
                raise ValueError(
                    "summary_window goes with whole heads in every layer: "
                    "no latent cache, no state layer, no sliding window")
            if self.summary_window % self.summary_chunk:
                raise ValueError(
                    f"summary_window {self.summary_window} is not whole "
                    f"chunks of {self.summary_chunk}")
            summary = (int(self.summary_window), int(self.summary_chunk))
        if self.experts_held is not None and (
                self.experts_held[0] < 0 or self.experts_held[1] < 1
                or sum(self.experts_held) > self.num_experts):
            raise ValueError(f"experts_held {self.experts_held!r} is no "
                             f"block of the {self.num_experts} experts")
        kinds_i = tuple(self.layer_indexer)
        if self.indexer is not None and (
                not latent or set(mixers) != {"attention"}
                or len(kinds_i) != n or set(kinds_i) - {"full", "shared"}
                or kinds_i[0] != "full"):
            raise ValueError(
                f"an indexer chooses rows of the latent cache: every layer "
                f"latent attention, layer_indexer {self.layer_indexer!r} "
                f"one of full | shared a layer, the first of them full")
        if self.routing not in ("softmax_topk", "sigmoid_bias"):
            raise ValueError(f"routing {self.routing!r}: softmax_topk or "
                             f"sigmoid_bias")
        theta = float(self.rope_theta)

        def same(*names):       # the class calls them what the model does
            return {k: getattr(self, k) for k in names}

        def mixer(i):
            word = mixers[i]
            if word == "short_conv":
                return _mixer(word, taps=self.conv_taps)
            if word == "linear_delta":
                return _mixer(
                    word, heads=self.linear_heads,
                    head_dim=self.linear_head_dim, taps=self.linear_conv_taps,
                    decay_floor=self.linear_decay_floor,
                    key_heads=self.linear_key_heads, decay=self.linear_decay,
                    gate=self.linear_gate)
            if word == "sparse_block":
                return _mixer(
                    word, sizes=tuple(self.sparse),
                    **same("num_heads", "num_kv_heads", "head_dim"))
            if word == "lightning":
                # a layer's decay follows from ITS published index
                heads, head_dim, first, depth = self.lightning
                return _mixer(word, heads=heads, head_dim=head_dim,
                              layer=first + i, depth=depth, rope_theta=theta)
            if latent:
                indexed = self.indexer is not None
                return _mixer(
                    "indexed_latent" if indexed else "latent",
                    rope_theta=theta, head_gate=self.attention_head_gate,
                    indexer=((kinds_i[i],) + tuple(self.indexer)
                             if indexed else None),
                    **same("num_heads", "q_lora_rank", "kv_lora_rank",
                           "qk_nope_head_dim", "qk_rope_head_dim",
                           "v_head_dim", "rope_interleave", "q_head_norm"))
            return _mixer(
                "attention" if summary is None else "summary",
                window=windows[i],
                rope_theta=theta if lr[i % len(lr)] else None,
                qk_norm_eps=self.rms_eps if self.qk_norm else None,
                summary=summary, output_gate=self.attention_output_gate,
                qk_norm_unit_offset=self.norm_unit_offset,
                **same("num_heads", "num_kv_heads", "head_dim", "rotary_dim",
                       "qk_norm_gain"))
        dense = Mlp(self.dense_width, None, self.activation)
        routed = Mlp(None, Routed(**dict(
            same(*Routed._fields), experts_held=self.experts_held
            and tuple(self.experts_held))), self.activation)
        return tuple(
            LayerSpec(mixer(i), dense if i < self.num_dense_layers else routed,
                      self.window) for i in range(n))

    def layer_mixers(self):
        """The mixer kind of every layer, in a configuration's words
        (``MIXERS``)."""
        return [spec.mixer.kind if spec.mixer.kind in MIXERS else "attention"
                for spec in self.layer_specs()]

    @property
    def carries_state(self) -> bool:
        """Whether the cache holds running state beside pages of history
        (a short-convolution, linear_delta or lightning layer): a state entry is
        already past its page's newest token, so that token cannot be
        replayed on a copy of the page, and a chunk's call has to be told
        its real length (``last_pos``)."""
        return bool(set(self.layer_mixers()) & set(STATE_MIXERS))

    @property
    def stats_names(self):
        """What ``"stats"/"counts"`` holds, in order (``COUNTS``)."""
        return _counted(self.layer_specs())[0]

    def latent_expanded(self, s: int) -> bool:
        """Whether the latent layers of a decode-mode call of ``s`` queries
        a row attend EXPANDED (``LatentAttention.expands``)."""
        return bool(_layers_that(self.layer_specs(), _common(self),
                                 "expands", s))

    def layers_walking(self, s: int) -> int:
        """How many layers of a decode-mode CONTINUATION call of ``s``
        queries a row attend through the walk over their K and V pools
        (``GroupedQueryAttention.walks``; layers under ``STATS``' own
        counts)."""
        return _layers_that(self.layer_specs(), _common(self), "walks", s)

    def call_stats_names(self, s: int):
        """``stats_names`` of a call of ``s`` queries a row: where its
        latent layers attend expanded they READ no cached row through the
        paged kernel — the count in that place is of the rows they carried
        through ``kv_b``, the chunk's own and the cached ones in whole
        steps of the walk, under a name of its own (with an ``indexer``:
        one count more, behind the ``INDEX_STATS`` a step has too).  Where
        layers WALK their K and V pools (``layers_walking``) a
        continuation chunk counts one thing more, last: ``kv_tokens_walked``,
        the keys the walks' steps gathered and the chunk's own, summed over
        those layers (a first chunk walks nothing and its counts end before
        it)."""
        specs, common = self.layer_specs(), _common(self)
        names = _counted(specs)[0]
        if _layers_that(specs, common, "walks", s):
            return names + ("kv_tokens_walked",)
        if not _layers_that(specs, common, "expands", s):
            return names
        if "latent_tokens_read" not in names:       # under an indexer
            return names + ("latent_tokens_expanded",)
        return tuple("latent_tokens_expanded" if n == "latent_tokens_read"
                     else n for n in names)

    def close_windows(self, params, cache, block_row, window):
        """Close window ``window`` (a traced int32) of ONE row in every
        layer of a ``summary_window`` model's paged cache (this is the
        decode-mode clone; plain trees in, no ``apply``): the window's
        pages, table entries ``[n * window, n * window + summary_window //
        page)`` of ``block_row`` [M], are read and their chunks' summaries
        written over the first ``n`` of them
        (``ops.window_summary.compact_window``, one call a layer).  The
        caller runs it before the first write into the next window.
        Returns the cache."""
        summary_window, chunk = self.layer_specs()[0].mixer.arg("summary")
        page = self.kv_page_size
        n = summary_window // chunk // page
        pages = jax.lax.dynamic_slice(block_row, (n * window,),
                                      (summary_window // page,))
        cache = dict(cache)
        for i in range(self.num_layers):
            attn = params[f"layer{i}"]["attn"]
            pools = cache[f"layer{i}"]["attn"]
            k, v = window_summary.compact_window(
                pools["paged_key"], pools["paged_value"], pages,
                attn["summary_phi"], attn["summary_mu"], chunk=chunk,
                use_pallas=self.use_pallas)
            cache[f"layer{i}"] = {"attn": {"paged_key": k,
                                           "paged_value": v}}
        return cache

    @nn.compact
    def __call__(self, tokens, train: bool = False, cache_index=None,
                 block_table=None, flash_prefill: bool = False,
                 window_pages: Optional[int] = None, last_pos=None,
                 head_pos=None):
        del train
        if self.model_axis is not None:
            raise ValueError("the routed decoder has no tensor-parallel "
                             "layout (serve it on one device)")
        specs = self.layer_specs()
        b, s = tokens.shape
        pdt = jnp.dtype(self.param_dtype)
        embed = self.param("embed", _normal(0.02),
                           (self.vocab_size, self.d_model), pdt)
        x = embed[tokens].astype(jnp.float32)      # the stream is f32
        residual_scale = 1.0
        if self.mup is not None:
            x = x * float(self.mup[0])
            residual_scale = float(self.mup[1]) / math.sqrt(self.mup[2])
        offset = jnp.arange(s, dtype=jnp.int32)[None, :]
        if self.decode:
            if cache_index is None:
                raise ValueError("decode mode needs cache_index [B] int32")
            positions = cache_index[:, None] + offset
        else:
            positions = jnp.broadcast_to(offset, (b, s))
        touched = load_max = advanced = computed = streamed = jnp.zeros(
            (), jnp.int32)
        chosen, chosen_rows, picked_rows = None, 0, 0
        for i, spec in enumerate(specs):
            x, sizes, rows, copied, chosen = RoutedBlock(
                spec, self.rms_eps, self.dtype, pdt,
                use_pallas=self.use_pallas, decode=self.decode,
                kv_page_size=self.kv_page_size,
                kv_pool_pages=self.kv_pool_pages,
                norm_unit_offset=self.norm_unit_offset,
                residual_scale=residual_scale, name=f"layer{i}")(
                    x, positions, cache_index, block_table, flash_prefill,
                    window_pages, last_pos, chosen)
            if chosen is not None:
                # what the layer's queries attended, counted from the
                # membership it went by: a full layer's own, a shared
                # layer's the count of the layer it took it from
                if spec.mixer.arg("indexer")[0] == "full":
                    chosen_rows = index_select.rows_chosen(chosen, positions)
                picked_rows = picked_rows + chosen_rows
            if sizes is not None:
                touched += jnp.sum(sizes > 0, dtype=jnp.int32)
                load_max += jnp.max(sizes)
                if spec.mlp.routed.experts_held is not None:
                    computed += jnp.sum(sizes, dtype=jnp.int32)
            if rows is not None:
                advanced += rows
            if copied is not None:
                streamed += copied
        # what the attention of this call has to read of the cache: a
        # row's whole history in a full layer (K and V, or the one latent
        # row a token), the window's reach in a window layer
        live = positions[:, -1] + 1
        routed = [spec.mlp.routed for spec in specs if spec.mlp.routed]
        assignments = jnp.asarray(
            b * s * sum(r.experts_per_token for r in routed), jnp.int32)
        if any(r.experts_held is not None for r in routed):
            assignments = computed      # the pairs computed HERE
        counts = _counted(specs)[1](
            specs, _Call(
                b, s, positions, offset, live, last_pos, cache_index,
                block_table, self.decode, self.kv_page_size,
                jnp.dtype(self.dtype).itemsize, self.latent_expanded(s),
                0 if flash_prefill else self.layers_walking(s), advanced,
                streamed, picked_rows))
        counts = jnp.stack([assignments, touched, load_max] + counts)
        n_counts = counts.shape[0]
        self.sow("stats", "counts", counts,
                 reduce_fn=lambda _, new: new,
                 init_fn=lambda: jnp.zeros((n_counts,), jnp.int32))
        if head_pos is not None:
            x = rows_at(x, head_pos)
        x = rms_norm(x, self.param("norm_f",
                                   _norm_init(self.norm_unit_offset),
                                   (self.d_model,), pdt),
                     self.rms_eps, self.norm_unit_offset)
        if self.mup is not None:
            x = x / float(self.mup[3])
        if self.tie_head:
            return jnp.einsum("bsd,vd->bsv", x.astype(self.dtype),
                              embed.astype(self.dtype),
                              preferred_element_type=jnp.float32)
        head = self.param("lm_head", _normal(0.02),
                          (self.d_model, self.vocab_size), pdt)
        return jnp.einsum("bsd,dv->bsv", x.astype(self.dtype),
                          head.astype(self.dtype),
                          preferred_element_type=jnp.float32)
