"""Decoder-only LM whose layers follow a per-layer pattern and whose MLP
is a dropless routed-expert layer — a family that is served
(``serve/decode.py``, ``serve/engine.py``), not trained, in this repo.

What a layer is comes from two tuples, one entry a layer:

  ``layer_window[l]``  True: the layer attends the last ``window``
                       positions up to the query's own; False: the whole
                       history.
  ``layer_rope[l]``    True: rotate-half rotary positions (``rope_theta``)
                       on q and k, applied to k BEFORE it is written to
                       the cache; False: no positional signal at all.

and its sizes from fields: ``num_heads`` query heads share
``num_kv_heads`` KV heads of ``head_dim`` (grouped-query attention: query
head ``i`` reads KV head ``i // (num_heads // num_kv_heads)``),
``num_experts`` gated-ReLU experts of width ``expert_width`` of which every
token takes its ``experts_per_token`` best.  Pre-norm RMSNorm blocks, no
bias anywhere, an untied output head.  There is no position table: a
position is the row's ``cache_index`` plus the offset in the chunk, so
``max_seq_len`` bounds only what the serving engine admits.

The layer, for ``x [S, d]``:

  1. ``h = RMSNorm(x)``
  2. router on ``h`` (BEFORE attention): logits in f32, the
     ``experts_per_token`` largest, softmax over those
  3. ``x += attention(h)``   (pattern above; the paged cache is
     ``models.transformer.paged_cache_attention``, shared with
     ``CausalSelfAttention``)
  4. ``x += sum_e w_e * down_e(relu(gate_e h2) * up_e h2)``,
     ``h2 = RMSNorm(x)`` — every chosen (token, expert) pair is computed:
     no capacity, nothing dropped (:func:`routed_experts`).

Parameters live in ``param_dtype`` (bfloat16 for serving: an f32 copy of
the experts would not fit beside the cache, and a per-step cast re-reads
every weight); matmuls take their inputs in ``dtype`` and accumulate in
f32; the residual stream, the norms, the router, softmax and the combine
are f32.  The f32 stream is what keeps the routing stable: with a bf16
stream the k-th and (k+1)-th router logits trade places against the f32
reference often enough that those flips alone were 0.008-0.019 of the
0.014-0.029 the served logits read against it (v5e, 12 seeds), and it
costs [tokens, d_model] words a layer beside 755e6 bytes of experts.

Every apply also yields five counts (``STATS``; summed over layers) in
the ``"stats"`` collection when the caller makes it mutable: the serving
engine puts them on its spans when tracing is on.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from dtf_tpu.models.transformer import paged_cache_attention
from dtf_tpu.ops.paged_attention import cached_attention, expand_kv_heads

# what ``"stats"/"counts"`` holds, in order
STATS = ("assignments", "experts_touched", "expert_load_max",
         "kv_tokens_read_global", "kv_tokens_read_window")

# grouped matmul tile (rows, contraction, columns): rows of one expert are
# padded to a multiple of the first inside the kernel's own bookkeeping
_GMM_ROWS = 128


def rms_norm(x, scale, eps: float):
    """``x / rms(x) * scale`` in f32, returned in x's dtype."""
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)
            * scale.astype(jnp.float32)).astype(x.dtype)


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


def route(h, w_router, k: int):
    """(expert ids [T, k], weights [T, k] f32) of the ``k`` largest router
    logits a token; softmax over the chosen (softmax over all, then
    renormalised over the chosen, is the same numbers).  f32 at full
    matmul precision: a rounding that flips the k-th choice moves the
    token's output by a whole expert, not by an ulp."""
    logits = jnp.einsum("td,de->te", h.astype(jnp.float32),
                        w_router.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    vals, idx = jax.lax.top_k(logits, k)
    return idx, jax.nn.softmax(vals, axis=-1)


def routed_experts(x, idx, weights, w_gate_up, w_down, *, use_pallas=None):
    """The dropless expert layer: ``y[t] = sum_j weights[t, j] *
    down_e(relu(gate_e x[t]) * up_e x[t])`` with ``e = idx[t, j]``.

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
    ragged_dot)."""
    t, k = idx.shape
    num_experts, _, f2 = w_gate_up.shape
    f = f2 // 2
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    flat = idx.reshape(-1)
    order = jnp.argsort(flat)                   # pairs, expert by expert
    token = order // k
    sizes = jnp.bincount(flat, length=num_experts).astype(jnp.int32)
    xs = x[token]                               # [T·k, d]

    if use_pallas:
        from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm
        interpret = use_pallas == "interpret"
        pad = -(t * k) % _GMM_ROWS
        xs = jnp.pad(xs, ((0, pad), (0, 0)))

        def grouped(lhs, rhs):
            kk, n = rhs.shape[1:]
            tile = (_GMM_ROWS, kk, next(c for c in (1280, 768, 512, 256, 128,
                                                    n) if n % c == 0))
            return gmm(lhs, rhs, sizes, jnp.float32, tile,
                       interpret=interpret)
    else:
        def grouped(lhs, rhs):
            return jax.lax.ragged_dot(lhs, rhs, sizes,
                                      preferred_element_type=jnp.float32)

    h = grouped(xs, w_gate_up)
    h = (jax.nn.relu(h[:, :f]) * h[:, f:]).astype(x.dtype)
    y = grouped(h, w_down)
    # back to the pairs' own order: a gather by the inverse permutation,
    # then the k weighted rows of a token are summed in f32
    inverse = jnp.zeros_like(order).at[order].set(
        jnp.arange(t * k, dtype=order.dtype))
    y = y[inverse].reshape(t, k, -1)
    return jnp.sum(y * weights[..., None], axis=1), sizes


def routed_experts_dense(x, idx, weights, w_gate_up, w_down):
    """The oracle of :func:`routed_experts`: every expert on every token,
    masked by the routing weights."""
    num_experts, _, f2 = w_gate_up.shape
    f = f2 // 2
    t = x.shape[0]
    full = jnp.zeros((t, num_experts), jnp.float32).at[
        jnp.arange(t)[:, None], idx].add(weights)
    h = jnp.einsum("td,edf->etf", x, w_gate_up,
                   preferred_element_type=jnp.float32)
    h = (jax.nn.relu(h[..., :f]) * h[..., f:]).astype(x.dtype)
    y = jnp.einsum("etf,efd->etd", h, w_down,
                   preferred_element_type=jnp.float32)
    return jnp.einsum("etd,te->td", y, full)


def _normal(stddev):
    return nn.initializers.normal(stddev)


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

    @nn.compact
    def __call__(self, h, positions, cache_index=None, block_table=None,
                 flash_prefill: bool = False,
                 window_pages: Optional[int] = None):
        b, s, d = h.shape
        hq, hkv, dh = self.num_heads, self.num_kv_heads, self.head_dim
        w_qkv = self.param("qkv", _normal(0.02), (d, (hq + 2 * hkv) * dh),
                           self.param_dtype)
        w_out = self.param("out", _normal(0.02), (hq * dh, d),
                           self.param_dtype)
        qkv = jnp.einsum("bsd,dn->bsn", h.astype(self.dtype),
                         w_qkv.astype(self.dtype),
                         preferred_element_type=jnp.float32
                         ).astype(self.dtype)
        q = qkv[..., :hq * dh].reshape(b, s, hq, dh)
        k = qkv[..., hq * dh:(hq + hkv) * dh].reshape(b, s, hkv, dh)
        v = qkv[..., (hq + hkv) * dh:].reshape(b, s, hkv, dh)
        if self.rope_theta is not None:
            q = rotate_half_rope(q, positions, self.rope_theta)
            k = rotate_half_rope(k, positions, self.rope_theta)
        if self.decode:
            if self.kv_page_size is None:
                raise ValueError("decode mode needs kv_page_size and "
                                 "kv_pool_pages")
            if cache_index is None or block_table is None:
                raise ValueError("decode mode needs cache_index [B] "
                                 "and block_table [B, M], both int32")
            o = paged_cache_attention(
                self, q, k, v, cache_index, block_table,
                flash_prefill=flash_prefill, window_pages=window_pages,
                window=self.window)
        else:
            # the whole sequence at once (tests, the toy): a plain mask
            kr, vr = expand_kv_heads(k, v, hq)
            i = jnp.arange(s)
            mask = i[None, :] <= i[:, None]
            if self.window is not None:
                mask &= i[None, :] > i[:, None] - self.window
            o = cached_attention(q, kr, vr,
                                 jnp.broadcast_to(mask, (b, s, s)))
        return jnp.einsum("bsn,nd->bsd", o.reshape(b, s, hq * dh),
                          w_out.astype(self.dtype),
                          preferred_element_type=jnp.float32)


class RoutedBlock(nn.Module):
    num_heads: int
    num_kv_heads: int
    head_dim: int
    num_experts: int
    experts_per_token: int
    expert_width: int
    window: Optional[int]
    rope_theta: Optional[float]
    rms_eps: float
    dtype: Any
    param_dtype: Any
    use_pallas: Any = None
    decode: bool = False
    kv_page_size: Optional[int] = None
    kv_pool_pages: Optional[int] = None

    @nn.compact
    def __call__(self, x, positions, cache_index=None, block_table=None,
                 flash_prefill: bool = False,
                 window_pages: Optional[int] = None):
        b, s, d = x.shape
        e, f = self.num_experts, self.expert_width
        ones = nn.initializers.ones
        g1 = self.param("norm1", ones, (d,), self.param_dtype)
        g2 = self.param("norm2", ones, (d,), self.param_dtype)
        w_router = self.param("router", _normal(0.02), (d, e),
                              self.param_dtype)
        w_gate_up = self.param("gate_up", _normal(0.02), (e, d, 2 * f),
                               self.param_dtype)
        w_down = self.param("down", _normal(0.02), (e, f, d),
                            self.param_dtype)
        h = rms_norm(x, g1, self.rms_eps)
        idx, weights = route(h.reshape(b * s, d), w_router,
                             self.experts_per_token)
        x = x + GroupedQueryAttention(
            self.num_heads, self.num_kv_heads, self.head_dim, self.window,
            self.rope_theta, self.dtype, self.param_dtype,
            use_pallas=self.use_pallas, decode=self.decode,
            kv_page_size=self.kv_page_size,
            kv_pool_pages=self.kv_pool_pages, name="attn")(
                h, positions, cache_index, block_table, flash_prefill,
                window_pages)
        h2 = rms_norm(x, g2, self.rms_eps).reshape(b * s, d)
        y, sizes = routed_experts(h2.astype(self.dtype), idx, weights,
                                  w_gate_up.astype(self.dtype),
                                  w_down.astype(self.dtype),
                                  use_pallas=self.use_pallas)
        return x + y.reshape(b, s, d), sizes


class RoutedDecoderLM(nn.Module):
    """``__call__(tokens [B, S] int32) -> logits [B, S, vocab]`` f32; in
    decode mode with ``cache_index`` [B], ``block_table`` [B, M] and the
    two statics of ``TransformerLM`` (``flash_prefill``,
    ``window_pages``), which ``serve.decode.Decoder`` drives alike."""

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
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    use_pallas: Any = None
    decode: bool = False
    kv_page_size: Optional[int] = None
    kv_pool_pages: Optional[int] = None
    # serve.decode.make_decode_model names it; this family has no
    # tensor-parallel layout yet
    model_axis: Optional[str] = None
    stats_names = STATS         # no field: what "stats"/"counts" holds

    def layer_kinds(self):
        """[(window or None, rope_theta or None)] a layer."""
        lw, lr = tuple(self.layer_window), tuple(self.layer_rope)
        return [(int(self.window) if lw[i % len(lw)] else None,
                 float(self.rope_theta) if lr[i % len(lr)] else None)
                for i in range(self.num_layers)]

    @nn.compact
    def __call__(self, tokens, train: bool = False, cache_index=None,
                 block_table=None, flash_prefill: bool = False,
                 window_pages: Optional[int] = None):
        del train
        if self.model_axis is not None:
            raise ValueError("the routed decoder has no tensor-parallel "
                             "layout (serve it on one device)")
        if self.num_heads % self.num_kv_heads:
            raise ValueError(f"num_heads {self.num_heads} is no multiple of "
                             f"num_kv_heads {self.num_kv_heads}")
        b, s = tokens.shape
        pdt = jnp.dtype(self.param_dtype)
        embed = self.param("embed", _normal(0.02),
                           (self.vocab_size, self.d_model), pdt)
        x = embed[tokens].astype(jnp.float32)      # the stream is f32
        offset = jnp.arange(s, dtype=jnp.int32)[None, :]
        if self.decode:
            if cache_index is None:
                raise ValueError("decode mode needs cache_index [B] int32")
            positions = cache_index[:, None] + offset
        else:
            positions = jnp.broadcast_to(offset, (b, s))
        kinds = self.layer_kinds()
        touched = load_max = jnp.zeros((), jnp.int32)
        for i, (window, theta) in enumerate(kinds):
            x, sizes = RoutedBlock(
                self.num_heads, self.num_kv_heads, self.head_dim,
                self.num_experts, self.experts_per_token, self.expert_width,
                window, theta, self.rms_eps, self.dtype, pdt,
                use_pallas=self.use_pallas, decode=self.decode,
                kv_page_size=self.kv_page_size,
                kv_pool_pages=self.kv_pool_pages, name=f"layer{i}")(
                    x, positions, cache_index, block_table, flash_prefill,
                    window_pages)
            touched += jnp.sum(sizes > 0, dtype=jnp.int32)
            load_max += jnp.max(sizes)
        # what the attention of this call has to read of K (and of V): a
        # row's whole history in a full layer, the window's reach in a
        # window layer
        live = positions[:, -1] + 1
        n_window = sum(w is not None for w, _ in kinds)
        counts = jnp.stack([
            jnp.asarray(b * s * self.experts_per_token * len(kinds),
                        jnp.int32),
            touched, load_max,
            (len(kinds) - n_window) * jnp.sum(live),
            n_window * jnp.sum(jnp.minimum(live, self.window + s - 1))])
        self.sow("stats", "counts", counts,
                 reduce_fn=lambda _, new: new,
                 init_fn=lambda: jnp.zeros((len(STATS),), jnp.int32))
        x = rms_norm(x, self.param("norm_f", nn.initializers.ones,
                                   (self.d_model,), pdt), self.rms_eps)
        head = self.param("lm_head", _normal(0.02),
                          (self.d_model, self.vocab_size), pdt)
        return jnp.einsum("bsd,dv->bsv", x.astype(self.dtype),
                          head.astype(self.dtype),
                          preferred_element_type=jnp.float32)
