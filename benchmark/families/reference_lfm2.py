"""The plain reference of the family ``lfm2``: a decoder of pre-norm RMSNorm
blocks whose mixer is, a layer, either a double-gated SHORT CONVOLUTION or
grouped-query attention with per-head norms of q and k; two leading dense
gated-SiLU layers, then layers of top-k-of-E gated-SiLU experts chosen by
sigmoid scores plus a bias; a tied head.  float32, highest matmul
precision, no kernel, no cache, NO STATE: the convolution runs over the
whole sequence from its definition, K and V of every token are kept, every
expert is applied densely to every token and masked by the routing
weights, RoPE and the causal mask are written out.  It reads the program's
parameter tree (bf16 values, cast to float32 a layer — and a block of
experts — at a time: one expert layer in float32 is 1.41e9 bytes) and
nothing else of the program; in particular never the program's routing.

For layer ``l`` on ``x [S, d]`` (RMSNorm eps from the configuration, a
learned scale, no bias anywhere):

  1. ``h = RMSNorm(x; norm1)``
  2. a convolution layer (``conv`` in the tree): ``[B | C | z] = h in_proj``
     (three blocks of d); ``u = B * z``; ``c_t = sum_j w_j * u_{t-(L-1)+j}``
     with ``w`` [d, L] one filter a channel, ``u`` zero before the sequence;
     ``x += (C * c) out_proj``.  No activation.
     An attention layer (``attn``): ``q, k, v = h qkv`` split into ``H``
     query and ``Hkv`` key and value heads of ``Dh``; ``q = RMSNorm(q;
     q_norm)`` and ``k = RMSNorm(k; k_norm)`` over the ``Dh`` values of each
     head; rotate-half RoPE over the whole head (theta from the
     configuration) on q and k; query head ``i`` reads KV head ``i // (H /
     Hkv)``; scores / sqrt(Dh), causal over all ``j <= i``, softmax;
     ``x += concat(o) out``
  3. ``h2 = RMSNorm(x; norm2)``.  A dense layer (``dense_gate_up`` in the
     tree): ``x += W_d(silu(W_g h2) * W_u h2)``.  A routed layer: ``s =
     sigmoid(h2 router)``; the choice is the k largest of ``s +
     router_bias``; the weights are the chosen experts' ``s`` WITHOUT the
     bias, over (their sum + 1e-6), times ``routed_scale``; ``x +=
     sum_{e chosen} w_e E_e(h2)``, ``E(u) = W_d(silu(W_g u) * W_u u)``
  4. after the last layer ``RMSNorm(x; norm_f)``, then ``x embed^T``: the
     head is the embedding matrix.

The sizes the parameter tree does not show (heads, k, theta, the scale)
come from the configuration file beside the benchmark, or, for a tree of
the toy's width, from the family's ``TOY``.

``lib/agreement.tokens_agree`` materialises ``forward``'s [B, S, vocab]
logits; at the published widths and the cell's sample (two prompts padded
to 6,208 positions x 65,536) that is 3.3e9 bytes beside 14e9 of weights
and pool.  ``served_tokens_agree`` here gathers the hidden rows that chose
the served tokens BEFORE the head, a prompt at a time, blocks the queries
of attention and the rows of every MLP, and returns the same dictionary.
"""

from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np

# what is no family's own: the cast (through a control's rounding), the
# norm, the rotation, grouped-query attention in blocks of queries, the
# comparison of gathered rows (``lib/agreement.tokens_agree``'s numbers) and
# the 8-bit grid; gated SiLU and the masked experts from the other
# sigmoid-routed family
from benchmark.families.reference_joyai import (  # noqa: F401
    _experts, _gated)
from benchmark.families.reference_smallthinker import (  # noqa: F401
    _attention, _f32, _rms_norm, _rope, compare, rounded_to)

_HERE = os.path.dirname(os.path.abspath(__file__))
CONFIG_FILE = os.path.join(_HERE, "..", "configs", "lfm2-8b-a1b.json")
SUM_EPS = 1e-6          # the published code adds it to the chosen scores' sum
HEAD_BLOCKS = 4         # the tied head, cast a block of vocabulary rows a time
ROW_BLOCK = 1024        # rows the MLP of a layer takes at a time


def arch_of_config(cfg: dict) -> dict:
    return {"d_model": cfg["hidden_size"],
            "heads": cfg["num_attention_heads"],
            "kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg["hidden_size"] // cfg["num_attention_heads"],
            "top_k": cfg["num_experts_per_tok"],
            "routed_scale": float(cfg["routed_scaling_factor"]),
            "theta": float(cfg["rope_theta"]), "eps": cfg["norm_eps"]}


def arch_of_model_kwargs(kw: dict) -> dict:
    return {"d_model": kw["d_model"], "heads": kw["num_heads"],
            "kv_heads": kw["num_kv_heads"], "head_dim": kw["head_dim"],
            "top_k": kw["experts_per_token"],
            "routed_scale": float(kw["routed_scale"]),
            "theta": float(kw["rope_theta"]), "eps": kw["rms_eps"]}


def arch_of(params) -> dict:
    """The sizes that go with this parameter tree: the configuration's,
    or the toy's, by the tree's hidden size."""
    from benchmark.families import lfm2
    with open(CONFIG_FILE) as f:
        cfg = json.load(f)
    known = [arch_of_config(cfg), arch_of_model_kwargs(
        dict(cfg["build_model"]["kwargs"],
             **lfm2.TOY["serve"]["model_kwargs"]))]
    d = params["embed"].shape[1]
    for arch in known:
        if arch["d_model"] == d:
            return arch
    raise ValueError(f"no sizes known for a tree of hidden size {d} (known: "
                     f"{[a['d_model'] for a in known]})")


def short_conv(h, p, weights=None, state=None):
    """h [B, S, d] -> [B, S, d]: both gates, every tap, from the
    definition.  ``state``: a function ``u`` goes through (a control
    rounds it to bfloat16, as the program's state holds it)."""
    d = h.shape[-1]
    bcz = h @ _f32(p["in_proj"], weights)
    u = bcz[..., :d] * bcz[..., 2 * d:]
    if state is not None:
        u = state(u)
    w = _f32(p["taps"], weights)                        # [d, L]
    taps = w.shape[1]
    s = h.shape[1]
    c = 0.0
    for j in range(taps):
        back = taps - 1 - j                             # u_{t - back}
        c = c + w[:, j] * jnp.pad(u, ((0, 0), (back, 0), (0, 0)))[:, :s]
    return (bcz[..., d:2 * d] * c) @ _f32(p["out_proj"], weights)


def routing_weights(scores, bias, top_k, routed_scale):
    """[T, E] float32: 0 where an expert is not chosen, else its weight.
    The choice reads ``scores + bias``; the weight reads ``scores``, over
    the chosen scores' sum plus ``SUM_EPS``."""
    _, idx = jax.lax.top_k(scores + bias, top_k)
    chosen = jnp.take_along_axis(scores, idx, -1)
    chosen = (chosen / (jnp.sum(chosen, -1, keepdims=True) + SUM_EPS)
              * routed_scale)
    t, e = scores.shape
    return jnp.zeros((t, e), jnp.float32).at[
        jnp.arange(t)[:, None], idx].add(chosen)


def _by_rows(f, *xs):
    """``f`` over equal blocks of at most ``ROW_BLOCK`` rows of ``xs`` (each
    [T, .]), one block after another.  An MLP treats every row alone, so
    the blocks change nothing but what is alive at once: compiled for the
    chip, the 8,257 rows of the sample's longest prompt taken whole need
    3.42e9 bytes of temporaries beside 14e9 of weights and pool, in blocks
    2.44e9 (6,208 rows: 2.64e9 and 1.88e9; PR 34).  The zero rows that fill
    the last block are dropped."""
    t = xs[0].shape[0]
    if t <= ROW_BLOCK:
        return f(*xs)
    blocks = -(-t // ROW_BLOCK)
    rows = -(-t // blocks)
    xs = tuple(jnp.pad(x, ((0, blocks * rows - t), (0, 0))
                       ).reshape(blocks, rows, x.shape[1]) for x in xs)
    y = jax.lax.map(lambda block: f(*block), xs)
    return y.reshape(blocks * rows, y.shape[-1])[:t]


def hidden(params, tokens, arch=None, weights=None, router_input=None,
           state=None, zero_bias=False):
    """tokens [B, S] -> the final normed hidden rows [B, S, d] float32.
    ``weights``: a function every weight matrix goes through as it is
    cast (the controls round them to fewer bits); None = as they are.
    ``router_input``: a function the router's input goes through (a
    control rounds it to bfloat16 and nothing else: what it reads is the
    share of ``logit_rms`` that top-k choices flipping make).  ``state``:
    :func:`short_conv`'s.  ``zero_bias``: the control without
    the router's bias."""
    arch = arch or arch_of(params)
    with jax.default_matmul_precision("highest"):
        b, s = tokens.shape
        if weights is None:
            x = jnp.asarray(params["embed"][tokens], jnp.float32)
        else:           # the controls round every matrix, as control.py
            x = _f32(params["embed"], weights)[tokens]
        n_layers = sum(1 for k in params if k.startswith("layer"))
        hq, hkv, dh = arch["heads"], arch["kv_heads"], arch["head_dim"]
        for l in range(n_layers):
            p = params[f"layer{l}"]
            h = _rms_norm(x, _f32(p["norm1"]), arch["eps"])
            if "conv" in p:
                x = x + short_conv(h, p["conv"], weights, state)
            else:
                a = p["attn"]
                qkv = h @ _f32(a["qkv"], weights)
                q = qkv[..., :hq * dh].reshape(b, s, hq, dh)
                k = qkv[..., hq * dh:(hq + hkv) * dh].reshape(b, s, hkv, dh)
                v = qkv[..., (hq + hkv) * dh:].reshape(b, s, hkv, dh)
                q = _rms_norm(q, _f32(a["q_norm"]), arch["eps"])
                k = _rms_norm(k, _f32(a["k_norm"]), arch["eps"])
                q, k = _rope(q, arch["theta"]), _rope(k, arch["theta"])
                x = x + _attention(q, k, v, None) @ _f32(a["out"], weights)
            h2 = _rms_norm(x, _f32(p["norm2"]), arch["eps"]).reshape(
                b * s, -1)
            if "dense_gate_up" in p:
                wgu, wd = (_f32(p["dense_gate_up"], weights),
                           _f32(p["dense_down"], weights))
                y = _by_rows(lambda rows: _gated(rows, wgu, wd), h2)
            else:
                routed = h2 if router_input is None else router_input(h2)
                scores = jax.nn.sigmoid(routed @ _f32(p["router"], weights))
                bias = _f32(p["router_bias"])
                full = routing_weights(
                    scores, jnp.zeros_like(bias) if zero_bias else bias,
                    arch["top_k"], arch["routed_scale"])
                y = _by_rows(
                    lambda rows, w: _experts(rows, w, p["gate_up"],
                                             p["down"], weights), h2, full)
            x = x + y.reshape(b, s, -1)
        return _rms_norm(x, _f32(params["norm_f"]), arch["eps"])


def _head(rows, embed, weights=None):
    """rows [..., d] float32 -> logits [..., vocab] through the embedding
    matrix itself, cast a block of vocabulary rows at a time."""
    with jax.default_matmul_precision("highest"):
        v = embed.shape[0]
        if weights is not None or v % HEAD_BLOCKS:
            return rows @ _f32(embed, weights).T
        step = v // HEAD_BLOCKS
        return jnp.concatenate(
            [rows @ _f32(embed[r:r + step]).T for r in range(0, v, step)],
            -1)


def forward(params, tokens):
    """tokens [B, S] int32 -> logits [B, S, vocab] float32 (the toy and
    the tests; at the published widths see the module's docstring)."""
    return _head(hidden(params, tokens), params["embed"])


def rows_that_chose(params, prompts, served, weights=None,
                    **controls) -> list:
    """Teacher-forced, as ``lib/agreement.rows_that_chose``: for each
    (prompt, served tokens) pair the logits at the positions that chose
    each served token, a [tokens, vocab] array a pair — the hidden rows
    gathered before the head.  A pair at a time, at its own length.
    ``weights`` and ``controls``: ``hidden``'s."""
    arch = arch_of(params)

    def rows(params, tokens, at):
        x = hidden(params, tokens, arch, weights, **controls)
        return _head(x[0, at], params["embed"], weights)
    rows = jax.jit(rows)
    out = []
    for p, t in zip(prompts, served):
        tokens = np.concatenate([np.asarray(p, np.int32),
                                 np.asarray(t, np.int32)])[None]
        at = len(p) - 1 + np.arange(len(t))     # position that chose t[j]
        out.append(np.asarray(rows(params, jnp.asarray(tokens),
                                   jnp.asarray(at))))
    return out


def served_tokens_agree(params, prompts, served, rtol: float,
                        program_logits=None, logit_rms_limit=None) -> dict:
    return compare(rows_that_chose(params, prompts, served), served, rtol,
                   program_logits, logit_rms_limit)


def greedy_tokens(params, prompts, new_tokens: int, weights=None) -> list:
    """What a system that computed this reference (its weight matrices
    through ``weights``) would serve: each prompt's next tokens by greedy
    choice, no cache, the padded batch again for every token."""
    total = max(len(p) for p in prompts) + new_tokens
    batch = np.zeros((len(prompts), total), np.int32)
    for r, p in enumerate(prompts):
        batch[r, :len(p)] = p
    arch = arch_of(params)

    def step(params, tokens, at):
        x = hidden(params, tokens, arch, weights)
        x = jnp.take_along_axis(x, at[:, None, None], axis=1)[:, 0]
        return jnp.argmax(_head(x, params["embed"], weights), -1)
    step = jax.jit(step)
    for j in range(new_tokens):
        at = np.asarray([len(p) - 1 + j for p in prompts], np.int32)
        nxt = np.asarray(step(params, jnp.asarray(batch), jnp.asarray(at)))
        for r, p in enumerate(prompts):
            batch[r, len(p) + j] = int(nxt[r])
    return [batch[r, len(p):len(p) + new_tokens].tolist()
            for r, p in enumerate(prompts)]
