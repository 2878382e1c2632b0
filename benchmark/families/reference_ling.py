"""The plain reference of the family ``ling``: a decoder of pre-norm RMSNorm
blocks whose mixer is, a layer, either DELTA-RULE LINEAR ATTENTION with a
decay a channel (a matrix of state a head) or multi-head LATENT attention
with a direct query projection, a norm a query head and a gate a head; two
leading dense gated-SiLU layers, then layers of a shared expert beside
top-k-of-E gated-SiLU experts chosen by sigmoid scores plus a bias UNDER A
GROUP LIMIT, of which this device HOLDS A BLOCK; an untied head.  float32,
highest matmul precision, no kernel, no cache, no pages, no chunking: the
linear layer is the literal recurrence, a ``lax.scan`` over tokens carrying
``S`` in float32; latent attention is expanded from the definition and
never absorbed; every held expert is applied densely to every token and
masked by the routing weights; RoPE and the causal mask are written out.
It reads the program's parameter tree (bf16 values, cast to float32 a
layer — and a block of experts — at a time) and nothing else of the
program; in particular never the program's routing.

For layer ``l`` on ``x [S, d]`` (RMSNorm eps from the configuration, a
learned scale, no bias anywhere), ``h = RMSNorm(x; norm1)``:

  1. a linear layer (``linear`` in the tree; H heads of D): ``[q | k | v] =
     silu(conv(h qkv))``, ``conv`` one causal filter of ``taps`` taps a
     channel, zeros before the sequence; a head's ``q <- q / |q| *
     D**-0.5``, ``k <- k / |k|``; ``a_t = floor * sigmoid(exp(a_log) * (h
     decay + dt_bias))`` a channel, ``beta_t = sigmoid(h beta)`` a head;
     ``S_t = (I - beta_t k_t k_t^T) Diag(exp(a_t)) S_{t-1} + beta_t k_t
     v_t^T``, ``S_0 = 0``; ``o_t = S_t^T q_t``; ``x += (RMSNorm_head(o;
     out_norm) * sigmoid(h gate)) out``.  No positions.
  2. a latent layer (``attn``): ``q = h q`` [H, nope + rope]; ``q =
     RMSNorm(q; q_head_norm)`` over a head's values; ``[c_kv | k_r] = h
     kv_a``; ``c_kv = RMSNorm(c_kv; kv_norm)``; rotate-half RoPE over
     ``rope`` dimensions on ``q_rope`` a head and on ``k_r``, one a token
     for all heads; ``[k_nope_h | v_h] = c_kv kv_b``; scores over ``nope +
     rope`` / sqrt(nope + rope), causal, softmax; ``o_h <- o_h *
     sigmoid((h gate)_h)``; ``x += concat(o) out``.
  3. ``h2 = RMSNorm(x; norm2)``.  A dense layer: ``x += W_d(silu(W_g h2) *
     W_u h2)``.  A routed layer: ``s = sigmoid(h2 router)`` over ALL the
     experts; ``s' = s + router_bias``; the experts are ``groups`` runs of
     consecutive ids, a group's score the sum of its 2 largest ``s'``, the
     ``groups_kept`` best groups stay; the choice is the k largest ``s'``
     among them; the weights are the chosen ``s`` WITHOUT the bias over
     their sum, times ``routed_scale``; ``x += E_shared(h2) + sum_{e
     chosen AND held} w_e E_e(h2)`` — what the experts held elsewhere
     would have added is left out.
  4. after the last layer ``RMSNorm(x; norm_f)`` and an untied head onto
     the vocabulary rows held.

The sizes the tree does not show come from the configuration file beside
the benchmark, or, for a tree of the toy's width, from the family's
``TOY``.  ``served_tokens_agree`` gathers the hidden rows that chose the
served tokens BEFORE the head, a prompt at a time, blocks the queries of
attention and the rows of every MLP, and returns
``lib/agreement.tokens_agree``'s dictionary.
"""

from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np

# what is no family's own: the cast (through a control's rounding), the
# norm, the head in blocks of columns, the comparison of gathered rows
# (``lib/agreement.tokens_agree``'s numbers), the 8-bit grid; rotate-half
# RoPE, attention in blocks of queries, gated SiLU and the masked experts
# from the other latent family; the rows of an MLP in blocks
from benchmark.families.reference_joyai import (  # noqa: F401
    _experts, _gated, _rope)
from benchmark.families.reference_lfm2 import _by_rows
from benchmark.families.reference_smallthinker import (  # noqa: F401
    _f32, _head, _rms_norm, compare, rounded_to)

_HERE = os.path.dirname(os.path.abspath(__file__))
CONFIG_FILE = os.path.join(_HERE, "..", "configs", "ling-3.0-flash-vl.json")
Q_BLOCK = 256           # queries of attention a block


def _attention(q, k, v):
    """q, k [B, S, H, Dk], v [B, S, H, Dv]; causal.  The mask is written
    out; the queries go a block at a time, each against the keys it can
    see (one ``lax.map`` over equal blocks, so one block's scores are alive
    at a time: 0.27e9 bytes at 8,257 keys)."""
    b, s, h, d = q.shape
    blocks = -(-s // Q_BLOCK)
    qb = jnp.pad(q, ((0, 0), (0, blocks * Q_BLOCK - s), (0, 0), (0, 0))
                 ).reshape(b, blocks, Q_BLOCK, h, d)
    j = jnp.arange(s)[None, :]

    def one(xs):
        q_, first = xs
        i = first + jnp.arange(Q_BLOCK)[:, None]
        scores = jnp.einsum("bqhd,bkhd->bhqk", q_, k) / np.sqrt(d)
        scores = jnp.where((j <= i)[None, None], scores, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
    o = jax.lax.map(one, (jnp.moveaxis(qb, 1, 0),
                          jnp.arange(blocks) * Q_BLOCK))
    return jnp.moveaxis(o, 0, 1).reshape(b, blocks * Q_BLOCK, -1)[:, :s]


def arch_of_model_kwargs(kw: dict) -> dict:
    return {"d_model": kw["d_model"], "heads": kw["num_heads"],
            "nope": kw["qk_nope_head_dim"], "rope": kw["qk_rope_head_dim"],
            "v": kw["v_head_dim"], "kv_rank": kw["kv_lora_rank"],
            "top_k": kw["experts_per_token"],
            "routed_scale": float(kw["routed_scale"]),
            "theta": float(kw["rope_theta"]), "eps": kw["rms_eps"],
            "linear_heads": kw["linear_heads"],
            "linear_head_dim": kw["linear_head_dim"],
            "decay_floor": float(kw["linear_decay_floor"]),
            "groups": kw["route_groups"],
            "groups_kept": kw["route_groups_kept"],
            "held": tuple(kw["experts_held"] or (0, kw["num_experts"]))}


def arch_of(params) -> dict:
    """The sizes that go with this parameter tree: the configuration's
    build call, or the toy's, by the tree's hidden size."""
    from benchmark.families import ling
    with open(CONFIG_FILE) as f:
        kw = json.load(f)["build_model"]["kwargs"]
    known = [arch_of_model_kwargs(kw), arch_of_model_kwargs(
        dict(kw, **ling.TOY["serve"]["model_kwargs"]))]
    d = params["embed"].shape[1]
    for arch in known:
        if arch["d_model"] == d:
            return arch
    raise ValueError(f"no sizes known for a tree of hidden size {d} (known: "
                     f"{[a['d_model'] for a in known]})")


def linear_attention(h, p, arch, weights=None, state=None):
    """h [B, S, d] -> [B, S, d]: the three filters and their SiLU, both L2
    norms, the decay a channel, beta, the literal recurrence, the output
    norm and gate.  ``state``: a function the matrices go through after
    every token (a control rounds them to bfloat16, as the program's pool
    holds them between steps)."""
    b, s, _ = h.shape
    hn, dh = arch["linear_heads"], arch["linear_head_dim"]
    n = hn * dh
    pre = h @ _f32(p["qkv"], weights)
    w = _f32(p["taps"], weights)                        # [3n, taps]
    taps = w.shape[1]
    mixed = 0.0
    for j in range(taps):
        back = taps - 1 - j                             # pre_{t - back}
        mixed = mixed + w[:, j] * jnp.pad(
            pre, ((0, 0), (back, 0), (0, 0)))[:, :s]
    rate = jnp.repeat(jnp.exp(_f32(p["a_log"])), dh)
    gate_in = rate * (h @ _f32(p["decay"], weights) + _f32(p["dt_bias"]))
    write_in = h @ _f32(p["beta"], weights)                 # [B, S, H]

    def token(big_s, xs):
        # a token's own numbers are made here, from its row of the three
        # projections: made for the whole sequence beforehand they are
        # 1.1e9 bytes more at the sample's longest prompt
        mixed_t, gate_t, write_t = xs                   # [B, .]
        q_t, k_t, v_t = (jax.nn.silu(mixed_t[:, i * n:(i + 1) * n]
                                     ).reshape(b, hn, dh) for i in range(3))
        q_t = q_t / jnp.sqrt(jnp.sum(q_t * q_t, -1, keepdims=True)
                             + 1e-6) * dh ** -0.5
        k_t = k_t / jnp.sqrt(jnp.sum(k_t * k_t, -1, keepdims=True) + 1e-6)
        alpha_t = jnp.exp(arch["decay_floor"] * jax.nn.sigmoid(gate_t)
                          ).reshape(b, hn, dh)
        beta_t = jax.nn.sigmoid(write_t)
        big_s = alpha_t[..., :, None] * big_s           # Diag(alpha) S
        seen = jnp.einsum("bhk,bhkv->bhv", k_t, big_s)
        big_s = big_s + beta_t[..., None, None] * (
            k_t[..., :, None] * (v_t - seen)[..., None, :])
        if state is not None:
            big_s = state(big_s)
        return big_s, jnp.einsum("bhk,bhkv->bhv", q_t, big_s)
    _, o = jax.lax.scan(
        token, jnp.zeros((b, hn, dh, dh), jnp.float32),
        tuple(jnp.moveaxis(x, 1, 0) for x in (mixed, gate_in, write_in)))
    o = _rms_norm(jnp.moveaxis(o, 0, 1), _f32(p["out_norm"]), arch["eps"])
    gate = jax.nn.sigmoid(h @ _f32(p["gate"], weights))
    return (o.reshape(b, s, n) * gate) @ _f32(p["out"], weights)


def latent_attention(h, a, arch, weights=None, latent=None):
    """h [B, S, d] -> [B, S, d], expanded from the definition."""
    b, s, _ = h.shape
    hq, dn, dr, dv = arch["heads"], arch["nope"], arch["rope"], arch["v"]
    r = arch["kv_rank"]
    q = (h @ _f32(a["q"], weights)).reshape(b, s, hq, dn + dr)
    q = _rms_norm(q, _f32(a["q_head_norm"]), arch["eps"])
    kv = h @ _f32(a["kv_a"], weights)
    c_kv = _rms_norm(kv[..., :r], _f32(a["kv_norm"]), arch["eps"])
    k_rope = _rope(kv[..., None, r:], arch["theta"], False)
    if latent is not None:
        c_kv, k_rope = latent(c_kv), latent(k_rope)
    q_rope = _rope(q[..., dn:], arch["theta"], False)
    kv_all = (c_kv @ _f32(a["kv_b"], weights)).reshape(b, s, hq, dn + dv)
    q = jnp.concatenate([q[..., :dn], q_rope], -1)
    k = jnp.concatenate(
        [kv_all[..., :dn], jnp.broadcast_to(k_rope, (b, s, hq, dr))], -1)
    o = _attention(q, k, kv_all[..., dn:]).reshape(b, s, hq, dv)
    gate = jax.nn.sigmoid(h @ _f32(a["gate"], weights))     # [B, S, H]
    return (o * gate[..., None]).reshape(b, s, hq * dv) @ _f32(
        a["out"], weights)


def routing_weights(scores, bias, top_k, routed_scale, groups, groups_kept):
    """[T, E] float32: 0 where an expert is not chosen, else its weight.
    The group limit and the choice read ``scores + bias``; the weight
    reads ``scores``."""
    t, e = scores.shape
    biased = scores + bias
    if groups > 1:
        per_group = jnp.sort(biased.reshape(t, groups, e // groups), -1)
        group_score = per_group[..., -1] + per_group[..., -2]
        _, kept = jax.lax.top_k(group_score, groups_kept)
        is_open = jnp.zeros((t, groups), bool).at[
            jnp.arange(t)[:, None], kept].set(True)
        biased = jnp.where(jnp.repeat(is_open, e // groups, axis=1), biased,
                           -jnp.inf)
    _, idx = jax.lax.top_k(biased, top_k)
    chosen = jnp.take_along_axis(scores, idx, -1)
    chosen = chosen / jnp.sum(chosen, -1, keepdims=True) * routed_scale
    return jnp.zeros((t, e), jnp.float32).at[
        jnp.arange(t)[:, None], idx].add(chosen)


def hidden(params, tokens, arch=None, weights=None, router_input=None,
           state=None, latent=None, zero_bias=False, held=None):
    """tokens [B, S] -> the final normed hidden rows [B, S, d] float32.
    ``weights``: a function every weight matrix goes through as it is
    cast (the controls round them to fewer bits); None = as they are.
    ``router_input``: a function the router's input goes through (a
    control rounds it to bfloat16 and nothing else: what it reads is the
    share of ``logit_rms`` that top-k choices flipping make).  ``state``:
    :func:`linear_attention`'s.  ``latent``: a function ``c_kv`` and
    ``k_rope`` go through.  ``zero_bias``: the control without the router's
    bias.  ``held``: (first id, count) in place of the configuration's —
    the tree's expert weights are then those experts'."""
    arch = arch or arch_of(params)
    first, count = held or arch["held"]
    with jax.default_matmul_precision("highest"):
        b, s = tokens.shape
        if weights is None:
            x = jnp.asarray(params["embed"][tokens], jnp.float32)
        else:           # the controls round every matrix, as control.py
            x = _f32(params["embed"], weights)[tokens]
        n_layers = sum(1 for k in params if k.startswith("layer"))
        for l in range(n_layers):
            p = params[f"layer{l}"]
            h = _rms_norm(x, _f32(p["norm1"]), arch["eps"])
            if "linear" in p:
                x = x + linear_attention(h, p["linear"], arch, weights,
                                         state)
            else:
                x = x + latent_attention(h, p["attn"], arch, weights, latent)
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
                    arch["top_k"], arch["routed_scale"], arch["groups"],
                    arch["groups_kept"])[:, first:first + count]
                wsgu, wsd = (_f32(p["shared_gate_up"], weights),
                             _f32(p["shared_down"], weights))
                y = _by_rows(
                    lambda rows, w: _experts(rows, w, p["gate_up"],
                                             p["down"], weights)
                    + _gated(rows, wsgu, wsd), h2, full)
            x = x + y.reshape(b, s, -1)
        return _rms_norm(x, _f32(params["norm_f"]), arch["eps"])


def forward(params, tokens):
    """tokens [B, S] int32 -> logits [B, S, vocab] float32 (the toy and
    the tests; at the published widths see the module's docstring)."""
    return _head(hidden(params, tokens), params["lm_head"])


def rows_that_chose(params, prompts, served, weights=None,
                    **controls) -> list:
    """Teacher-forced, as ``lib/agreement.rows_that_chose``: for each
    (prompt, served tokens) pair the logits at the positions that chose
    each served token, a [tokens, vocab] array a pair — the hidden rows
    gathered before the head.  A pair at a time, at its own length.
    ``weights`` and ``controls``: ``hidden``'s."""
    arch = arch_of(params)
    # two programs, the hidden rows between them: compiled as one for the
    # chip, the 8,257-position sample's gathered rows came out NaN from
    # layers that were finite (my chip runs, PR 39; 1,088 and 4,160 did not)
    rows = jax.jit(lambda params, tokens: hidden(params, tokens, arch,
                                                 weights, **controls)[0])
    head = jax.jit(lambda params, x: _head(x, params["lm_head"], weights))
    out = []
    for p, t in zip(prompts, served):
        tokens = np.concatenate([np.asarray(p, np.int32),
                                 np.asarray(t, np.int32)])[None]
        at = len(p) - 1 + np.arange(len(t))     # position that chose t[j]
        out.append(np.asarray(head(params, rows(params, jnp.asarray(tokens)
                                                )[at])))
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
        return jnp.argmax(_head(x, params["lm_head"], weights), -1)
    step = jax.jit(step)
    for j in range(new_tokens):
        at = np.asarray([len(p) - 1 + j for p in prompts], np.int32)
        nxt = np.asarray(step(params, jnp.asarray(batch), jnp.asarray(at)))
        for r, p in enumerate(prompts):
            batch[r, len(p) + j] = int(nxt[r])
    return [batch[r, len(p):len(p) + new_tokens].tolist()
            for r, p in enumerate(prompts)]
