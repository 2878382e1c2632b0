"""The plain reference of the family ``qwen3_next``: a decoder of pre-norm
blocks under ZERO-CENTRED RMSNorms (``x * rsqrt(mean x^2 + eps) * (1 +
w)``) whose mixer is, a layer, either the GATED DELTA RULE (one scalar of
decay a value head, fewer key heads than value heads, a matrix of state a
value head) or GATED grouped-query attention (a norm a head of q and of k,
rotary positions over the first lanes of a head only, the heads' outputs
times the sigmoid of a gate); every layer's MLP a shared expert under a
scalar gate beside top-k-of-E gated-SiLU experts chosen by a softmax, of
which this device HOLDS A BLOCK; an untied head.  float32, highest matmul
precision, no kernel, no cache, no pages, no chunking: the linear layer is
the literal recurrence, a ``lax.scan`` over tokens carrying ``S`` in
float32, its filter a padded causal convolution; attention is softmax over
the whole prefix, the mask written out, the queries in blocks; every held
expert is applied densely to every token and masked by the routing
weights.  It reads the program's parameter tree (bf16 values, cast to
float32 a layer — and a block of experts — at a time) and nothing else of
the program; in particular never the program's routing, and it imports
nothing from ``dtf_tpu``.

For layer ``l`` on ``x [S, d]``, ``h = norm(x; norm1)``:

  1. a linear layer (``linear`` in the tree; Hk key heads, Hv value heads
     of D): ``[q | k | v | z] = h qkvz``; ``[q | k | v] <- silu(conv([q | k
     | v]))``, ``conv`` one causal filter of ``taps`` taps a channel, zeros
     before the sequence; a key head's ``q <- q / |q| * D**-0.5``, ``k <- k
     / |k|``; value head ``i`` reads key head ``i // (Hv // Hk)``; ``[b |
     a] = h ba``; ``beta_t = sigmoid(b)``, ``g_t = -exp(a_log) *
     softplus(a + dt_bias)``, one scalar a value head; ``S <- exp(g_t) S``;
     ``u = (v_t - S^T k_t) beta_t``; ``S <- S + k_t u^T``; ``o_t = S^T
     q_t``; ``x += ((out_norm * o / rms(o)) * silu(z)) out`` — this one
     norm's weight is PLAIN, not ``1 + w``.  No positions.
  2. an attention layer (``attn``): ``[q | k | v | gate] = h qkv`` (Hq, Hkv,
     Hkv, Hq heads of D); ``q = norm(q; q_norm)``, ``k = norm(k; k_norm)``
     over a head's values; rotate-half RoPE over the FIRST ``rotary``
     lanes of a head of q and k, the others carry no position; query head
     ``i`` reads KV head ``i // (Hq // Hkv)``; scores / sqrt(D), causal,
     softmax; ``x += (o * sigmoid(gate)) out``.
  3. ``h2 = norm(x; norm2)``; ``p = softmax(h2 router)`` over ALL the
     experts; the k largest; their weights renormalised over the k; ``x +=
     sigmoid(h2 shared_gate) E_shared(h2) + sum_{e chosen AND held} w_e
     E_e(h2)`` — what the experts held elsewhere would have added is left
     out.
  4. after the last layer ``norm(x; norm_f)`` and an untied head onto the
     vocabulary rows held.

Where this departs from the published ``qwen3_next`` code: the columns of
``qkvz``, ``ba`` and ``qkv`` lie flat (the published projections interleave
them by key head, and q_proj interleaves (query, gate) a head: a
permutation of columns); the delta rule is written token by token where the
published code takes blocks of 64 (the same recurrence); ``repeat_kv`` is a
reshape of the queries; the experts are dense and masked where the
published code loops over the experts hit (the same sum); this device's
share of the experts and of the vocabulary.

The sizes the tree does not show come from the configuration file beside
the benchmark, or, for a tree of the toy's width, from the family's
``TOY``.  ``served_tokens_agree`` gathers the hidden rows that chose the
served tokens BEFORE the head, a prompt at a time, and returns
``lib/agreement.tokens_agree``'s dictionary.  ``fault`` (``hidden``) runs
the reference with ONE injected fault, for the readings a limit is set
from: ``zero_state_carry`` / ``zero_filter_carry`` (a linear layer's state
/ its filter's inputs start from zeros at every multiple of
``FAULT_CHUNK``, as a chunked prefill that dropped the carry), ``ungated``
(the attention output without its gate), ``rope_all`` (rotary positions
over the whole head).
"""

from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np

# what is no family's own: the cast (through a control's rounding), the
# head in blocks of columns, the comparison of gathered rows
# (``lib/agreement.tokens_agree``'s numbers), the 8-bit grid, rotate-half
# RoPE; gated SiLU and the masked experts; the rows of an MLP in blocks
from benchmark.families.reference_joyai import _experts, _gated
from benchmark.families.reference_lfm2 import _by_rows
from benchmark.families.reference_smallthinker import (  # noqa: F401
    _f32, _head, _rope, compare, rounded_to)

_HERE = os.path.dirname(os.path.abspath(__file__))
CONFIG_FILE = os.path.join(_HERE, "..", "configs", "qwen3-next-80b-a3b.json")
Q_BLOCK = 256           # queries of attention a block
FAULTS = ("zero_state_carry", "zero_filter_carry", "ungated", "rope_all")
FAULT_CHUNK = 2048      # where a dropped carry would fall: the cell's chunk


def _norm(x, w, eps):
    """The zero-centred RMSNorm: the learned vector is the scale's distance
    from 1."""
    return (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
            * (1.0 + w))


def arch_of_model_kwargs(kw: dict) -> dict:
    return {"d_model": kw["d_model"], "heads": kw["num_heads"],
            "kv_heads": kw["num_kv_heads"], "head_dim": kw["head_dim"],
            "rotary": kw["rotary_dim"], "theta": float(kw["rope_theta"]),
            "eps": kw["rms_eps"], "top_k": kw["experts_per_token"],
            "value_heads": kw["linear_heads"],
            "key_heads": kw["linear_key_heads"],
            "linear_head_dim": kw["linear_head_dim"],
            "held": tuple(kw["experts_held"] or (0, kw["num_experts"]))}


def arch_of(params) -> dict:
    """The sizes that go with this parameter tree: the configuration's
    build call, or the toy's, by the tree's hidden size."""
    from benchmark.families import qwen3_next
    with open(CONFIG_FILE) as f:
        kw = json.load(f)["build_model"]["kwargs"]
    known = [arch_of_model_kwargs(kw), arch_of_model_kwargs(
        dict(kw, **qwen3_next.TOY["serve"]["model_kwargs"]))]
    d = params["embed"].shape[1]
    for arch in known:
        if arch["d_model"] == d:
            return arch
    raise ValueError(f"no sizes known for a tree of hidden size {d} (known: "
                     f"{[a['d_model'] for a in known]})")


def _attention(q, k, v):
    """q [B, S, Hq, D], k and v [B, S, Hkv, D]; causal, query head ``i``
    against KV head ``i // (Hq // Hkv)``.  The mask is written out; the
    queries go a block at a time, each against every key with those it may
    not see masked (one ``lax.map`` over equal blocks, so one block's
    scores are alive at a time: 0.27e9 bytes at 16,449 keys)."""
    b, s, hq, d = q.shape
    hkv = k.shape[2]
    blocks = -(-s // Q_BLOCK)
    qb = jnp.pad(q, ((0, 0), (0, blocks * Q_BLOCK - s), (0, 0), (0, 0))
                 ).reshape(b, blocks, Q_BLOCK, hkv, hq // hkv, d)
    j = jnp.arange(s)[None, :]

    def one(xs):
        q_, first = xs
        i = first + jnp.arange(Q_BLOCK)[:, None]
        scores = jnp.einsum("bqhgd,bkhd->bhgqk", q_, k) / np.sqrt(d)
        scores = jnp.where((j <= i)[None, None, None], scores, -jnp.inf)
        return jnp.einsum("bhgqk,bkhd->bqhgd", jax.nn.softmax(scores, -1),
                          v)
    o = jax.lax.map(one, (jnp.moveaxis(qb, 1, 0),
                          jnp.arange(blocks) * Q_BLOCK))
    return jnp.moveaxis(o, 0, 1).reshape(b, blocks * Q_BLOCK, hq * d)[:, :s]


def gated_attention(h, a, arch, weights=None, fault=None):
    """h [B, S, d] -> [B, S, d]."""
    b, s, _ = h.shape
    hq, hkv, dh = arch["heads"], arch["kv_heads"], arch["head_dim"]
    r = dh if fault == "rope_all" else arch["rotary"]
    qkv = h @ _f32(a["qkv"], weights)
    q = qkv[..., :hq * dh].reshape(b, s, hq, dh)
    k = qkv[..., hq * dh:(hq + hkv) * dh].reshape(b, s, hkv, dh)
    v = qkv[..., (hq + hkv) * dh:(hq + 2 * hkv) * dh].reshape(b, s, hkv, dh)
    gate = qkv[..., (hq + 2 * hkv) * dh:]
    q = _norm(q, _f32(a["q_norm"]), arch["eps"])
    k = _norm(k, _f32(a["k_norm"]), arch["eps"])
    q, k = (jnp.concatenate([_rope(x[..., :r], arch["theta"]), x[..., r:]],
                            -1) for x in (q, k))
    o = _attention(q, k, v)
    if fault != "ungated":
        o = o * jax.nn.sigmoid(gate)
    return o @ _f32(a["out"], weights)


def gated_delta(h, p, arch, weights=None, state=None, fault=None):
    """h [B, S, d] -> [B, S, d]: the filter and its SiLU, both L2 norms,
    the decay a head, beta, the literal recurrence, the output norm and
    its gate.  ``state``: a function the matrices go through after every
    token (a control rounds them to bfloat16, as the program's pool holds
    them between steps)."""
    b, s, _ = h.shape
    hv, hk, dh = (arch["value_heads"], arch["key_heads"],
                  arch["linear_head_dim"])
    n, kn = hv * dh, hk * dh
    c = 2 * kn + n
    pre = h @ _f32(p["qkvz"], weights)
    pre, z = pre[..., :c], pre[..., c:]
    w = _f32(p["taps"], weights)                        # [c, taps]
    taps = w.shape[1]
    at = jnp.arange(s) % FAULT_CHUNK                    # offset in a chunk
    mixed = 0.0
    for j in range(taps):
        back = taps - 1 - j                             # pre_{t - back}
        term = jnp.pad(pre, ((0, 0), (back, 0), (0, 0)))[:, :s]
        if fault == "zero_filter_carry":
            term = jnp.where((at >= back)[None, :, None], term, 0.0)
        mixed = mixed + w[:, j] * term
    ba = h @ _f32(p["ba"], weights)                     # [B, S, 2 Hv]
    decay_in = ba[..., hv:] + _f32(p["dt_bias"])
    rate = jnp.exp(_f32(p["a_log"]))
    fresh = (at == 0) if fault == "zero_state_carry" else jnp.zeros(s, bool)

    def token(big_s, xs):
        # a token's own numbers are made here, from its row of the
        # projections: made for the whole sequence beforehand they are
        # 0.8e9 bytes more at the sample's longest prompt
        mixed_t, decay_t, write_t, fresh_t = xs         # [B, .]
        mixed_t = jax.nn.silu(mixed_t)
        q_t = mixed_t[:, :kn].reshape(b, hk, dh)
        k_t = mixed_t[:, kn:2 * kn].reshape(b, hk, dh)
        v_t = mixed_t[:, 2 * kn:].reshape(b, hv, dh)
        q_t = q_t * jax.lax.rsqrt(jnp.sum(q_t * q_t, -1, keepdims=True)
                                  + 1e-6) * dh ** -0.5
        k_t = k_t * jax.lax.rsqrt(jnp.sum(k_t * k_t, -1, keepdims=True)
                                  + 1e-6)
        q_t, k_t = (jnp.repeat(x, hv // hk, axis=1) for x in (q_t, k_t))
        g_t = -rate * jax.nn.softplus(decay_t)          # [B, Hv]
        beta_t = jax.nn.sigmoid(write_t)
        big_s = jnp.where(fresh_t, 0.0, big_s)
        big_s = jnp.exp(g_t)[..., None, None] * big_s
        seen = jnp.einsum("bhk,bhkv->bhv", k_t, big_s)
        big_s = big_s + k_t[..., :, None] * (
            (v_t - seen) * beta_t[..., None])[..., None, :]
        if state is not None:
            big_s = state(big_s)
        return big_s, jnp.einsum("bhk,bhkv->bhv", q_t, big_s)
    _, o = jax.lax.scan(
        token, jnp.zeros((b, hv, dh, dh), jnp.float32),
        tuple(jnp.moveaxis(x, 1, 0) for x in (mixed, decay_in,
                                              ba[..., :hv])) + (fresh,))
    o = jnp.moveaxis(o, 0, 1)                           # [B, S, Hv, D]
    # the gated norm's weight is plain
    o = (o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + arch["eps"])
         * _f32(p["out_norm"]))
    return (o.reshape(b, s, n) * jax.nn.silu(z)) @ _f32(p["out"], weights)


def routing_weights(logits, top_k):
    """[T, E] float32: 0 where an expert is not chosen, else the softmax
    over ALL the experts renormalised over the ``top_k`` chosen."""
    probs = jax.nn.softmax(logits, -1)
    chosen, idx = jax.lax.top_k(probs, top_k)
    chosen = chosen / jnp.sum(chosen, -1, keepdims=True)
    t, e = logits.shape
    return jnp.zeros((t, e), jnp.float32).at[
        jnp.arange(t)[:, None], idx].add(chosen)


def expert_layer(h2, p, arch, weights=None, router_input=None, held=None,
                 shared=True):
    """h2 [T, d] -> [T, d]: the held experts' part of the routed sum and,
    where ``shared``, the gated shared expert.  ``held``: (first id, count)
    in place of the configuration's — the tree's expert weights are then
    those experts'."""
    first, count = held or arch["held"]
    routed = h2 if router_input is None else router_input(h2)
    wsgu, wsd = (_f32(p["shared_gate_up"], weights),
                 _f32(p["shared_down"], weights))
    wsg = _f32(p["shared_gate"], weights)

    def rows_of(rows, routed_rows):
        full = routing_weights(routed_rows @ _f32(p["router"], weights),
                               arch["top_k"])[:, first:first + count]
        y = _experts(rows, full, p["gate_up"], p["down"], weights)
        if shared:
            y = y + jax.nn.sigmoid(rows @ wsg) * _gated(rows, wsgu, wsd)
        return y
    return _by_rows(rows_of, h2, routed)


def hidden(params, tokens, arch=None, weights=None, router_input=None,
           state=None, fault=None, held=None):
    """tokens [B, S] -> the final normed hidden rows [B, S, d] float32.
    ``weights``: a function every weight matrix goes through as it is
    cast (the controls round them to fewer bits); None = as they are.
    ``router_input``: a function the router's input goes through (a
    control rounds it to bfloat16 and nothing else).  ``state``:
    :func:`gated_delta`'s.  ``fault``: one of ``FAULTS``.  ``held``:
    :func:`expert_layer`'s."""
    arch = arch or arch_of(params)
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"fault {fault!r}: one of {FAULTS}")
    with jax.default_matmul_precision("highest"):
        b, s = tokens.shape
        if weights is None:
            x = jnp.asarray(params["embed"][tokens], jnp.float32)
        else:           # the controls round every matrix, as control.py
            x = _f32(params["embed"], weights)[tokens]
        n_layers = sum(1 for k in params if k.startswith("layer"))
        for l in range(n_layers):
            p = params[f"layer{l}"]
            h = _norm(x, _f32(p["norm1"]), arch["eps"])
            if "linear" in p:
                x = x + gated_delta(h, p["linear"], arch, weights, state,
                                    fault)
            else:
                x = x + gated_attention(h, p["attn"], arch, weights, fault)
            h2 = _norm(x, _f32(p["norm2"]), arch["eps"]).reshape(b * s, -1)
            x = x + expert_layer(h2, p, arch, weights, router_input,
                                 held).reshape(b, s, -1)
        return _norm(x, _f32(params["norm_f"]), arch["eps"])


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
    # two programs, the hidden rows between them, as the other state
    # family's reference (reference_ling.py says why)
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
