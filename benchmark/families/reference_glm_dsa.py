"""The plain reference of the family ``glm_dsa``: a decoder of pre-norm
RMSNorm blocks with multi-head LATENT attention over the rows a LIGHTNING
INDEXER chose (learned sparse attention, ``full`` layers) or the layer
below chose (``shared`` layers: IndexShare), a leading dense gated-SiLU
layer, then layers of a shared expert beside top-k-of-E gated-SiLU experts
chosen by sigmoid scores plus a correction bias, of which the device HOLDS
a block.  float32, highest matmul precision, no kernel, no cache, no pages,
no chunks, NO ABSORPTION and no membership: K and V of every token and head
are expanded from the latent by the definition, ``I(t, s)`` is written as
below and its ``k`` largest taken with ``lax.top_k`` (ties to the lower
position), the attention runs under the explicit mask of that choice, every
held expert is applied densely to every token and masked by the routing
weights.  A prompt at a time, in blocks (queries, heads, rows) so that
16,449 positions at width 6,144 fit beside the program.  It reads the
program's parameter tree (bf16 values, cast to float32 a matrix at a time)
and nothing else of the program.

For layer ``l`` on ``x [S, d]`` (``rms(x; g) = x / sqrt(mean(x^2) + eps) *
g``, no bias on any projection):

  1. ``h = rms(x; norm1)``
  2. ``c_q = rms(h q_a; q_norm)``; ``[q_nope | q_rope] = c_q q_b`` a head;
     ``[c_kv | k_r] = h kv_a``; ``c_kv = rms(c_kv; kv_norm)``; ``k_rope =
     RoPE(k_r)`` one a token; ``[k_nope_h | v_h] = c_kv kv_b``; RoPE over
     the rope dimensions, interleaved pairs, angle ``pos theta^(-2i/rope)``.
     ``score_h(t, s) = (q_nope_h,t . k_nope_h,s + q_rope_h,t . k_rope_s) /
     sqrt(nope + rope)``
  3. a ``full`` layer (``attn/indexer`` in the tree): ``q_tj = c_q W_q[j]``
     (H_I heads of D_I), ``k_s = LayerNorm(h W_k; k_norm, k_norm_bias)``,
     RoPE on the first ``rope`` values of both; ``w_t = h W_w * H_I^-0.5 *
     D_I^-0.5``; ``I(t, s) = sum_j w_tj relu(q_tj . k_s)`` for ``s <= t``;
     ``S_t`` = the ``top`` largest (every ``s <= t`` while ``t < top``).
     A ``shared`` layer: ``S_t`` of the nearest ``full`` layer below.
  4. ``x += concat_h(sum_{s in S_t} softmax_s(score_h(t, s)) v_h,s) out``
  5. ``h2 = rms(x; norm2)``; dense layer ``x += W_d(silu(W_g h2) * W_u
     h2)``; routed layer ``s = sigmoid(h2 router)``, the choice the k
     largest of ``s + router_bias``, weights the chosen ``s`` over their
     sum times ``routed_scale``; ``x += E_shared(h2) + sum_{e chosen AND
     held} w_e E_e(h2)``: what the absent experts would add is left out
  6. ``rms(x; norm_f)`` and an untied head onto the rows held.

``faults`` (the builder's controls: the reference with ONE thing changed,
read against the sound one): ``dense`` no selection, everything at every
length; ``shared_first`` a ``shared`` layer attends the FIRST ``top``
positions instead of the choice; ``stale_index`` the index keys of the
newest ``FAULT_PAGE`` positions are not there yet (they score as zero
keys); ``no_relu`` the ReLU left out; ``no_weights`` ``w_t = 1``.
"""

from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.families.reference_joyai import (_gated, _rope,
                                                routing_weights)
from benchmark.families.reference_lfm2 import _by_rows
from benchmark.families.reference_smallthinker import (  # noqa: F401
    _f32, _head, _rms_norm, compare, rounded_to)

_HERE = os.path.dirname(os.path.abspath(__file__))
CONFIG_FILE = os.path.join(_HERE, "..", "configs", "glm-5.2.json")
Q_BLOCK = 256           # queries of attention a block
HEAD_GROUP = 8          # heads expanded from the latent at a time
FAULT_PAGE = 256        # the page of the deployment the faults speak of
LN_EPS = 1e-6


def arch_of_model_kwargs(kw: dict) -> dict:
    heads, head_dim, top, rope = kw["indexer"]
    first, count = kw["experts_held"]
    return {"d_model": kw["d_model"], "heads": kw["num_heads"],
            "nope": kw["qk_nope_head_dim"], "rope": kw["qk_rope_head_dim"],
            "v": kw["v_head_dim"], "kv_rank": kw["kv_lora_rank"],
            "top_k": kw["experts_per_token"],
            "routed_scale": float(kw["routed_scale"]),
            "theta": float(kw["rope_theta"]),
            "interleave": bool(kw["rope_interleave"]),
            "eps": kw["rms_eps"], "index_heads": heads,
            "index_dim": head_dim, "top": top, "index_rope": rope,
            "layer_indexer": tuple(kw["layer_indexer"]),
            "held": (first, first + count)}


def arch_of(params) -> dict:
    """The sizes that go with this parameter tree: the configuration's
    build call, or the toy's, by the tree's hidden size."""
    from benchmark.families import glm_dsa
    with open(CONFIG_FILE) as f:
        kw = json.load(f)["build_model"]["kwargs"]
    known = [arch_of_model_kwargs(kw), arch_of_model_kwargs(
        dict(kw, **glm_dsa.TOY["serve"]["model_kwargs"]))]
    d = params["embed"].shape[1]
    for arch in known:
        if arch["d_model"] == d:
            return arch
    raise ValueError(f"no sizes known for a tree of hidden size {d} (known: "
                     f"{[a['d_model'] for a in known]})")


def _layer_norm(x, g, b):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * g + b


def _by_queries(f, s: int):
    """``f(positions [Q_BLOCK]) -> [Q_BLOCK, ...]`` over the ``s`` queries
    of a prompt, a block after another (positions past the last are
    computed and dropped)."""
    blocks = -(-s // Q_BLOCK)
    y = jax.lax.map(f, jnp.arange(blocks * Q_BLOCK).reshape(blocks, Q_BLOCK))
    return y.reshape((blocks * Q_BLOCK,) + y.shape[2:])[:s]


def index_choice(h, c_q, p, arch, weights=None, faults=(),
                 choice_input=None):
    """[S, S] bool: the rows each query of one prompt attends.  h [S, d],
    c_q [S, rq]; ``p`` the layer's ``attn/indexer`` parameters."""
    s = h.shape[0]
    hn, dh, top, r = (arch["index_heads"], arch["index_dim"], arch["top"],
                      arch["index_rope"])

    def turned(x):          # [S, H, D]: the first r values turn
        return jnp.concatenate(
            [_rope(x[None, ..., :r], arch["theta"], arch["interleave"])[0],
             x[..., r:]], -1)
    q = turned((c_q @ _f32(p["q"], weights)).reshape(s, hn, dh))
    k = turned(_layer_norm(h @ _f32(p["k"], weights), _f32(p["k_norm"]),
                           _f32(p["k_norm_bias"]))[:, None])[:, 0]
    w = (h @ _f32(p["weights"], weights)) * (hn ** -0.5 * dh ** -0.5)
    if "no_weights" in faults:
        w = jnp.ones_like(w)
    if choice_input is not None:
        q, k = choice_input(q), choice_input(k)
    pos = jnp.arange(s)

    def block(t):
        at = jnp.minimum(t, s - 1)
        prod = jnp.einsum("qhd,kd->qhk", q[at], k)
        if "stale_index" in faults:
            fresh = pos[None, :] > t[:, None] - FAULT_PAGE
            prod = jnp.where(fresh[:, None, :], 0.0, prod)
        if "no_relu" not in faults:
            prod = jnp.maximum(prod, 0.0)
        score = jnp.sum(w[at][:, :, None] * prod, axis=1)       # [Q, S]
        seen = pos[None, :] <= t[:, None]
        _, best = jax.lax.top_k(jnp.where(seen, score, -jnp.inf),
                                min(top, s))
        taken = jnp.zeros((Q_BLOCK, s), bool).at[
            jnp.arange(Q_BLOCK)[:, None], best].set(True)
        return taken & seen
    return _by_queries(block, s)


def latent_attention(h, c_q, a, arch, mask, weights=None, latent=None):
    """h [S, d] and its query latent c_q [S, rq] -> attention's output
    [S, d]: K and V of every token expanded from the latent,
    ``HEAD_GROUP`` heads at a time, each under ``mask`` [S, S]."""
    s = h.shape[0]
    hq, dn, dr, dv = arch["heads"], arch["nope"], arch["rope"], arch["v"]
    r = arch["kv_rank"]
    kv = h @ _f32(a["kv_a"], weights)
    c_kv = _rms_norm(kv[:, :r], _f32(a["kv_norm"]), arch["eps"])
    k_rope = _rope(kv[None, :, None, r:], arch["theta"],
                   arch["interleave"])[0]                       # [S, 1, dr]
    if latent is not None:
        c_kv, k_rope = latent(c_kv), latent(k_rope)
    g = HEAD_GROUP if hq % HEAD_GROUP == 0 else hq
    q_b = a["q_b"].reshape(-1, hq // g, g * (dn + dr))
    kv_b = a["kv_b"].reshape(r, hq // g, g * (dn + dv))
    w_o = a["out"].reshape(hq // g, g * dv, -1)

    def group(y, xs):
        wq, wkv, wo = xs
        q = (c_q @ _f32(wq, weights)).reshape(s, g, dn + dr)
        q = jnp.concatenate(
            [q[..., :dn], _rope(q[None, ..., dn:], arch["theta"],
                                arch["interleave"])[0]], -1)
        kv_all = (c_kv @ _f32(wkv, weights)).reshape(s, g, dn + dv)
        k = jnp.concatenate(
            [kv_all[..., :dn], jnp.broadcast_to(k_rope, (s, g, dr))], -1)

        def block(t):
            at = jnp.minimum(t, s - 1)
            sc = jnp.einsum("qhd,khd->hqk", q[at], k) / np.sqrt(dn + dr)
            sc = jnp.where(mask[at][None], sc, -jnp.inf)
            return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1),
                              kv_all[..., dn:])
        o = _by_queries(block, s).reshape(s, g * dv)
        return y + o @ _f32(wo, weights), None
    y, _ = jax.lax.scan(group, jnp.zeros_like(h),
                        (jnp.moveaxis(q_b, 1, 0), jnp.moveaxis(kv_b, 1, 0),
                         w_o))
    return y


def _experts(h, full, gate_up, down, weights):
    """The held experts on every token, masked by their routing weights
    ``full`` [T, held]; one expert cast at a time."""
    def one(y, xs):
        wgu, wd, w = xs
        return y + w[:, None] * _by_rows(
            lambda rows: _gated(rows, *_f32((wgu, wd), weights)), h), None
    y, _ = jax.lax.scan(one, jnp.zeros_like(h), (gate_up, down, full.T))
    return y


def hidden(params, tokens, arch=None, weights=None, faults=(),
           router_input=None, latent=None, expert_input=None):
    """tokens [1, S] -> the final normed hidden rows [1, S, d] float32.
    ``weights``: a function every weight matrix goes through as it is cast
    (the controls round them to fewer bits).  ``faults``: the module's
    docstring.  ``router_input``: a function the CHOICE's inputs (the index
    queries and keys, nothing else) go through — the choice of rows is
    this family's first router, and a flipped row its flipped expert.
    ``expert_input``: the same for the experts' router.  ``latent``: a
    function ``c_kv`` after its norm and ``k_rope`` after rotation go
    through."""
    arch = arch or arch_of(params)
    with jax.default_matmul_precision("highest"):
        if tokens.shape[0] != 1:
            raise ValueError("the reference takes a prompt at a time")
        s = tokens.shape[1]
        if weights is None:
            x = jnp.asarray(params["embed"][tokens[0]], jnp.float32)
        else:
            x = _f32(params["embed"], weights)[tokens[0]]
        n_layers = sum(1 for k in params if k.startswith("layer"))
        pos = jnp.arange(s)
        lo, hi = arch["held"]
        chosen = None
        for l in range(n_layers):
            p = params[f"layer{l}"]
            a = p["attn"]
            h = _rms_norm(x, _f32(p["norm1"]), arch["eps"])
            c_q = _rms_norm(h @ _f32(a["q_a"], weights), _f32(a["q_norm"]),
                            arch["eps"])
            if "indexer" in a:
                chosen = mask = index_choice(h, c_q, a["indexer"], arch,
                                             weights, faults, router_input)
            else:
                mask = chosen
            if "dense" in faults:
                mask = pos[None, :] <= pos[:, None]
            elif "shared_first" in faults and "indexer" not in a:
                mask = ((pos[None, :] <= pos[:, None])
                        & (pos[None, :] < arch["top"]))
            x = x + latent_attention(h, c_q, a, arch, mask, weights,
                                     latent)
            h2 = _rms_norm(x, _f32(p["norm2"]), arch["eps"])
            if "dense_gate_up" in p:
                wgu, wd = (_f32(p["dense_gate_up"], weights),
                           _f32(p["dense_down"], weights))
                y = _by_rows(lambda rows: _gated(rows, wgu, wd), h2)
            else:
                routed = h2 if expert_input is None else expert_input(h2)
                scores = jax.nn.sigmoid(routed @ _f32(p["router"], weights))
                full = routing_weights(scores, _f32(p["router_bias"]),
                                       arch["top_k"], arch["routed_scale"])
                y = _experts(h2, full[:, lo:hi], p["gate_up"], p["down"],
                             weights)
                wgu, wd = (_f32(p["shared_gate_up"], weights),
                           _f32(p["shared_down"], weights))
                y = y + _by_rows(lambda rows: _gated(rows, wgu, wd), h2)
            x = x + y
        return _rms_norm(x, _f32(params["norm_f"]), arch["eps"])[None]


def forward(params, tokens):
    """tokens [B, S] int32 -> logits [B, S, vocab] float32, a row at a
    time (the toy and the tests)."""
    return jnp.concatenate(
        [_head(hidden(params, tokens[i:i + 1]), params["lm_head"])
         for i in range(tokens.shape[0])], 0)


def rows_that_chose(params, prompts, served, weights=None,
                    **controls) -> list:
    """Teacher-forced, as ``lib/agreement.rows_that_chose``: for each
    (prompt, served tokens) pair the logits at the positions that chose
    each served token, a [tokens, vocab] array a pair — the hidden rows
    gathered before the head, a pair at a time at its own length, in two
    programs.  ``weights`` and ``controls``: ``hidden``'s."""
    arch = arch_of(params)
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
    choice, no cache, a prompt at a time, the whole forward again for
    every token."""
    arch = arch_of(params)

    def step(params, tokens, at):
        x = hidden(params, tokens, arch, weights)[0, at]
        return jnp.argmax(_head(x, params["lm_head"], weights), -1)
    step = jax.jit(step)
    out = []
    for p in prompts:
        row = np.zeros((1, len(p) + new_tokens), np.int32)
        row[0, :len(p)] = p
        for j in range(new_tokens):
            row[0, len(p) + j] = int(step(params, jnp.asarray(row),
                                          len(p) - 1 + j))
        out.append(row[0, len(p):].tolist())
    return out
