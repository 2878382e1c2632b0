"""The plain reference of the family ``joyai``: a decoder of pre-norm
RMSNorm blocks with multi-head LATENT attention, a leading dense gated-SiLU
layer, then layers of a shared expert beside top-k-of-E gated-SiLU experts
chosen by sigmoid scores plus a correction bias.  float32, highest matmul
precision, no kernel, no cache, NO ABSORPTION: K and V of every token and
head are expanded from the latent by the definition, every expert is
applied densely to every token and masked by the routing weights, RoPE and
the causal mask are written out.  It reads the program's parameter tree
(bf16 values, cast to float32 a layer — and a block of experts — at a
time: one expert layer in float32 is 4.96e9 bytes) and nothing else of the
program; in particular never the program's routing.

For layer ``l`` on ``x [S, d]`` (RMSNorm eps from the configuration, a
learned scale, no bias anywhere):

  1. ``h = RMSNorm(x; norm1)``
  2. ``c_q = RMSNorm(h q_a; q_norm)``; ``q = c_q q_b`` [H, nope + rope],
     split a head into ``q_nope`` and ``q_rope``.
     ``[c_kv | k_r] = h kv_a``; ``c_kv = RMSNorm(c_kv; kv_norm)``;
     ``k_rope = RoPE(k_r)``, one a token, shared by all heads; ``q_rope =
     RoPE(q_rope)`` a head.  RoPE over ``rope`` dimensions, theta from the
     configuration, pairs ``(x[2i], x[2i+1])`` where ``rope_interleave``
     (else rotate-half), angle ``pos theta^(-2i/rope)``.
     ``[k_nope_h | v_h] = c_kv kv_b`` [H, nope + v].
     ``score_h(i, j) = (q_nope_h,i . k_nope_h,j + q_rope_h,i . k_rope_j) /
     sqrt(nope + rope)``, causal over all ``j <= i``, softmax;
     ``x += concat_h(sum_j p_h(i, j) v_h,j) out``
  3. ``h2 = RMSNorm(x; norm2)``.  A dense layer (``dense_gate_up`` in the
     tree): ``x += W_d(silu(W_g h2) * W_u h2)``.  A routed layer: ``s =
     sigmoid(h2 router)``; the choice is the k largest of ``s +
     router_bias``; the weights are the chosen experts' ``s`` WITHOUT the
     bias, over their sum, times ``routed_scale``; ``x += E_shared(h2) +
     sum_{e chosen} w_e E_e(h2)``, every ``E(u) = W_d(silu(W_g u) * W_u u)``
  4. after the last layer ``RMSNorm(x; norm_f)`` and an untied head.

The sizes the parameter tree does not show (heads, the head's split, k,
theta, the scale, the pairing) come from the configuration file beside the
benchmark, or, for a tree of the toy's width, from the family's ``TOY``.

``lib/agreement.tokens_agree`` materialises ``forward``'s [B, S, vocab]
logits; at the published widths and the cell's sample (12,352 positions x
129,280) that is 6.4e9 bytes beside 13.6e9 of weights and pool.
``served_tokens_agree`` here gathers the hidden rows that chose the served
tokens BEFORE the head, blocks the queries of attention, and returns the
same dictionary.
"""

from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np

# what is no family's own: the cast (through a control's rounding), the
# norm, the head in blocks of columns, the comparison of gathered rows
# (``lib/agreement.tokens_agree``'s numbers) and the 8-bit grid
from benchmark.families.reference_smallthinker import (  # noqa: F401
    _f32, _head, _rms_norm, compare, rounded_to)

_HERE = os.path.dirname(os.path.abspath(__file__))
CONFIG_FILE = os.path.join(_HERE, "..", "configs", "joyai-llm-flash.json")
Q_BLOCK = 512           # queries of attention a block
EXPERT_BLOCK = 16       # experts cast to float32 and applied at a time


def arch_of_config(cfg: dict) -> dict:
    return {"d_model": cfg["hidden_size"],
            "heads": cfg["num_attention_heads"],
            "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
            "v": cfg["v_head_dim"], "kv_rank": cfg["kv_lora_rank"],
            "top_k": cfg["num_experts_per_tok"],
            "routed_scale": float(cfg["routed_scaling_factor"]),
            "theta": float(cfg["rope_theta"]),
            "interleave": bool(cfg["rope_interleave"]),
            "eps": cfg["rms_norm_eps"]}


def arch_of_model_kwargs(kw: dict) -> dict:
    return {"d_model": kw["d_model"], "heads": kw["num_heads"],
            "nope": kw["qk_nope_head_dim"], "rope": kw["qk_rope_head_dim"],
            "v": kw["v_head_dim"], "kv_rank": kw["kv_lora_rank"],
            "top_k": kw["experts_per_token"],
            "routed_scale": float(kw["routed_scale"]),
            "theta": float(kw["rope_theta"]),
            "interleave": bool(kw["rope_interleave"]), "eps": kw["rms_eps"]}


def arch_of(params) -> dict:
    """The sizes that go with this parameter tree: the configuration's,
    or the toy's, by the tree's hidden size."""
    from benchmark.families import joyai
    with open(CONFIG_FILE) as f:
        cfg = json.load(f)
    known = [arch_of_config(cfg), arch_of_model_kwargs(
        dict(cfg["build_model"]["kwargs"],
             **joyai.TOY["serve"]["model_kwargs"]))]
    d = params["embed"].shape[1]
    for arch in known:
        if arch["d_model"] == d:
            return arch
    raise ValueError(f"no sizes known for a tree of hidden size {d} (known: "
                     f"{[a['d_model'] for a in known]})")


def _rope(x, theta, interleave):
    """x [B, S, H, D] at positions 0..S-1."""
    s, d = x.shape[1], x.shape[-1]
    inv_freq = theta ** (-np.arange(0, d, 2, dtype=np.float32) / d)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq   # [S,D/2]
    cos, sin = jnp.cos(angle)[None, :, None], jnp.sin(angle)[None, :, None]
    if interleave:
        x1, x2 = x[..., 0::2], x[..., 1::2]
        return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                         -1).reshape(x.shape)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v):
    """q, k [B, S, H, Dk], v [B, S, H, Dv]; causal.  The mask is written
    out; the queries go a block at a time, each against the keys it can
    see, so that no [S, S] score matrix of a long prompt exists."""
    b, s, h, d = q.shape
    out = []
    for start in range(0, s, Q_BLOCK):
        end = min(start + Q_BLOCK, s)
        i = jnp.arange(start, end)[:, None]
        j = jnp.arange(0, end)[None, :]
        scores = jnp.einsum("bqhd,bkhd->bhqk", q[:, start:end],
                            k[:, :end]) / np.sqrt(d)
        scores = jnp.where((j <= i)[None, None], scores, -jnp.inf)
        out.append(jnp.einsum("bhqk,bkhd->bqhd",
                              jax.nn.softmax(scores, -1), v[:, :end]))
    return jnp.concatenate(out, 1).reshape(b, s, -1)


def _gated(h, gate_up, down):
    f = down.shape[0]
    a = h @ gate_up
    return (jax.nn.silu(a[:, :f]) * a[:, f:]) @ down


def routing_weights(scores, bias, top_k, routed_scale):
    """[T, E] float32: 0 where an expert is not chosen, else its weight.
    The choice reads ``scores + bias``; the weight reads ``scores``."""
    _, idx = jax.lax.top_k(scores + bias, top_k)
    chosen = jnp.take_along_axis(scores, idx, -1)
    chosen = chosen / jnp.sum(chosen, -1, keepdims=True) * routed_scale
    t, e = scores.shape
    return jnp.zeros((t, e), jnp.float32).at[
        jnp.arange(t)[:, None], idx].add(chosen)


def _experts(h, full, gate_up, down, weights):
    """Every expert on every token, masked by the routing weights
    ``full`` [T, E]; ``EXPERT_BLOCK`` experts cast at a time."""
    e = gate_up.shape[0]
    blk = EXPERT_BLOCK if e % EXPERT_BLOCK == 0 else 1

    def one(y, xs):
        wgu, wd, w = xs
        for i in range(blk):
            y = y + w[i][:, None] * _gated(
                h, *_f32((wgu[i], wd[i]), weights))
        return y, None
    y, _ = jax.lax.scan(
        one, jnp.zeros_like(h),
        (gate_up.reshape((e // blk, blk) + gate_up.shape[1:]),
         down.reshape((e // blk, blk) + down.shape[1:]),
         full.T.reshape(e // blk, blk, -1)))
    return y


def hidden(params, tokens, arch=None, weights=None, router_input=None,
           latent=None, zero_bias=False):
    """tokens [B, S] -> the final normed hidden rows [B, S, d] float32.
    ``weights``: a function every weight matrix goes through as it is
    cast (the controls round them to fewer bits); None = as they are.
    ``router_input``: a function the router's input goes through (a
    control rounds it to bfloat16 and nothing else: what it reads is the
    share of ``logit_rms`` that top-k choices flipping make).  ``latent``:
    a function ``c_kv`` after its norm and ``k_rope`` after rotation go
    through (a control rounds them to bfloat16, as the cache holds them).
    ``zero_bias``: the control without the score-correction bias."""
    arch = arch or arch_of(params)
    with jax.default_matmul_precision("highest"):
        b, s = tokens.shape
        if weights is None:
            x = jnp.asarray(params["embed"][tokens], jnp.float32)
        else:           # the controls round every matrix, as control.py
            x = _f32(params["embed"], weights)[tokens]
        n_layers = sum(1 for k in params if k.startswith("layer"))
        hq, dn, dr, dv = arch["heads"], arch["nope"], arch["rope"], arch["v"]
        r = arch["kv_rank"]
        for l in range(n_layers):
            p = params[f"layer{l}"]
            a = p["attn"]
            h = _rms_norm(x, _f32(p["norm1"]), arch["eps"])
            c_q = _rms_norm(h @ _f32(a["q_a"], weights), _f32(a["q_norm"]),
                            arch["eps"])
            q = (c_q @ _f32(a["q_b"], weights)).reshape(b, s, hq, dn + dr)
            kv = h @ _f32(a["kv_a"], weights)
            c_kv = _rms_norm(kv[..., :r], _f32(a["kv_norm"]), arch["eps"])
            k_rope = _rope(kv[..., None, r:], arch["theta"],
                           arch["interleave"])
            if latent is not None:
                c_kv, k_rope = latent(c_kv), latent(k_rope)
            q_rope = _rope(q[..., dn:], arch["theta"], arch["interleave"])
            kv_all = (c_kv @ _f32(a["kv_b"], weights)).reshape(
                b, s, hq, dn + dv)
            q = jnp.concatenate([q[..., :dn], q_rope], -1)
            k = jnp.concatenate(
                [kv_all[..., :dn],
                 jnp.broadcast_to(k_rope, (b, s, hq, dr))], -1)
            o = _attention(q, k, kv_all[..., dn:])
            x = x + o @ _f32(a["out"], weights)
            h2 = _rms_norm(x, _f32(p["norm2"]), arch["eps"]).reshape(
                b * s, -1)
            if "dense_gate_up" in p:
                y = _gated(h2, _f32(p["dense_gate_up"], weights),
                           _f32(p["dense_down"], weights))
            else:
                routed = h2 if router_input is None else router_input(h2)
                scores = jax.nn.sigmoid(routed @ _f32(p["router"], weights))
                bias = _f32(p["router_bias"])
                full = routing_weights(
                    scores, jnp.zeros_like(bias) if zero_bias else bias,
                    arch["top_k"], arch["routed_scale"])
                y = _experts(h2, full, p["gate_up"], p["down"], weights)
                y = y + _gated(h2, _f32(p["shared_gate_up"], weights),
                               _f32(p["shared_down"], weights))
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
    gathered before the head.  A pair at a time, at its own length: a
    2,048-token prompt padded to a 12,288-token neighbour would cost the
    neighbour's activations twice over.  ``weights`` and ``controls``:
    ``hidden``'s."""
    arch = arch_of(params)

    def rows(params, tokens, at):
        x = hidden(params, tokens, arch, weights, **controls)
        return _head(x[0, at], params["lm_head"], weights)
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
        return jnp.argmax(_head(x, params["lm_head"], weights), -1)
    step = jax.jit(step)
    for j in range(new_tokens):
        at = np.asarray([len(p) - 1 + j for p in prompts], np.int32)
        nxt = np.asarray(step(params, jnp.asarray(batch), jnp.asarray(at)))
        for r, p in enumerate(prompts):
            batch[r, len(p) + j] = int(nxt[r])
    return [batch[r, len(p):len(p) + new_tokens].tolist()
            for r, p in enumerate(prompts)]
