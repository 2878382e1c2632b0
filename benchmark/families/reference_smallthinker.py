"""The plain reference of the family ``smallthinker``: a decoder whose
layers alternate one global NoPE layer with three window RoPE layers,
grouped-query heads, and a top-k-of-E gated-ReLU expert layer whose router
reads the pre-attention norm.  float32, highest matmul precision, no
kernel, no cache, every expert applied densely to every token and masked
by the routing weights, RoPE and the window mask written out.  It reads the
program's parameter tree (bf16 values, cast to float32 a layer — and an
expert — at a time: the whole tree in float32 would be 22e9 bytes) and
nothing else of the program.

For layer ``l`` on ``x [S, d]`` (RMSNorm eps from the configuration, no
bias anywhere):

  1. ``h = RMSNorm(x; norm1)``
  2. ``r = h W_router`` in f32; the k largest; weights = softmax over them
  3. attention on ``h``: Hq query heads over Hkv KV heads of D (query head
     ``i`` reads KV head ``i // (Hq / Hkv)``), scale 1/sqrt(D);
     ``rope_layout[l] = 1``: rotate-half RoPE over the whole head on q and
     k, else no position at all; ``sliding_window_layout[l] = 1``: query
     ``i`` sees keys ``i - W < j <= i``, else every ``j <= i``;
     ``x += concat(heads) W_out``
  4. ``h2 = RMSNorm(x; norm2)``; ``x += sum_{e in top k} w_e W_down,e(
     relu(W_gate,e h2) * W_up,e h2)``
  5. after the last layer ``RMSNorm(x; norm_f)`` and an untied head.

The sizes the parameter tree does not show (heads, k, window, theta, the
two layouts) come from the configuration file beside the benchmark, or,
for a tree of the toy's width, from the family's ``TOY``.

``lib/agreement.tokens_agree`` materialises ``forward``'s [B, S, vocab]
logits; at the published widths and the cell's sample that is 7.5e9 bytes
beside 14.3e9 of weights and pool.  ``served_tokens_agree`` here gathers
the hidden rows that chose the served tokens BEFORE the head, blocks the
queries of attention, and returns the same dictionary.
"""

from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
CONFIG_FILE = os.path.join(_HERE, "..", "configs",
                           "smallthinker-21b-a3b.json")
Q_BLOCK = 512           # queries of attention a block
HEAD_BLOCKS = 8         # the head's columns, a block at a time


def arch_of_config(cfg: dict) -> dict:
    return {"d_model": cfg["hidden_size"],
            "heads": cfg["num_attention_heads"],
            "kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg["head_dim"],
            "top_k": cfg["moe_num_active_primary_experts"],
            "window": cfg["sliding_window_size"],
            "theta": float(cfg["rope_theta"]), "eps": cfg["rms_norm_eps"],
            "layer_window": [bool(v) for v in cfg["sliding_window_layout"]],
            "layer_rope": [bool(v) for v in cfg["rope_layout"]]}


def arch_of_model_kwargs(kw: dict) -> dict:
    return {"d_model": kw["d_model"], "heads": kw["num_heads"],
            "kv_heads": kw["num_kv_heads"], "head_dim": kw["head_dim"],
            "top_k": kw["experts_per_token"], "window": kw["window"],
            "theta": float(kw["rope_theta"]), "eps": kw["rms_eps"],
            "layer_window": [bool(v) for v in kw["layer_window"]],
            "layer_rope": [bool(v) for v in kw["layer_rope"]]}


def arch_of(params) -> dict:
    """The sizes that go with this parameter tree: the configuration's,
    or the toy's, by the tree's hidden size."""
    from benchmark.families import smallthinker
    with open(CONFIG_FILE) as f:
        cfg = json.load(f)
    known = [arch_of_config(cfg), arch_of_model_kwargs(
        dict(cfg["build_model"]["kwargs"],
             **smallthinker.TOY["serve"]["model_kwargs"]))]
    d = params["embed"].shape[1]
    for arch in known:
        if arch["d_model"] == d:
            return arch
    raise ValueError(f"no sizes known for a tree of hidden size {d} (known: "
                     f"{[a['d_model'] for a in known]})")


def _f32(tree, weights=None):
    def cast(a):
        a = jnp.asarray(a, jnp.float32)
        return weights(a) if weights is not None and a.ndim >= 2 else a
    return jax.tree_util.tree_map(cast, tree)


def _rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, theta):
    """x [B, S, H, D] at positions 0..S-1, rotate-half pairing."""
    s, d = x.shape[1], x.shape[-1]
    inv_freq = theta ** (-np.arange(0, d, 2, dtype=np.float32) / d)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq   # [S,D/2]
    cos, sin = jnp.cos(angle)[None, :, None], jnp.sin(angle)[None, :, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, window):
    """q [B, S, Hq, D], k and v [B, S, Hkv, D]; causal, and where
    ``window`` is set query i sees keys i - window < j <= i.  The mask is
    written out; the queries go a block at a time, each against the keys
    it can see, so that no [S, S] score matrix of a long prompt exists."""
    b, s, hq, d = q.shape
    hkv = k.shape[2]
    q = q.reshape(b, s, hkv, hq // hkv, d)
    out = []
    for start in range(0, s, Q_BLOCK):
        end = min(start + Q_BLOCK, s)
        first = 0 if window is None else max(0, start - window + 1)
        i = jnp.arange(start, end)[:, None]
        j = jnp.arange(first, end)[None, :]
        mask = j <= i
        if window is not None:
            mask &= j > i - window
        scores = jnp.einsum("bqhgd,bkhd->bhgqk", q[:, start:end],
                            k[:, first:end]) / np.sqrt(d)
        scores = jnp.where(mask[None, None, None], scores, -jnp.inf)
        out.append(jnp.einsum("bhgqk,bkhd->bqhgd",
                              jax.nn.softmax(scores, -1), v[:, first:end]))
    return jnp.concatenate(out, 1).reshape(b, s, hq * d)


def _experts(h, router_logits, top_k, gate_up, down, weights):
    """Every expert on every token, masked by the routing weights."""
    vals, idx = jax.lax.top_k(router_logits, top_k)
    chosen = jax.nn.softmax(vals, -1)
    t, e = router_logits.shape
    full = jnp.zeros((t, e), jnp.float32).at[
        jnp.arange(t)[:, None], idx].add(chosen)        # 0 where not chosen
    f = down.shape[1]

    def one(y, xs):
        wgu, wd, w = xs
        wgu, wd = _f32((wgu, wd), weights)
        a = h @ wgu
        return y + w[:, None] * ((jax.nn.relu(a[:, :f]) * a[:, f:]) @ wd), None
    y, _ = jax.lax.scan(one, jnp.zeros_like(h), (gate_up, down, full.T))
    return y


def hidden(params, tokens, arch=None, weights=None, router_input=None):
    """tokens [B, S] -> the final normed hidden rows [B, S, d] float32.
    ``weights``: a function every weight matrix goes through as it is
    cast (the controls round them to fewer bits); None = as they are.
    ``router_input``: a function the router's input goes through (a
    control rounds it to bfloat16, as the program's stream is, and
    nothing else: what it reads is the share of ``logit_rms`` that top-k
    choices flipping between bf16 and f32 make)."""
    arch = arch or arch_of(params)
    with jax.default_matmul_precision("highest"):
        b, s = tokens.shape
        if weights is None:
            x = jnp.asarray(params["embed"][tokens], jnp.float32)
        else:           # the controls round every matrix, as control.py
            x = _f32(params["embed"], weights)[tokens]
        n_layers = sum(1 for k in params if k.startswith("layer"))
        lw, lr = arch["layer_window"], arch["layer_rope"]
        hq, hkv, dh = arch["heads"], arch["kv_heads"], arch["head_dim"]
        for l in range(n_layers):
            p = params[f"layer{l}"]
            h = _rms_norm(x, _f32(p["norm1"]), arch["eps"])
            routed = h if router_input is None else router_input(h)
            router_logits = routed.reshape(b * s, -1) @ _f32(p["router"],
                                                             weights)
            qkv = h @ _f32(p["attn"]["qkv"], weights)
            q = qkv[..., :hq * dh].reshape(b, s, hq, dh)
            k = qkv[..., hq * dh:(hq + hkv) * dh].reshape(b, s, hkv, dh)
            v = qkv[..., (hq + hkv) * dh:].reshape(b, s, hkv, dh)
            if lr[l % len(lr)]:
                q, k = _rope(q, arch["theta"]), _rope(k, arch["theta"])
            o = _attention(q, k, v,
                           arch["window"] if lw[l % len(lw)] else None)
            x = x + o @ _f32(p["attn"]["out"], weights)
            h2 = _rms_norm(x, _f32(p["norm2"]), arch["eps"])
            y = _experts(h2.reshape(b * s, -1), router_logits, arch["top_k"],
                         p["gate_up"], p["down"], weights)
            x = x + y.reshape(b, s, -1)
        return _rms_norm(x, _f32(params["norm_f"]), arch["eps"])


def _head(rows, head, weights=None):
    """rows [..., d] float32 -> logits [..., vocab], the head cast a block
    of columns at a time."""
    with jax.default_matmul_precision("highest"):
        v = head.shape[1]
        if weights is not None or v % HEAD_BLOCKS:
            return rows @ _f32(head, weights)
        step = v // HEAD_BLOCKS
        return jnp.concatenate(
            [rows @ _f32(head[:, c:c + step])
             for c in range(0, v, step)], -1)


def forward(params, tokens):
    """tokens [B, S] int32 -> logits [B, S, vocab] float32 (the toy and
    the tests; at the published widths see the module's docstring)."""
    return _head(hidden(params, tokens), params["lm_head"])


def rows_that_chose(params, prompts, served, weights=None,
                    router_input=None) -> list:
    """Teacher-forced, as ``lib/agreement.rows_that_chose``: for each
    (prompt, served tokens) pair the logits at the positions that chose
    each served token, a [tokens, vocab] array a pair — the hidden rows
    gathered before the head."""
    total = max(len(p) + len(t) for p, t in zip(prompts, served))
    most = max(len(t) for t in served)
    batch = np.zeros((len(prompts), total), np.int32)
    chose = np.zeros((len(prompts), most), np.int32)
    for r, (p, t) in enumerate(zip(prompts, served)):
        batch[r, :len(p)] = p
        batch[r, len(p):len(p) + len(t)] = t
        chose[r] = np.minimum(len(p) - 1 + np.arange(most), total - 1)
    arch = arch_of(params)

    def rows(params, tokens, at):
        x = hidden(params, tokens, arch, weights, router_input)
        x = jnp.take_along_axis(x, at[:, :, None], axis=1)
        return _head(x, params["lm_head"], weights)
    logits = np.asarray(jax.jit(rows)(params, jnp.asarray(batch),
                                      jnp.asarray(chose)))
    return [logits[r, :len(t)] for r, t in enumerate(served)]


def compare(chose, served, rtol, program_logits=None,
            logit_rms_limit=None) -> dict:
    """The two numbers of ``lib/agreement.tokens_agree`` and its
    dictionary, from rows already gathered."""
    scale = max(float(np.abs(rows).max()) for rows in chose)
    worst, compared, identical = 0.0, 0, 0
    for rows, t in zip(chose, served):
        chosen = rows[np.arange(len(t)), np.asarray(t)]
        gap = rows.max(-1) - chosen
        worst = max(worst, float(gap.max()))
        compared += len(t)
        identical += int((gap == 0).sum())
    finite = all(bool(np.isfinite(rows).all()) for rows in chose)
    said = {"ok": finite and worst <= 2 * rtol * scale,
            "tokens_compared": compared, "greedy_identical": identical,
            "worst_gap": worst, "logit_scale": scale,
            "allowed_gap": 2 * rtol * scale}
    if program_logits is not None:
        ref = np.concatenate(chose).astype(np.float64)
        got = np.concatenate([np.asarray(a, np.float64)
                              for a in program_logits])
        rms = float(np.sqrt(np.mean((got - ref) ** 2)) / ref.std())
        said.update(logit_rms=rms, logit_rms_limit=logit_rms_limit,
                    logit_max=float(np.abs(got - ref).max() / scale))
        said["ok"] = bool(said["ok"] and np.isfinite(rms)
                          and rms <= logit_rms_limit)
    return said


def served_tokens_agree(params, prompts, served, rtol: float,
                        program_logits=None, logit_rms_limit=None) -> dict:
    return compare(rows_that_chose(params, prompts, served), served, rtol,
                   program_logits, logit_rms_limit)


def rounded_to(bits: int):
    """A weight matrix on a signed ``bits``-bit grid, one scale per output
    column — ``lib/agreement.with_weights_at``'s rounding, for the
    controls of ``benchmark/control_rows.py``."""
    def rounded(w):
        top = 2 ** (bits - 1) - 1
        scale = jnp.max(jnp.abs(w), axis=tuple(range(w.ndim - 1)),
                        keepdims=True) / top
        scale = jnp.where(scale == 0, 1.0, scale)
        return jnp.round(w / scale) * scale
    return rounded


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
