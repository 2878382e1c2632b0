"""The plain reference of the family ``minicpm_sala``: a decoder of pre-norm
RMSNorm blocks with dense gated-SiLU MLPs and muP scalars whose mixer is, a
layer, either BLOCK-SPARSE softmax attention that chooses which 64-token
blocks a query reads through pooled keys, or LIGHTNING linear attention (a
constant decay a head, no write gate).  float32, highest matmul precision,
a prompt at a time, no kernel, no cache, no pages, no chunks: the linear
layer is the literal recurrence, a ``lax.scan`` over tokens; the sparse
layer scores every query against every pooled key that exists for it,
chooses ITS blocks, and attends the whole sequence under the mask of that
choice, a block of queries at a time.  It reads the program's parameter
tree (bf16 values, cast to float32 a layer at a time) and nothing else of
the program.

``rms(x; g) = x / sqrt(mean(x^2) + eps) * g``.  ``x = scale_emb *
E[token]``; a layer ``x += c * Mixer(rms(x; norm1))``, ``x += c *
W_down(silu(W_gate h) * W_up h)`` with ``h = rms(x; norm2)`` and ``c =
scale_depth / sqrt(published depth)``; logits ``= lm_head . rms(x; norm_f)
/ (hidden / dim_model_base)``.  ``s = head_dim**-0.5``.

**sparse** (``attn`` in the tree): ``[q | k | v] = h qkv`` (Hq, Hkv, Hkv
heads); ``q, k <- rms`` a head; no positions; query head ``h`` reads KV
head ``g = h // (Hq / Hkv)``.  Pooled key ``c_j`` = mean of ``k_m``, ``m``
in ``[stride j, stride j + pool)``; it exists for a query at ``t`` iff
``stride j + pool - 1 <= t``.  Block ``b`` = positions ``[block b, block (b
+ 1))``, ``b_t = t // block``.  A query at ``t`` with ``t + 1 <=
dense_len``: causal softmax over every ``m <= t``.  Else ``p[h, j] =
softmax over the existing j of s q_h . c_j``; ``r[g, j] = sum of p over
the heads of g``; ``R[g, b] = max of r[g, j] over the existing j in (block
/ stride) b - 1 .. (block / stride) (b + 1) - 1``; FORCED: the first
``init`` blocks and ``b_t - window / block + 1 .. b_t``; CHOSEN: the
``top`` best of the others by ``R``, ties to the lower ``b``; softmax over
``m <= t`` in forced and chosen blocks.  ``out = out_w (o * sigmoid(h
gate))``.

**lightning** (``linear``): ``[q | k | v] = h qkv``, H heads of D; ``q, k
<- rms`` a head; rotate-half RoPE at the true position; ``S_t = lambda_h
S_{t-1} + k_t v_t^T``, ``S_0 = 0``, ``o_t = s S_t^T q_t``; ``lambda_h =
exp(-2**(-8 (h + 1) / H) (1 - l / (depth - 1) + 1e-5))``, ``l`` the
layer's PUBLISHED index; ``out = out_w (rms(o; out_norm) a head *
sigmoid(h gate))``.

``faults`` (the builder's controls: the reference with ONE thing changed,
read against the sound one): ``no_chosen`` past ``dense_len`` only the
forced blocks; ``dense`` everything at every length; ``stale_pooled`` the
pooled keys of the newest ``FAULT_PAGE`` positions do not exist yet;
``no_decay`` ``lambda`` = 1; ``zero_state`` the state zeroed every
``FAULT_PAGE`` tokens.
"""

from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.families.reference_lfm2 import _by_rows
from benchmark.families.reference_joyai import _gated
from benchmark.families.reference_smallthinker import (  # noqa: F401
    _f32, _head, _rms_norm, _rope, compare, rounded_to)

_HERE = os.path.dirname(os.path.abspath(__file__))
CONFIG_FILE = os.path.join(_HERE, "..", "configs", "minicpm-sala-9b.json")
Q_BLOCK = 128           # queries of attention a block
FAULT_PAGE = 2048       # the page of the deployment the faults speak of


def arch_of_model_kwargs(kw: dict) -> dict:
    block, pool, stride, top, window, init, dense_len = kw["sparse"][:7]
    heads, head_dim, first, depth = kw["lightning"]
    return {"d_model": kw["d_model"], "heads": kw["num_heads"],
            "kv_heads": kw["num_kv_heads"], "head_dim": kw["head_dim"],
            "block": block, "pool": pool, "stride": stride, "top": top,
            "window": window, "init": init, "dense_len": dense_len,
            "linear_heads": heads, "linear_head_dim": head_dim,
            "first_layer": first, "depth": depth,
            "theta": float(kw["rope_theta"]), "eps": kw["rms_eps"],
            "scale_emb": float(kw["mup"][0]),
            "branch": float(kw["mup"][1]) / np.sqrt(kw["mup"][2]),
            "head_divisor": float(kw["mup"][3])}


def arch_of(params) -> dict:
    """The sizes that go with this parameter tree: the configuration's
    build call, or the toy's, by the tree's hidden size."""
    from benchmark.families import minicpm_sala
    with open(CONFIG_FILE) as f:
        kw = json.load(f)["build_model"]["kwargs"]
    kw.setdefault("rms_eps", 1e-6)
    known = [arch_of_model_kwargs(kw), arch_of_model_kwargs(
        dict(kw, **minicpm_sala.TOY["serve"]["model_kwargs"]))]
    d = params["embed"].shape[1]
    for arch in known:
        if arch["d_model"] == d:
            return arch
    raise ValueError(f"no sizes known for a tree of hidden size {d} (known: "
                     f"{[a['d_model'] for a in known]})")


def blocks_read(q, pooled, t, arch, faults=(), choice_input=None):
    """[B, Q, Hkv, blocks] bool: the blocks the queries ``q`` [B, Q, Hq, D]
    at positions ``t`` [Q] read, through ``pooled`` [B, J, Hkv, D]."""
    b, n, hq, d = q.shape
    j_all, hkv = pooled.shape[1], pooled.shape[2]
    per = arch["block"] // arch["stride"]
    blocks = j_all // per
    if choice_input is not None:
        q, pooled = choice_input(q), choice_input(pooled)
    seen = t - (FAULT_PAGE if "stale_pooled" in faults else 0)
    exists = (arch["stride"] * jnp.arange(j_all)[None, :] + arch["pool"] - 1
              <= seen[:, None])                             # [Q, J]
    sc = jnp.einsum("bqhgd,bjhd->bqhgj",
                    q.reshape(b, n, hkv, hq // hkv, d), pooled
                    ) * d ** -0.5
    sc = jnp.where(exists[None, :, None, None, :], sc, -jnp.inf)
    top_ = jnp.max(sc, -1, keepdims=True)
    p = jnp.where(exists[None, :, None, None, :],
                  jnp.exp(sc - jnp.where(jnp.isfinite(top_), top_, 0.0)),
                  0.0)
    total = jnp.sum(p, -1, keepdims=True)
    r = jnp.sum(p / jnp.where(total > 0, total, 1.0), axis=3)  # [B,Q,Hkv,J]
    ids = jnp.arange(blocks)
    over = per * ids[:, None] - 1 + jnp.arange(per + 1)[None, :]  # [nb, 5]
    ok = ((over >= 0) & (over < j_all))[None] & exists[
        :, jnp.clip(over, 0, j_all - 1)]                    # [Q, nb, 5]
    big_r = jnp.max(jnp.where(ok[None, :, None],
                              r[..., jnp.clip(over, 0, j_all - 1)],
                              -jnp.inf), -1)                # [B,Q,Hkv,nb]
    own = t // arch["block"]
    first_window = own - arch["window"] // arch["block"] + 1
    forced = ((ids[None, :] < arch["init"])
              | ((ids[None, :] >= first_window[:, None])
                 & (ids[None, :] <= own[:, None])))         # [Q, nb]
    free = (~forced) & (ids[None, :] < first_window[:, None])
    _, best = jax.lax.top_k(
        jnp.where(free[None, :, None], big_r, -jnp.inf),
        min(arch["top"], blocks))
    chosen = jnp.zeros(big_r.shape, bool).at[
        jnp.arange(b)[:, None, None, None],
        jnp.arange(n)[None, :, None, None],
        jnp.arange(hkv)[None, None, :, None], best].set(True)
    chosen &= free[None, :, None]       # fewer than ``top`` candidates
    if "no_chosen" in faults:
        chosen = jnp.zeros_like(chosen)
    dense = t + 1 <= arch["dense_len"]
    if "dense" in faults:
        dense = jnp.ones_like(dense)
    return jnp.where(dense[None, :, None, None],
                     (ids[None, :] <= own[:, None])[None, :, None],
                     chosen | forced[None, :, None])


def sparse_attention(h, a, arch, weights=None, faults=(),
                     choice_input=None):
    """h [B, S, d] -> [B, S, d]."""
    b, s, _ = h.shape
    hq, hkv, dh = arch["heads"], arch["kv_heads"], arch["head_dim"]
    qkv = h @ _f32(a["qkv"], weights)
    q = _rms_norm(qkv[..., :hq * dh].reshape(b, s, hq, dh),
                  _f32(a["q_norm"]), arch["eps"])
    k = _rms_norm(qkv[..., hq * dh:(hq + hkv) * dh].reshape(b, s, hkv, dh),
                  _f32(a["k_norm"]), arch["eps"])
    v = qkv[..., (hq + hkv) * dh:].reshape(b, s, hkv, dh)
    stride, block = arch["stride"], arch["block"]
    per = block // stride
    blocks = -(-s // block)
    # c_j, j < blocks * per: sums of ``stride`` rows, two neighbours a key
    halves = jnp.sum(jnp.pad(
        k, ((0, 0), (0, (blocks * per + 1) * stride - s), (0, 0), (0, 0))
    ).reshape(b, blocks * per + 1, stride, hkv, dh), axis=2)
    pooled = (halves[:, :-1] + halves[:, 1:]) / arch["pool"]
    tiles = -(-s // Q_BLOCK)
    qb = jnp.pad(q, ((0, 0), (0, tiles * Q_BLOCK - s), (0, 0), (0, 0))
                 ).reshape(b, tiles, Q_BLOCK, hq, dh)
    m = jnp.arange(s)

    def one(xs):
        q_, first = xs
        t = first + jnp.arange(Q_BLOCK)
        read = blocks_read(q_, pooled, t, arch, faults, choice_input)
        mask = (read[..., m // block]                   # [B, Q, Hkv, S]
                & (m[None, :] <= t[:, None])[None, :, None])
        sc = jnp.einsum("bqhgd,bkhd->bqhgk",
                        q_.reshape(b, Q_BLOCK, hkv, hq // hkv, dh), k
                        ) * dh ** -0.5
        sc = jnp.where(mask[:, :, :, None, :], sc, -jnp.inf)
        return jnp.einsum("bqhgk,bkhd->bqhgd", jax.nn.softmax(sc, -1), v)
    o = jax.lax.map(one, (jnp.moveaxis(qb, 1, 0),
                          jnp.arange(tiles) * Q_BLOCK))
    o = jnp.moveaxis(o, 0, 1).reshape(b, tiles * Q_BLOCK, hq * dh)[:, :s]
    gate = jax.nn.sigmoid(h @ _f32(a["gate"], weights))
    return (o * gate) @ _f32(a["out"], weights)


def lightning_attention(h, p, layer, arch, weights=None, faults=(),
                        state=None):
    """h [B, S, d] -> [B, S, d]: the literal recurrence.  ``state``: a
    function the matrices go through after every token."""
    b, s, _ = h.shape
    hn, dh = arch["linear_heads"], arch["linear_head_dim"]
    n = hn * dh
    qkv = h @ _f32(p["qkv"], weights)
    q, k, v = (qkv[..., i * n:(i + 1) * n].reshape(b, s, hn, dh)
               for i in range(3))
    q = _rope(_rms_norm(q, _f32(p["q_norm"]), arch["eps"]), arch["theta"])
    k = _rope(_rms_norm(k, _f32(p["k_norm"]), arch["eps"]), arch["theta"])
    slopes = 2.0 ** (-8.0 * np.arange(1, hn + 1, dtype=np.float32) / hn)
    lam = np.exp(-slopes * (1.0 - layer / max(arch["depth"] - 1, 1) + 1e-5))
    if "no_decay" in faults:
        lam = np.ones_like(lam)
    lam = jnp.asarray(lam, jnp.float32)[None, :, None, None]

    def token(big_s, xs):
        q_t, k_t, v_t, i = xs                               # [B, H, D]
        if "zero_state" in faults:
            big_s = jnp.where(i % FAULT_PAGE == 0, 0.0, big_s)
        big_s = lam * big_s + k_t[..., :, None] * v_t[..., None, :]
        if state is not None:
            big_s = state(big_s)
        return big_s, dh ** -0.5 * jnp.einsum("bhk,bhkv->bhv", q_t, big_s)
    _, o = jax.lax.scan(
        token, jnp.zeros((b, hn, dh, dh), jnp.float32),
        tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v))
        + (jnp.arange(s),))
    o = _rms_norm(jnp.moveaxis(o, 0, 1), _f32(p["out_norm"]), arch["eps"])
    gate = jax.nn.sigmoid(h @ _f32(p["gate"], weights))
    return (o.reshape(b, s, n) * gate) @ _f32(p["out"], weights)


def hidden(params, tokens, arch=None, weights=None, faults=(),
           router_input=None, state=None):
    """tokens [B, S] -> the final hidden rows [B, S, d] float32, normed
    and divided.  ``weights``: a function every weight matrix goes through
    as it is cast (the controls round them to fewer bits).  ``faults``: the
    module's docstring.  ``router_input``: a function the CHOICE's inputs
    (the queries and the pooled keys, nothing else) go through — this
    family's router is the choice of blocks.  ``state``:
    :func:`lightning_attention`'s."""
    arch = arch or arch_of(params)
    c = arch["branch"]
    with jax.default_matmul_precision("highest"):
        b, s = tokens.shape
        if weights is None:
            x = jnp.asarray(params["embed"][tokens], jnp.float32)
        else:
            x = _f32(params["embed"], weights)[tokens]
        x = x * arch["scale_emb"]
        n_layers = sum(1 for k in params if k.startswith("layer"))
        for l in range(n_layers):
            p = params[f"layer{l}"]
            h = _rms_norm(x, _f32(p["norm1"]), arch["eps"])
            if "linear" in p:
                mixed = lightning_attention(
                    h, p["linear"], arch["first_layer"] + l, arch, weights,
                    faults, state)
            else:
                mixed = sparse_attention(h, p["attn"], arch, weights, faults,
                                         router_input)
            x = x + c * mixed
            h2 = _rms_norm(x, _f32(p["norm2"]), arch["eps"]).reshape(
                b * s, -1)
            wgu, wd = (_f32(p["dense_gate_up"], weights),
                       _f32(p["dense_down"], weights))
            y = _by_rows(lambda rows: _gated(rows, wgu, wd), h2)
            x = x + c * y.reshape(b, s, -1)
        return (_rms_norm(x, _f32(params["norm_f"]), arch["eps"])
                / arch["head_divisor"])


def forward(params, tokens):
    """tokens [B, S] int32 -> logits [B, S, vocab] float32 (the toy and
    the tests)."""
    return _head(hidden(params, tokens), params["lm_head"])


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
