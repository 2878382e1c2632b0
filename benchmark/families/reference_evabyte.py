"""The plain reference of the family ``evabyte``: a dense byte-level
decoder whose every attention layer reads the exact keys of the query's own
aligned window beside one learned summary a chunk of every window before
it.  float32, highest matmul precision, no kernel, no cache, no page: the
summaries of every chunk are computed from the whole sequence's rotated
keys and the two sums of the estimator written out.  It reads the program's
parameter tree (bf16 values, cast to float32 a matrix at a time) and
nothing else of the program.

Residual stream ``x_t`` in f32; ``rms(x; g) = x / sqrt(mean(x^2) + eps) *
(1 + g)``.  For layer ``l``, with ``W`` the window, ``C`` the chunk, ``s =
D^-1/2`` and, a head, the learned ``phi``, ``mu`` [D]:

  1. ``h = rms(x; norm1)``; ``q, k, v = h W_qkv`` as H heads of D;
     rotate-half RoPE on ``q_t`` and ``k_t`` at the true position ``t``
  2. a chunk ``c`` = positions ``[C c, C c + C)``:
     ``a_m = softmax over m in c of (k_m . phi)``,
     ``k~_c = sum_m a_m k_m + mu``, ``v~_c = sum_m a_m v_m``
  3. ``w = t // W``; ``E_t = {m : W w <= m <= t}``; ``C_t = {c : c < (W /
     C) w}``;
     ``o_t = [sum_E e^{s q_t.k_m} v_m + sum_C e^{s q_t.k~_c} v~_c] /
     [sum_E e^{s q_t.k_m} + sum_C e^{s q_t.k~_c}]``
  4. ``x += o W_out``; ``h2 = rms(x; norm2)``;
     ``x += (silu(h2 W_gate) * h2 W_up) W_down``
  5. after the last layer ``rms(x; norm_f)`` and the untied next-byte head.

(The exponentials share one subtracted maximum a query, which changes
neither sum's ratio.)  The sizes the tree does not show (heads, window,
chunk, theta, eps) come from the configuration file beside the benchmark,
or, for a tree of the toy's width, from the family's ``TOY``.

A prompt at a time and a WINDOW at a time, a sublayer a program (the
queries of attention in blocks of ``ROWS``): what stays between windows is
the residual rows and the summaries, and only the rows the comparison
reads are normed and kept at the end, so the 16,449 positions of the cell's
longest sample fit in the 1.8e9 B that 14.3e9 B of weights and pool leave
(a layer of the whole sample as one program asked for 2.36e9 B).
``faults`` (the builder's controls, run from a scratch script) names
departures no run of the benchmark asks for.
"""

from __future__ import annotations

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

# the comparison every routed family's reference shares, and the controls'
# rounding of a weight matrix
from benchmark.families.reference_smallthinker import (  # noqa: F401
    compare, rounded_to)

_HERE = os.path.dirname(os.path.abspath(__file__))
CONFIG_FILE = os.path.join(_HERE, "..", "configs", "evabyte-6.5b.json")
ROWS = 256              # queries of attention a block


def arch_of_config(cfg: dict) -> dict:
    return {"d_model": cfg["hidden_size"],
            "heads": cfg["num_attention_heads"],
            "head_dim": cfg["hidden_size"] // cfg["num_attention_heads"],
            "window": cfg["window_size"], "chunk": cfg["chunk_size"],
            "theta": float(cfg["rope_theta"]), "eps": cfg["rms_norm_eps"]}


def arch_of_model_kwargs(kw: dict) -> dict:
    return {"d_model": kw["d_model"], "heads": kw["num_heads"],
            "head_dim": kw["head_dim"], "window": kw["summary_window"],
            "chunk": kw["summary_chunk"], "theta": float(kw["rope_theta"]),
            "eps": kw["rms_eps"]}


def arch_of(params) -> dict:
    """The sizes that go with this parameter tree: the configuration's,
    or the toy's, by the tree's hidden size."""
    from benchmark.families import evabyte
    with open(CONFIG_FILE) as f:
        cfg = json.load(f)
    known = [arch_of_config(cfg), arch_of_model_kwargs(
        dict(cfg["build_model"]["kwargs"],
             **evabyte.TOY["serve"]["model_kwargs"]))]
    d = params["embed"].shape[1]
    for arch in known:
        if arch["d_model"] == d:
            return arch
    raise ValueError(f"no sizes known for a tree of hidden size {d} (known: "
                     f"{[a['d_model'] for a in known]})")


def _f32(a, weights=None):
    """``a`` in float32; a matrix through ``weights`` where given (the
    controls round every matrix to fewer bits)."""
    a = jnp.asarray(a, jnp.float32)
    return weights(a) if weights is not None and a.ndim >= 2 else a


def _rms(x, g, eps):
    return (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
            * (1.0 + _f32(g)))


def _rope(x, theta, first):
    """x [S, H, D] at positions first..first+S-1, rotate-half pairing."""
    s, d = x.shape[0], x.shape[-1]
    inv_freq = theta ** (-np.arange(0, d, 2, dtype=np.float32) / d)
    angle = (first + jnp.arange(s)).astype(jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(angle)[:, None], jnp.sin(angle)[:, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _summaries(k, v, phi, mu, chunk):
    """k, v [S, H, D] (S whole chunks) -> k~, v~ [S / chunk, H, D]."""
    s, h, d = k.shape
    kc, vc = (a.reshape(s // chunk, chunk, h, d) for a in (k, v))
    a = jax.nn.softmax(jnp.einsum("cmhd,hd->cmh", kc, phi), axis=1)
    return (jnp.einsum("cmh,cmhd->chd", a, kc) + mu,
            jnp.einsum("cmh,cmhd->chd", a, vc))


def _attention(q, k, v, ks, vs, seen, scale):
    """One window: q, k, v [W, H, D] (the window's own tokens, causal
    among themselves), ks, vs [C, H, D] the summaries of the whole
    sequence's chunks of which the first ``seen`` (traced) are the closed
    windows'; the two sums of the estimator written out, the queries a
    block of ``ROWS`` at a time -> o [W, H * D]."""
    w, h, d = q.shape
    may = (jnp.arange(ks.shape[0]) < seen)[None, None, :]
    out = []
    for start in range(0, w, min(ROWS, w)):
        end = start + min(ROWS, w)
        i = jnp.arange(start, end)[:, None]
        j = jnp.arange(end)[None, :]
        qb = q[start:end]
        exact = jnp.where((j <= i)[None],
                          jnp.einsum("qhd,khd->hqk", qb, k[:end]) * scale,
                          -jnp.inf)
        summ = jnp.where(may, jnp.einsum("qhd,chd->hqc", qb, ks) * scale,
                         -jnp.inf)
        top = jnp.maximum(exact.max(-1), summ.max(-1))
        e_exact = jnp.exp(exact - top[..., None])
        e_summ = jnp.exp(summ - top[..., None])
        num = (jnp.einsum("hqk,khd->qhd", e_exact, v[:end])
               + jnp.einsum("hqc,chd->qhd", e_summ, vs))
        den = e_exact.sum(-1) + e_summ.sum(-1)                   # [H, Q]
        out.append(num / den.T[:, :, None])
    return jnp.concatenate(out, 0).reshape(w, h * d)


@functools.partial(jax.jit, donate_argnums=(2, 3),
                   static_argnames=("arch", "weights", "faults"))
def _mix_window(x, p, ks, vs, w, *, arch, weights=None, faults=()):
    """The attention sublayer on window ``w`` (traced) of one sequence: x
    [W, d] -> (x + attention, ks, vs with this window's summaries
    written)."""
    arch = dict(arch)
    with jax.default_matmul_precision("highest"):
        n, _ = x.shape
        h, d, a = arch["heads"], arch["head_dim"], p["attn"]
        per_window = n // arch["chunk"]
        qkv = _rms(x, p["norm1"], arch["eps"]) @ _f32(a["qkv"], weights)
        q, k, v = (qkv[:, i * h * d:(i + 1) * h * d].reshape(n, h, d)
                   for i in range(3))
        q = _rope(q, arch["theta"], w * n)
        k = _rope(k, arch["theta"], w * n)
        seen = w * per_window
        if "no_summaries" in faults:
            seen = 0
        if "stale_summaries" in faults:
            seen = (w - 1) * per_window
        o = _attention(q, k, v, ks, vs, seen, d ** -0.5)
        phi = _f32(a["summary_phi"], weights)
        if "mean_pooling" in faults:
            phi = jnp.zeros_like(phi)
        ks_w, vs_w = _summaries(k, v, phi, _f32(a["summary_mu"], weights),
                                arch["chunk"])
        at = (w * per_window, 0, 0)
        return (x + o @ _f32(a["out"], weights),
                jax.lax.dynamic_update_slice(ks, ks_w, at),
                jax.lax.dynamic_update_slice(vs, vs_w, at))


@functools.partial(jax.jit, static_argnames=("eps", "weights"))
def _mlp(x, p, *, eps, weights=None):
    """The MLP sublayer on one window's rows."""
    with jax.default_matmul_precision("highest"):
        f = p["dense_down"].shape[0]
        gu = _rms(x, p["norm2"], eps) @ _f32(p["dense_gate_up"], weights)
        return x + (jax.nn.silu(gu[:, :f]) * gu[:, f:]) @ _f32(
            p["dense_down"], weights)


def hidden(params, tokens, arch=None, weights=None, faults=(), first=0):
    """tokens [S] int32 (one sequence) -> the final normed hidden rows
    ``first``.. [S - first, d] float32 (the teacher-forced comparison
    reads a sample's last rows alone, and rows beside the pool are what
    the chip has least of).  The sequence is padded to whole windows; a
    causal layer's real rows do not see the padding.  ``weights``: a
    function every weight matrix goes through as it is cast; ``faults``:
    names of ``no_summaries`` (window-only attention), ``stale_summaries``
    (the newest closed window's are missing: one window stale),
    ``mean_pooling`` (``phi`` = 0)."""
    arch = arch or arch_of(params)
    window, s = arch["window"], len(tokens)
    padded = jnp.pad(jnp.asarray(tokens, jnp.int32), (0, -s % window))
    embed = _f32(params["embed"], weights)
    xs = [embed[padded[i:i + window]] for i in range(0, len(padded), window)]
    frozen = tuple(sorted(arch.items()))
    for l in range(sum(1 for k in params if k.startswith("layer"))):
        p = params[f"layer{l}"]
        ks = jnp.zeros((len(padded) // arch["chunk"], arch["heads"],
                        arch["head_dim"]), jnp.float32)
        vs = jnp.zeros_like(ks)
        for w in range(len(xs)):
            xw, ks, vs = _mix_window(xs[w], p, ks, vs, jnp.int32(w),
                                     arch=frozen, weights=weights,
                                     faults=tuple(faults))
            xs[w] = _mlp(xw, p, eps=arch["eps"], weights=weights)
    w0 = first // window
    rows = jnp.concatenate([_rms(xw, params["norm_f"], arch["eps"])
                            for xw in xs[w0:]])
    return rows[first - w0 * window:s - w0 * window]


@functools.partial(jax.jit, static_argnames=("weights",))
def _head(rows, head, weights=None):
    with jax.default_matmul_precision("highest"):
        return rows @ _f32(head, weights)


def forward(params, tokens):
    """tokens [B, S] int32 -> logits [B, S, vocab] float32, a sequence at
    a time (the toy and the tests)."""
    return jnp.stack([_head(hidden(params, t), params["lm_head"])
                      for t in tokens])


def rows_that_chose(params, prompts, served, weights=None, faults=(),
                    **_) -> list:
    """Teacher-forced, as ``lib/agreement.rows_that_chose``: for each
    (prompt, served tokens) pair the logits at the positions that chose
    each served token, a [tokens, vocab] array a pair; a pair at a time, at
    its own length."""
    arch = arch_of(params)
    out = []
    for p, t in zip(prompts, served):
        x = hidden(params, np.concatenate([p, t[:-1]]).astype(np.int32),
                   arch, weights, faults, first=len(p) - 1)
        out.append(np.asarray(_head(x, params["lm_head"], weights)))
    return out


def served_tokens_agree(params, prompts, served, rtol: float,
                        program_logits=None, logit_rms_limit=None) -> dict:
    return compare(rows_that_chose(params, prompts, served), served, rtol,
                   program_logits, logit_rms_limit)


def greedy_tokens(params, prompts, new_tokens: int, weights=None) -> list:
    """What a system that computed this reference (its weight matrices
    through ``weights``) would serve: each prompt's next tokens by greedy
    choice, no cache, the whole sequence again for every token."""
    arch = arch_of(params)
    out = []
    for p in prompts:
        seq = list(np.asarray(p, np.int32))
        for _ in range(new_tokens):
            x = hidden(params, np.asarray(seq, np.int32), arch, weights,
                       first=len(seq) - 1)
            seq.append(int(jnp.argmax(_head(x, params["lm_head"],
                                            weights))))
        out.append(seq[len(p):])
    return out
