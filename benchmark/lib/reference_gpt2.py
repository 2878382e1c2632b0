"""The plain reference of the GPT-2 block as ``dtf_tpu.models.transformer``
builds it: float32, highest matmul precision, no kernel, no cache, no
batching tricks.  It reads the program's parameter tree and nothing else
of the program.

Departures from the published GPT-2 (they are the program's, noted in the
configuration file): no bias on the attention output projection and on
the second MLP projection; the output head is untied from the embedding
and has a bias; LayerNorm epsilon 1e-6 (flax's default); GELU in its tanh
form.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _layer_norm(x, p, eps=1e-6):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        np.sqrt(2.0 / np.pi) * (x + 0.044715 * x ** 3)))


def forward(params, tokens):
    """tokens [B, S] int32 -> logits [B, S, vocab] float32."""
    with jax.default_matmul_precision("highest"):
        f32 = lambda t: jax.tree_util.tree_map(
            lambda a: jnp.asarray(a, jnp.float32), t)
        b, s = tokens.shape
        x = f32(params["embed"]["embedding"])[tokens]
        x = x + f32(params["pos_embed"])[:s][None]
        causal = jnp.tril(jnp.ones((s, s), bool))
        n_layers = sum(1 for k in params if k.startswith("block"))
        for i in range(n_layers):
            p = f32(params[f"block{i}"])
            h = _layer_norm(x, p["ln1"])
            qkv = jnp.einsum("bsd,dthe->bsthe", h, p["attn"]["qkv"]["kernel"])
            qkv = qkv + p["attn"]["qkv"]["bias"]
            q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
            scores = jnp.einsum("bqhe,bkhe->bhqk", q, k) / np.sqrt(
                q.shape[-1])
            scores = jnp.where(causal[None, None], scores, -jnp.inf)
            o = jnp.einsum("bhqk,bkhe->bqhe", jax.nn.softmax(scores, -1), v)
            x = x + o.reshape(b, s, -1) @ p["attn"]["out"]["kernel"]
            h = _layer_norm(x, p["ln2"])
            h = _gelu_tanh(h @ p["fc1"]["kernel"] + p["fc1"]["bias"])
            x = x + h @ p["fc2"]["kernel"]
        x = _layer_norm(x, f32(params["ln_f"]))
        head = f32(params["lm_head"])
        return x @ head["kernel"] + head["bias"]


def served_tokens_agree(params, prompts, served, rtol: float) -> dict:
    """Teacher-force each (prompt, served tokens) pair through the
    reference and hold every served token to it: the token served is the
    reference's choice, or tied with it within ``2 * rtol`` of the logit
    scale (the system computes in bf16; ``rtol`` is what that may move a
    logit by, as ``chip_smoke.py`` sets it).  A wrong page, mask or
    position moves logits by their whole spread, far outside a tie.

    Sequences are padded to one length and run as one batch: one program.
    """
    n = len(prompts)
    total = max(len(p) + len(t) for p, t in zip(prompts, served))
    batch = np.zeros((n, total), np.int32)
    for r, (p, t) in enumerate(zip(prompts, served)):
        batch[r, :len(p)] = p
        batch[r, len(p):len(p) + len(t)] = t
    logits = np.asarray(jax.jit(forward)(params, jnp.asarray(batch)))
    scale = float(np.abs(logits).max())
    worst, compared, identical = 0.0, 0, 0
    for r, (p, t) in enumerate(zip(prompts, served)):
        # the logits at position len(p)-1+j chose served token j
        rows = logits[r, len(p) - 1:len(p) - 1 + len(t)]
        chosen = rows[np.arange(len(t)), np.asarray(t)]
        gap = rows.max(-1) - chosen
        worst = max(worst, float(gap.max()))
        compared += len(t)
        identical += int((gap == 0).sum())
    finite = bool(np.isfinite(logits).all())
    return {"ok": finite and worst <= 2 * rtol * scale,
            "tokens_compared": compared, "greedy_identical": identical,
            "worst_gap": worst, "logit_scale": scale,
            "allowed_gap": 2 * rtol * scale}
