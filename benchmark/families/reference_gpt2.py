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

from benchmark.lib.agreement import tokens_agree


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


def served_tokens_agree(params, prompts, served, rtol: float,
                        program_logits=None, logit_rms_limit=None) -> dict:
    return tokens_agree(forward, params, prompts, served, rtol,
                        program_logits, logit_rms_limit)
