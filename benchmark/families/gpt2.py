"""The family ``gpt2``: the GPT-2 block as ``dtf_tpu.models.transformer``
builds it (learned positions, LayerNorm, GELU MLP, full multi-head
attention), at the sizes a configuration's ``n_layer``, ``n_embd``,
``n_head``, ``n_inner`` and ``vocab_size`` give.  The interface is in
``benchmark/families/__init__.py``.

The counts are what the mathematics requires: forward plus backward (the
backward as twice the forward), causal attention once, no recomputation.
"""

from __future__ import annotations

from benchmark.lib import costs


def gpt_matmul_params(cfg: dict) -> int:
    """Parameters that take part in a matrix multiplication per token:
    qkv, out, fc1, fc2 of every block and the output head (embedding
    look-ups and LayerNorms are not matmuls)."""
    d, ff = cfg["n_embd"], cfg["n_inner"]
    per_layer = 3 * d * d + d * d + d * ff + ff * d
    return cfg["n_layer"] * per_layer + d * cfg["vocab_size"]


def gpt_forward_flops_per_token(cfg: dict, seq_len: int) -> float:
    """2 FLOPs per matmul parameter, plus causal attention: each token
    attends to (seq_len + 1) / 2 positions on average, 2 matmuls (scores,
    values) of 2·d FLOPs per position per layer."""
    attn = cfg["n_layer"] * 2 * 2 * cfg["n_embd"] * (seq_len + 1) / 2
    return 2.0 * gpt_matmul_params(cfg) + attn


def train_flops_per_sample(cfg: dict, traffic: dict) -> float:
    """One sample = one sequence of ``seq_len`` tokens, forward + backward."""
    s = traffic["seq_len"]
    return 3.0 * gpt_forward_flops_per_token(cfg, s) * s


def flash_train_step(cfg: dict, traffic: dict, chips: int) -> tuple:
    """(FLOPs, bytes) of every flash forward and backward call one chip
    makes in one train step of the LM: one of each per layer, on that
    chip's share of the batch."""
    heads = cfg["n_head"]
    head_dim = cfg["n_embd"] // heads
    per_chip = traffic["batch_size"] // chips
    f_f, b_f = costs.flash_fwd(per_chip, heads, traffic["seq_len"], head_dim)
    f_b, b_b = costs.flash_bwd(per_chip, heads, traffic["seq_len"], head_dim)
    n = cfg["n_layer"]
    return n * (f_f + f_b), n * (b_f + b_b)


def paged_decode_context(cfg: dict, context_tokens: float) -> tuple:
    """(FLOPs, bytes) of the paged decode attention of every layer over
    ``context_tokens`` cached positions (bf16 cache)."""
    heads = cfg["n_head"]
    flops, nbytes = costs.paged_decode(context_tokens, heads,
                                       cfg["n_embd"] // heads)
    return cfg["n_layer"] * flops, cfg["n_layer"] * nbytes


def model_flops(cfg: dict, call: dict):
    """FLOPs the configuration's mathematics needs for one compiled call of
    the serving engine (``readers/span_mfu.py`` says what ``call`` holds):
    2 a matmul parameter of the blocks a REAL token, the head at the one
    position a chunk samples and at one a decoding row, attention as 4 x
    width a layer a key a query must see.  A chunk's keys are the causal
    rule's; a decode step's the lower bound ``context_tokens`` (none: no
    attention counted).  None for a step whose rows nobody counted."""
    d = cfg["n_embd"]
    head = d * cfg["vocab_size"]
    body = gpt_matmul_params(cfg) - head
    per_key = cfg["n_layer"] * 2 * 2 * d
    if "tokens" in call:
        n = call["real_tokens"]
        return (2.0 * body * n + 2.0 * head
                + per_key * costs.causal_keys(call["start"], n))
    if not call.get("rows"):
        return None
    return (2.0 * (body + head) * call["rows"]
            + per_key * call.get("context_tokens", 0.0))


STEP_COSTS = {"flash_train_step": flash_train_step}
COUNTED_COSTS = {"paged_decode_context": paged_decode_context}
SPAN_COSTS = {"model_flops": model_flops}

# rehearse.py's sizes: the same block, small enough for the CPU
_TOY_MODEL = {"num_layers": 2, "d_model": 128, "num_heads": 1, "d_ff": 256}
TOY = {
    "train": {"model_kwargs": dict(_TOY_MODEL, max_seq_len=128),
              "batch_size": 8, "seq_len": 128, "log_steps": 2},
    "serve": {"model_kwargs": dict(_TOY_MODEL, max_seq_len=256),
              "vocab_size": 512,
              "engine": {"max_batch": 4, "max_seq_len": 256,
                         "kv_pool_pages": 65, "prefill_chunk": 64},
              # two layers of width 128 round less than 24 of 2048: the
              # toy's own limit (bf16 reads 0.0076-0.0090, 8-bit weights
              # 0.0158-0.0201: CPU, seeds 11-16)
              "agreement": {"prompt_lens": [16, 48, 96, 160],
                            "logit_rms_limit": 0.012},
              "traffic": {"ramp_s": 1, "drain_s": 10, "rate_per_s": 3.0,
                          "clients": 4, "prepare_per_s": 200.0,
                          "prompt_len": {"median": 48, "sigma": 0.5,
                                         "min": 16, "max": 160,
                                         "snap_to": [16, 48, 96, 160]},
                          "output_len": {"median": 6, "sigma": 0.4,
                                         "min": 3, "max": 12}}},
}
