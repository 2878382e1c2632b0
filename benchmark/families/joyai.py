"""The family ``joyai``: the decoder ``dtf_tpu.models.routed_decoder`` builds
with LATENT attention (one cached row of ``kv_lora_rank +
qk_rope_head_dim`` values a token a layer, absorbed at decode), a leading
dense gated-SiLU layer, then layers of a shared expert beside
top-k-of-E gated-SiLU experts chosen by sigmoid scores plus a correction
bias — at the sizes a configuration's ``hidden_size``,
``num_attention_heads``, ``q_lora_rank``, ``kv_lora_rank``, ``qk_*`` and
``v_head_dim``, ``n_routed_experts``, ``num_experts_per_tok``,
``moe_intermediate_size``, ``intermediate_size`` and
``first_k_dense_replace`` keys give.  The interface is in
``benchmark/families/__init__.py``; the family is served, not trained, so
``train_flops_per_sample`` is what ``families.load`` requires and no cell
reads yet.
"""

from __future__ import annotations

from benchmark.lib import costs

LANES = 128     # the cache row is stored in whole lane tiles


def row_lanes(cfg: dict) -> int:
    """Values a cached token occupies a layer, as stored (576 -> 640)."""
    return -(-(cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) // LANES) * LANES


def active_matmul_params(cfg: dict) -> int:
    """Parameters that meet one token in a matrix product: the five
    attention projections of every layer, the dense layers' MLP, and in
    an expert layer the router, the shared expert and the chosen experts;
    the untied head."""
    d, hq = cfg["hidden_size"], cfg["num_attention_heads"]
    rq, r = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    attn = (d * rq + rq * hq * (dn + dr) + d * (r + dr)
            + r * hq * (dn + dv) + hq * dv * d)
    dense = cfg["first_k_dense_replace"]
    expert = 3 * d * cfg["moe_intermediate_size"]
    routed = (d * cfg["n_routed_experts"]
              + (cfg["n_shared_experts"] + cfg["num_experts_per_tok"])
              * expert)
    layers = cfg["num_hidden_layers"]
    return (layers * attn + dense * 3 * d * cfg["intermediate_size"]
            + (layers - dense) * routed + d * cfg["vocab_size"])


def train_flops_per_sample(cfg: dict, traffic: dict) -> float:
    """One sequence of ``seq_len`` tokens, forward + backward: 6 FLOPs a
    matmul parameter a token activates, plus causal attention expanded
    (a token sees (S + 1) / 2 positions on average; q.k over nope + rope,
    p.v over v, every head)."""
    s = traffic["seq_len"]
    per_pos = 2 * cfg["num_attention_heads"] * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"])
    attn = cfg["num_hidden_layers"] * per_pos * (s + 1) / 2
    return 3.0 * (2.0 * active_matmul_params(cfg) + attn) * s


def expert_matmuls(cfg: dict, span: dict):
    """(FLOPs, bytes) of the GROUPED expert matmuls of one compiled call,
    from what the program counted on its span: every (token, expert) pair
    meets gate, up and down once; every expert some pair touched is read
    once (bf16).  The shared expert's and the dense layer's matmuls are
    XLA's and not the grouped kernel's: not counted.  None where the span
    carries no counts."""
    if "assignments" not in span:
        return None
    per_expert = 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]
    return (2.0 * span["assignments"] * per_expert,
            2.0 * span["experts_touched"] * per_expert)


def latent_attention_reads(cfg: dict, span: dict):
    """(FLOPs, bytes) of the paged attention of one compiled call over the
    latent cache, absorbed: every cached row a row's queries attend is
    read once (``row_lanes`` bf16 values as stored: 1,280 bytes for the 576
    that carry something), and meets every query head of every query of
    the call in a score over ``kv_lora_rank + qk_rope_head_dim`` values and
    a value sum over ``kv_lora_rank``.  The DECODE STEPS alone since PR 53 (a
    chunk attends expanded).  None where the span carries no count."""
    if "latent_tokens_read" not in span:
        return None
    tokens = span["latent_tokens_read"]
    q_len = span.get("tokens", 1)       # a decode step: one query a row
    # of a chunk's own q_len keys a query sees half on average
    seen = tokens - (q_len - 1) / 2 * cfg["num_hidden_layers"]
    per_pair = 2.0 * cfg["num_attention_heads"] * (
        2 * cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
    return per_pair * seen * q_len, 2.0 * tokens * row_lanes(cfg)


def model_flops(cfg: dict, call: dict):
    """FLOPs the configuration's mathematics needs for one compiled call of
    the serving engine (``readers/span_mfu.py`` says what ``call`` holds):
    2 a matmul parameter a REAL token activates (the experts it is sent to
    and the shared one), the head at the one position a chunk samples and
    at one a decoding row, and attention.  A decode step's query meets a
    cached latent row over ITS width — a score over ``kv_lora_rank +
    qk_rope_head_dim``, a value sum over ``kv_lora_rank``, every head: no
    form of a step is cheaper, since expanding a row's cached keys again
    costs more — over ``latent_tokens_read`` less the one position the
    program counts for each idle row.  A chunk's queries meet a key
    expanded once (the token's own projection, among its parameters): nope
    + rope + v a head a key the causal rule shows, as
    ``train_flops_per_sample`` counts it.  None for a step whose rows
    nobody counted."""
    head = cfg["hidden_size"] * cfg["vocab_size"]
    body = active_matmul_params(cfg) - head
    hq, layers = cfg["num_attention_heads"], cfg["num_hidden_layers"]
    if "tokens" in call:
        n = call["real_tokens"]
        per_key = 2 * hq * (cfg["qk_nope_head_dim"]
                            + cfg["qk_rope_head_dim"] + cfg["v_head_dim"])
        return (2.0 * body * n + 2.0 * head + layers * per_key
                * costs.causal_keys(call["start"], n))
    if not call.get("rows"):
        return None
    per_key = 2 * hq * (2 * cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
    keys = costs.step_keys(call, ("latent_tokens_read",), layers)
    return 2.0 * (body + head) * call["rows"] + per_key * keys


SPAN_COSTS = {"expert_matmuls": expert_matmuls,
              "latent_attention_reads": latent_attention_reads,
              "model_flops": model_flops}

# rehearse.py's sizes: the shape of the thing — one dense layer then three
# routed ones, 4 heads of nope/rope/v 16/8/16 over latents of rank 32 (q)
# and 24 (kv), 16 experts of which a token takes 4 beside a shared one, a
# score bias that moves the choice
_TOY_MODEL = {"num_layers": 4, "d_model": 64, "num_heads": 4,
              "q_lora_rank": 32, "kv_lora_rank": 24,
              "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
              "v_head_dim": 16, "num_dense_layers": 1, "dense_width": 96,
              "num_experts": 16, "experts_per_token": 4, "expert_width": 32,
              "shared_expert_width": 32, "router_bias_stddev": 0.05,
              "max_seq_len": 256}
TOY = {
    "serve": {"model_kwargs": _TOY_MODEL,
              "vocab_size": 512,
              "engine": {"max_batch": 4, "max_seq_len": 256,
                         "kv_page_size": 8, "kv_pool_pages": 129,
                         "prefill_chunk": 32},
              # four layers of width 64: the toy's own limit (the program,
              # bf16 matmuls on an f32 stream, reads 0.0030-0.0056 over
              # seeds 11-16, the reference with every matrix at 8 bits
              # 0.0122-0.0135 over seeds 11-13: control.py and
              # control_rows.py --toy, CPU, PR 32)
              "agreement": {"prompt_lens": [16, 48, 96, 160],
                            "logit_rms_limit": 0.008},
              "traffic": {"ramp_s": 1, "drain_s": 10, "clients": 4,
                          "prepare_per_s": 200.0,
                          "prepare_block_per_s": 200.0,
                          "prompt_len": {"median": 48, "sigma": 0.5,
                                         "min": 16, "max": 160,
                                         "snap_to": [16, 48, 96, 160]},
                          "output_len": {"median": 6, "sigma": 0.4,
                                         "min": 3, "max": 12}}},
}
