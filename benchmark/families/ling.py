"""The family ``ling``: the decoder ``dtf_tpu.models.routed_decoder`` builds
with a MIXER KIND A LAYER — delta-rule linear attention with a decay a
channel, whose state is a matrix a head and rides the page table as one
entry a (large) page, or LATENT attention (one cached row of ``kv_lora_rank
+ qk_rope_head_dim`` values a token, absorbed at decode) with a direct
query projection, a norm a query head and a gate a head — two leading
dense gated-SiLU layers, then layers of a shared expert beside
top-k-of-E gated-SiLU experts chosen by sigmoid scores plus a bias under a
GROUP LIMIT, of which the device HOLDS ``num_experts`` of the
``published`` count, and an untied head onto the vocabulary rows held; at
the sizes a configuration's ``hidden_size``, ``num_attention_heads``,
``head_dim``, ``layer_types``, ``kv_lora_rank``, ``qk_*`` and
``v_head_dim``, ``short_conv_kernel_size``, ``num_experts``,
``num_experts_per_tok``, ``moe_intermediate_size``, ``intermediate_size``
and ``first_k_dense_replace`` keys give.  The interface is in
``benchmark/families/__init__.py``; the family is served, not trained, so
``train_flops_per_sample`` is what ``families.load`` requires and no cell
reads yet.
"""

from __future__ import annotations

from benchmark.lib import costs

LANES = 128     # a cache row is stored in whole lane tiles


def layer_types(cfg: dict) -> list:
    """The kinds of the layers the configuration runs (``linear`` |
    ``full``): the first ``num_hidden_layers`` of the published order."""
    return list(cfg["layer_types"][:cfg["num_hidden_layers"]])


def row_lanes(cfg: dict) -> int:
    """Values a cached token occupies a latent layer, as stored
    (576 -> 640)."""
    return -(-(cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) // LANES) * LANES


def latent_bytes_per_token(cfg: dict) -> int:
    """bf16 bytes a cached token occupies over the latent layers."""
    return layer_types(cfg).count("full") * row_lanes(cfg) * 2


def matrix_bytes_per_page(cfg: dict) -> int:
    """bf16 bytes of ONE linear layer's matrices in one page's entry: a
    ``head_dim x head_dim`` matrix a head, as stored."""
    return cfg["num_attention_heads"] * cfg["head_dim"] ** 2 * 2


def state_bytes_per_page(cfg: dict) -> int:
    """bf16 bytes of the state entries of one page over the linear layers:
    the matrices, and the last ``short_conv_kernel_size - 1`` inputs of the
    three filters."""
    width = cfg["num_attention_heads"] * cfg["head_dim"]
    return layer_types(cfg).count("linear") * (
        matrix_bytes_per_page(cfg)
        + (cfg["short_conv_kernel_size"] - 1) * 3 * width * 2)


def active_matmul_params(cfg: dict) -> int:
    """Parameters that meet one token in a matrix product: the mixer's
    projections of every layer, the dense layers' MLP, in an expert layer
    the router (over the published count), the shared expert and the
    chosen experts; the untied head onto the rows held."""
    d, hq, dh = (cfg["hidden_size"], cfg["num_attention_heads"],
                 cfg["head_dim"])
    n = hq * dh
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    r = cfg["kv_lora_rank"]
    kinds = layer_types(cfg)
    linear = 5 * d * n + d * hq + n * d
    latent = (d * hq * (dn + dr) + d * (r + dr) + r * hq * (dn + dv)
              + d * hq + hq * dv * d)
    dense = cfg["first_k_dense_replace"]
    expert = 3 * d * cfg["moe_intermediate_size"]
    routed = (d * cfg["published"]["num_experts"]
              + (1 + cfg["num_experts_per_tok"]) * expert)
    return (kinds.count("linear") * linear + kinds.count("full") * latent
            + dense * 3 * d * cfg["intermediate_size"]
            + (len(kinds) - dense) * routed + d * cfg["vocab_size"])


def train_flops_per_sample(cfg: dict, traffic: dict) -> float:
    """One sequence of ``seq_len`` tokens, forward + backward: 6 FLOPs a
    matmul parameter a token activates, the linear layers' state (three
    products of ``head_dim x head_dim`` a head a token), and causal latent
    attention expanded (a token sees (S + 1) / 2 positions on average)."""
    s = traffic["seq_len"]
    kinds = layer_types(cfg)
    hq, dh = cfg["num_attention_heads"], cfg["head_dim"]
    state = kinds.count("linear") * 3 * 2 * hq * dh * dh
    per_pos = 2 * hq * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
                        + cfg["v_head_dim"])
    attn = kinds.count("full") * per_pos * (s + 1) / 2
    return 3.0 * (2.0 * active_matmul_params(cfg) + state + attn) * s


def expert_matmuls(cfg: dict, span: dict):
    """(FLOPs, bytes) of the GROUPED expert matmuls of one compiled call,
    from what the program counted on its span: every (token, expert) pair
    COMPUTED HERE (``assignments``: the pairs whose expert this device
    holds) meets gate, up and down once; every held expert some pair
    touched is read once (bf16).  The shared expert's and the dense
    layers' matmuls are XLA's: not counted.  None where the span carries
    no counts."""
    if "assignments" not in span:
        return None
    per_expert = 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]
    return (2.0 * span["assignments"] * per_expert,
            2.0 * span["experts_touched"] * per_expert)


def latent_attention_reads(cfg: dict, span: dict):
    """(FLOPs, bytes) of the paged attention of one compiled call over the
    latent cache, absorbed: every cached row the queries of the LATENT
    layers attend (``latent_tokens_read`` counts those layers only) is read
    once, ``row_lanes`` bf16 values as stored, and meets every query head
    of every query of the call in a score over ``kv_lora_rank +
    qk_rope_head_dim`` values and a value sum over ``kv_lora_rank``.  The
    DECODE STEPS alone since PR 53 (a chunk attends expanded).  None where
    the span carries no count."""
    if "latent_tokens_read" not in span:
        return None
    tokens = span["latent_tokens_read"]
    q_len = span.get("tokens", 1)       # a decode step: one query a row
    # of a chunk's own q_len keys a query sees half on average
    seen = tokens - (q_len - 1) / 2 * layer_types(cfg).count("full")
    per_pair = 2.0 * cfg["num_attention_heads"] * (
        2 * cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
    return per_pair * seen * q_len, 2.0 * tokens * row_lanes(cfg)


def linear_state_steps(cfg: dict, span: dict):
    """(FLOPs, bytes) of the state kernel of one decode step: for every
    (row, linear layer) whose entry went to a page of its own
    (``state_rows_advanced``, summed over the layers) the matrices are read
    once and written once AS STORED, and every head's matrix meets the
    token in three products (the decayed state against the key, the
    rank-one write, the new state against the query).  Idle rows, which
    the kernel also moves through the scratch page, are not counted: the
    share reads low, never high.  None where the span carries no count."""
    if "state_rows_advanced" not in span:
        return None
    rows = span["state_rows_advanced"]
    return (2.0 * 3 * cfg["num_attention_heads"] * cfg["head_dim"] ** 2
            * rows, 2.0 * matrix_bytes_per_page(cfg) * rows)


def model_flops(cfg: dict, call: dict):
    """FLOPs the configuration's mathematics needs for one compiled call of
    the serving engine (``readers/span_mfu.py`` says what ``call`` holds):
    2 a matmul parameter a REAL token meets outside the chosen experts
    (mixers, dense MLPs, the router over the published count, the shared
    expert), the head onto the rows held at the one position a chunk
    samples and at one a decoding row, and of a token's chosen experts the
    pairs COMPUTED HERE (``assignments``: the router's, not an
    implementation's; it runs over every row and padded position of the
    call, so the real tokens take their share of it; no count: none).  A
    linear layer's state: three products of ``head_dim x head_dim`` a head
    a real token.  The latent layer as ``joyai``'s: a decode step's query
    meets a cached row over its stored width (``latent_tokens_read`` less
    the one position counted for each idle row), a chunk's queries a key
    expanded once under the causal rule.  A chunk's real tokens are the
    program's own count (``linear_tokens`` over the linear layers) where
    the span carries it.  None for a step whose rows nobody counted."""
    d, hq, dh = (cfg["hidden_size"], cfg["num_attention_heads"],
                 cfg["head_dim"])
    kinds = layer_types(cfg)
    expert = 3 * d * cfg["moe_intermediate_size"]
    routed = len(kinds) - cfg["first_k_dense_replace"]
    head = d * cfg["vocab_size"]
    body = (active_matmul_params(cfg) - head
            - routed * cfg["num_experts_per_tok"] * expert)
    state = kinds.count("linear") * 3 * 2 * hq * dh * dh
    if "tokens" in call:
        n = call["real_tokens"]
        if "linear_tokens" in call:
            n = call["linear_tokens"] // kinds.count("linear")
        pairs = call.get("assignments", 0) * n / call["tokens"]
        per_key = 2 * hq * (cfg["qk_nope_head_dim"]
                            + cfg["qk_rope_head_dim"] + cfg["v_head_dim"])
        return ((2.0 * body + state) * n + 2.0 * head + 2.0 * expert * pairs
                + kinds.count("full") * per_key
                * costs.causal_keys(call["start"], n))
    if not call.get("rows"):
        return None
    rows = call["rows"]
    pairs = call.get("assignments", 0) * rows / call["slots"]
    per_key = 2 * hq * (2 * cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
    keys = costs.step_keys(call, ("latent_tokens_read",),
                           kinds.count("full"))
    return ((2.0 * (body + head) + state) * rows + 2.0 * expert * pairs
            + per_key * keys)


SPAN_COSTS = {"expert_matmuls": expert_matmuls,
              "latent_attention_reads": latent_attention_reads,
              "linear_state_steps": linear_state_steps,
              "model_flops": model_flops}

# rehearse.py's sizes: the shape of the thing — two dense and six routed
# layers in the order K K K K K M K K, 4 linear heads of 8 with four-tap
# filters, 4 latent heads of nope/rope/v 16/8/16 over a latent of rank 24
# with the head norm and the head gate, 16 experts in 4 groups of which 2
# are kept, top-4, the first 8 held, a bias that moves the choice
_TOY_MODEL = {"num_layers": 8, "d_model": 64, "num_heads": 4,
              "linear_heads": 4, "linear_head_dim": 8,
              "kv_lora_rank": 24, "qk_nope_head_dim": 16,
              "qk_rope_head_dim": 8, "v_head_dim": 16,
              "num_dense_layers": 2, "dense_width": 96, "num_experts": 16,
              "experts_per_token": 4, "expert_width": 32,
              "shared_expert_width": 32, "route_groups": 4,
              "route_groups_kept": 2, "experts_held": [0, 8],
              "router_bias_stddev": 0.05, "max_seq_len": 192}
TOY = {
    "serve": {"model_kwargs": _TOY_MODEL,
              "vocab_size": 512,
              "engine": {"max_batch": 4, "max_seq_len": 192,
                         "kv_page_size": 16, "kv_pool_pages": 65,
                         "prefill_chunk": 32},
              # eight layers of width 64: the toy's own limit (readings in
              # tests/benchmark_checks/test_ling.py's docstring).  97,
              # like the cell's 8,193: a length past ``max`` that no
              # request of the mix snaps to, three whole chunks and a final
              # one of ONE real token, so that the sample's first compared
              # position reads the state across a chunk boundary
              "agreement": {"prompt_lens": [16, 48, 96, 97],
                            "new_tokens": 24, "logit_rms_limit": 0.08},
              "traffic": {"ramp_s": 1, "drain_s": 10, "clients": 4,
                          "prepare_per_s": 200.0,
                          "prepare_block_per_s": 200.0,
                          "prompt_len": {"median": 48, "sigma": 0.5,
                                         "min": 16, "max": 96,
                                         "snap_to": [16, 48, 96, 97]},
                          "output_len": {"median": 6, "sigma": 0.4,
                                         "min": 3, "max": 12}}},
}
