"""The family ``lfm2``: the decoder ``dtf_tpu.models.routed_decoder`` builds
with a MIXER KIND A LAYER — a double-gated short convolution whose running
state (the filter's last inputs) rides the page table, or grouped-query
attention with per-head norms of q and k over pages whose row holds
``[k | v]`` of a 64-wide head — two leading dense gated-SiLU layers, then
layers of top-k-of-E gated-SiLU experts chosen by sigmoid scores plus a
bias, and a tied head; at the sizes a configuration's ``hidden_size``,
``num_attention_heads``, ``num_key_value_heads``, ``layer_types``,
``conv_L_cache``, ``num_experts``, ``num_experts_per_tok``,
``moe_intermediate_size``, ``intermediate_size`` and ``num_dense_layers``
keys give.  The interface is in ``benchmark/families/__init__.py``; the
family is served, not trained, so ``train_flops_per_sample`` is what
``families.load`` requires and no cell reads yet.
"""

from __future__ import annotations

from benchmark.lib import costs

LANES = 128     # a pool row is stored in whole lane tiles


def layer_types(cfg: dict) -> list:
    """The kinds of the layers the configuration runs: the first
    ``num_hidden_layers`` of the published order."""
    return list(cfg["layer_types"][:cfg["num_hidden_layers"]])


def head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def kv_row_lanes(cfg: dict) -> int:
    """Lanes ONE KV head of one token occupies a layer, as stored: its key
    and its value side by side in whole lane tiles (2 x 64 -> 128)."""
    return -(-2 * head_dim(cfg) // LANES) * LANES


def kv_bytes_per_token(cfg: dict) -> int:
    """bf16 bytes a cached token occupies over the attention layers."""
    return (layer_types(cfg).count("full_attention")
            * cfg["num_key_value_heads"] * kv_row_lanes(cfg) * 2)


def state_bytes_per_page(cfg: dict) -> int:
    """bf16 bytes of the state entries of one page over the convolution
    layers: the filter's last ``conv_L_cache - 1`` inputs a layer."""
    return (layer_types(cfg).count("conv") * (cfg["conv_L_cache"] - 1)
            * cfg["hidden_size"] * 2)


def active_matmul_params(cfg: dict) -> int:
    """Parameters that meet one token in a matrix product: the mixer's
    projections of every layer, the dense layers' MLP, in an expert layer
    the router and the chosen experts; the tied head."""
    d, dh = cfg["hidden_size"], head_dim(cfg)
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    kinds = layer_types(cfg)
    mixers = (kinds.count("conv") * (3 * d * d + d * d)
              + kinds.count("full_attention")
              * (d * (hq + 2 * hkv) * dh + hq * dh * d))
    dense = cfg["num_dense_layers"]
    routed = (d * cfg["num_experts"] + cfg["num_experts_per_tok"]
              * 3 * d * cfg["moe_intermediate_size"])
    return (mixers + dense * 3 * d * cfg["intermediate_size"]
            + (len(kinds) - dense) * routed + d * cfg["vocab_size"])


def train_flops_per_sample(cfg: dict, traffic: dict) -> float:
    """One sequence of ``seq_len`` tokens, forward + backward: 6 FLOPs a
    matmul parameter a token activates, plus causal attention in the
    attention layers (a token sees (S + 1) / 2 positions on average); the
    convolution's taps are elementwise and not counted."""
    s = traffic["seq_len"]
    per_pos = 2 * 2 * cfg["num_attention_heads"] * head_dim(cfg)
    attn = (layer_types(cfg).count("full_attention") * per_pos
            * (s + 1) / 2)
    return 3.0 * (2.0 * active_matmul_params(cfg) + attn) * s


def expert_matmuls(cfg: dict, span: dict):
    """(FLOPs, bytes) of the grouped expert matmuls of one compiled call,
    from what the program counted on its span: every (token, expert) pair
    meets gate, up and down once; every expert some pair touched is read
    once (bf16).  The dense layers' matmuls are XLA's: not counted.  None
    where the span carries no counts."""
    if "assignments" not in span:
        return None
    per_expert = 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]
    return (2.0 * span["assignments"] * per_expert,
            2.0 * span["experts_touched"] * per_expert)


def paged_attention_reads(cfg: dict, span: dict):
    """(FLOPs, bytes) of the paged attention of one compiled call: the
    cached rows the ATTENTION layers have to read (``kv_tokens_read_global``
    counts those layers only), each read once AS STORED — a KV head's key
    and value in one row of ``kv_row_lanes`` bf16 lanes — each meeting the
    call's query rows of every query head in a score and a value sum over
    the head's own width.  A chunk that starts at 0 attends through the
    flash kernel and reads no page: None, as where the span carries no
    counts."""
    if "kv_tokens_read_global" not in span or (
            "start" in span and span["start"] == 0):
        return None
    tokens = span["kv_tokens_read_global"]
    q_len = span.get("tokens", 1)       # a decode step: one query a row
    # of a chunk's own q_len keys a query sees half on average
    seen = tokens - (q_len - 1) / 2 * layer_types(cfg).count(
        "full_attention")
    return (2 * 2.0 * seen * q_len * cfg["num_attention_heads"]
            * head_dim(cfg),
            2.0 * tokens * cfg["num_key_value_heads"] * kv_row_lanes(cfg))


def model_flops(cfg: dict, call: dict):
    """FLOPs the configuration's mathematics needs for one compiled call of
    the serving engine (``readers/span_mfu.py`` says what ``call`` holds):
    2 a matmul parameter a REAL token activates (the experts it is sent to,
    not those held), the tied head at the one position a chunk samples and
    at one a decoding row, attention in the attention layers as 4 x query
    heads x head width a key a query must see — the causal rule in a chunk;
    in a decode step what the program counted (``kv_tokens_read_global``:
    the rows' histories, those layers only) less the one position it
    counts for each idle row.  The convolution's taps are elementwise: not
    counted.  None for a step whose rows nobody counted."""
    head = cfg["hidden_size"] * cfg["vocab_size"]
    body = active_matmul_params(cfg) - head
    attends = layer_types(cfg).count("full_attention")
    per_key = 2 * 2 * cfg["num_attention_heads"] * head_dim(cfg)
    if "tokens" in call:
        n = call["real_tokens"]
        return (2.0 * body * n + 2.0 * head + attends * per_key
                * costs.causal_keys(call["start"], n))
    if not call.get("rows"):
        return None
    keys = costs.step_keys(call, ("kv_tokens_read_global",), attends)
    return 2.0 * (body + head) * call["rows"] + per_key * keys


SPAN_COSTS = {"expert_matmuls": expert_matmuls,
              "paged_attention_reads": paged_attention_reads,
              "model_flops": model_flops}

# rehearse.py's sizes: the shape of the thing — two dense and six routed
# layers in the order c c a c c c a c, 4 query over 2 KV heads of 64 with
# their norms and [k | v] rows, 8 experts of which a token takes 2 by
# sigmoid score plus a bias that moves the choice, a tied head
_TOY_MODEL = {"num_layers": 8, "d_model": 64, "num_heads": 4,
              "num_kv_heads": 2, "head_dim": 64,
              "layer_mixer": ["short_conv", "short_conv", "attention",
                              "short_conv", "short_conv", "short_conv",
                              "attention", "short_conv"],
              "num_dense_layers": 2, "dense_width": 96, "num_experts": 8,
              "experts_per_token": 2, "expert_width": 32,
              "router_bias_stddev": 0.05, "rope_theta": 10000.0,
              "max_seq_len": 256}
TOY = {
    "serve": {"model_kwargs": _TOY_MODEL,
              "vocab_size": 512,
              "engine": {"max_batch": 4, "max_seq_len": 256,
                         "kv_page_size": 8, "kv_pool_pages": 193,
                         "prefill_chunk": 32},
              # eight layers of width 64: the toy's own limit (readings in
              # tests/benchmark_checks/test_lfm2.py's docstring)
              # 161, like the cell's 8,193: a length past ``max`` that no
              # request of the mix snaps to, five whole chunks and a final
              # one of ONE real token, so that the sample's first compared
              # position reads the carry across a chunk boundary
              "agreement": {"prompt_lens": [16, 48, 96, 161],
                            "logit_rms_limit": 0.008},
              "traffic": {"ramp_s": 1, "drain_s": 10, "clients": 4,
                          "prepare_per_s": 200.0,
                          "prepare_block_per_s": 200.0,
                          "prompt_len": {"median": 48, "sigma": 0.5,
                                         "min": 16, "max": 160,
                                         "snap_to": [16, 48, 96, 160, 161]},
                          "output_len": {"median": 6, "sigma": 0.4,
                                         "min": 3, "max": 12}}},
}
