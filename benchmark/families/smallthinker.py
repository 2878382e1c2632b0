"""The family ``smallthinker``: the decoder ``dtf_tpu.models.routed_decoder``
builds from a per-layer pattern — one global NoPE layer then three
window RoPE layers a period, grouped-query heads, a dropless
top-k-of-E gated-ReLU expert layer routed from the pre-attention norm —
at the sizes a configuration's ``hidden_size``, ``num_attention_heads``,
``num_key_value_heads``, ``head_dim``, ``moe_*`` and ``sliding_window_*``
keys give.  The interface is in ``benchmark/families/__init__.py``; the
family is served, not trained, so ``train_flops_per_sample`` is what
``families.load`` requires and no cell reads yet.
"""

from __future__ import annotations

from benchmark.lib import costs


def _layers(cfg: dict) -> list:
    """[is window layer] of the layers the configuration runs."""
    layout = cfg["sliding_window_layout"]
    return [bool(layout[i % len(layout)])
            for i in range(cfg["num_hidden_layers"])]


def active_matmul_params(cfg: dict) -> int:
    """Parameters that meet one token in a matrix product: q, k, v, o, the
    router and the chosen experts' gate, up and down of every layer, and
    the untied head."""
    d, dh = cfg["hidden_size"], cfg["head_dim"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    attn = d * (hq + 2 * hkv) * dh + hq * dh * d
    experts = (cfg["moe_num_active_primary_experts"]
               * 3 * d * cfg["moe_ffn_hidden_size"])
    router = d * cfg["moe_num_primary_experts"]
    return (cfg["num_hidden_layers"] * (attn + router + experts)
            + d * cfg["vocab_size"])


def train_flops_per_sample(cfg: dict, traffic: dict) -> float:
    """One sequence of ``seq_len`` tokens, forward + backward: 6 FLOPs a
    matmul parameter a token activates, plus causal attention — a full
    layer's token sees (S + 1) / 2 positions on average, a window layer's
    at most the window."""
    s = traffic["seq_len"]
    w = cfg["sliding_window_size"]
    seen_full = (s + 1) / 2
    seen_window = seen_full if s <= w else (w * (w + 1) / 2 + (s - w) * w) / s
    per_pos = 2 * 2 * cfg["num_attention_heads"] * cfg["head_dim"]
    attn = sum(per_pos * (seen_window if win else seen_full)
               for win in _layers(cfg))
    return 3.0 * (2.0 * active_matmul_params(cfg) + attn) * s


def expert_matmuls(cfg: dict, span: dict):
    """(FLOPs, bytes) of the expert matmuls of one compiled call, from
    what the program counted on its span: every (token, expert) pair meets
    gate, up and down once; every expert some pair touched is read once
    (bf16).  None where the span carries no counts."""
    if "assignments" not in span:
        return None
    per_expert = 3 * cfg["hidden_size"] * cfg["moe_ffn_hidden_size"]
    return (2.0 * span["assignments"] * per_expert,
            2.0 * span["experts_touched"] * per_expert)


def paged_attention_reads(cfg: dict, span: dict):
    """(FLOPs, bytes) of the paged attention of one compiled call: the K
    and V rows (bf16, all KV heads) the global and the window layers have
    to read, each read once, each meeting the call's query rows of every
    query head.  A chunk that starts at 0 attends through the flash kernel
    and reads no page: None, as where the span carries no counts."""
    if "kv_tokens_read_global" not in span or (
            "start" in span and span["start"] == 0):
        return None
    tokens = span["kv_tokens_read_global"] + span["kv_tokens_read_window"]
    q_len = span.get("tokens", 1)       # a decode step: one query a row
    hq, hkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    # of a chunk's own q_len keys a query sees half on average
    seen = tokens - (q_len - 1) / 2 * cfg["num_hidden_layers"]
    return (2 * 2.0 * seen * q_len * hq * dh,
            2 * 2.0 * tokens * hkv * dh)


def model_flops(cfg: dict, call: dict):
    """FLOPs the configuration's mathematics needs for one compiled call of
    the serving engine (``readers/span_mfu.py`` says what ``call`` holds):
    2 a matmul parameter a REAL token activates (the experts it is sent to,
    not those held), the head at the one position a chunk samples and at
    one a decoding row, attention as 4 x query heads x head width a layer a
    key a query must see — the causal rule in a chunk, in a window layer at
    most the window; in a decode step what the program counted
    (``kv_tokens_read_*``: a row's history, or the window's reach) less the
    one position it counts for each idle row.  None for a step whose rows
    nobody counted."""
    head = cfg["hidden_size"] * cfg["vocab_size"]
    body = active_matmul_params(cfg) - head
    per_key = 2 * 2 * cfg["num_attention_heads"] * cfg["head_dim"]
    layers = _layers(cfg)
    if "tokens" in call:
        n, start = call["real_tokens"], call["start"]
        keys = ((len(layers) - sum(layers)) * costs.causal_keys(start, n)
                + sum(layers) * costs.causal_keys(
                    start, n, cfg["sliding_window_size"]))
        return 2.0 * body * n + 2.0 * head + per_key * keys
    if not call.get("rows"):
        return None
    keys = costs.step_keys(call, ("kv_tokens_read_global",
                                  "kv_tokens_read_window"), len(layers))
    return 2.0 * (body + head) * call["rows"] + per_key * keys


SPAN_COSTS = {"expert_matmuls": expert_matmuls,
              "paged_attention_reads": paged_attention_reads,
              "model_flops": model_flops}

# rehearse.py's sizes: one period of the pattern, query/KV heads 4/2, a
# window shorter than the prompts, 8 experts of which a token takes 3
_TOY_MODEL = {"num_layers": 4, "d_model": 64, "num_heads": 4,
              "num_kv_heads": 2, "head_dim": 16, "num_experts": 8,
              "experts_per_token": 3, "expert_width": 32, "window": 24,
              "rope_theta": 10000.0, "rms_eps": 1e-6,
              "layer_window": [False, True, True, True],
              "layer_rope": [False, True, True, True], "max_seq_len": 256}
TOY = {
    "serve": {"model_kwargs": _TOY_MODEL,
              "vocab_size": 512,
              "engine": {"max_batch": 4, "max_seq_len": 256,
                         "kv_page_size": 8, "kv_pool_pages": 129,
                         "prefill_chunk": 32},
              # four layers of width 64: the toy's own limit (the program,
              # bf16 matmuls on an f32 stream, reads 0.0023-0.0053,
              # control.py --toy seeds 11-16, the largest holding a flipped
              # top-3 choice; every matrix at 8 bits 0.0128-0.0136,
              # control_rows.py --toy seeds 11-13)
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
