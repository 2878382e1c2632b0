"""The family ``qwen3_next``: the decoder ``dtf_tpu.models.routed_decoder``
builds with a MIXER KIND A LAYER — the GATED DELTA RULE (linear attention
with ONE SCALAR of decay a value head, ``linear_num_key_heads`` key heads
under ``linear_num_value_heads`` value heads, whose state is a matrix a
value head and rides the page table as one entry a large page, a ``silu(z)``
gate on a plain-weight norm) three layers in four, or GATED grouped-query
attention (heads of ``head_dim``, zero-centred norms of q and k, rotary
positions over the first ``partial_rotary_factor`` of a head, the heads'
outputs times the sigmoid of a gate that rides the query projection) —
then in EVERY layer a shared expert whose output a scalar gate scales
beside top-k-of-E gated-SiLU experts chosen by a softmax, of which the
device HOLDS ``num_experts`` of the ``published`` count, and an untied head
onto the vocabulary rows held; at the sizes a configuration's
``hidden_size``, ``num_attention_heads``, ``num_key_value_heads``,
``head_dim``, ``linear_*``, ``layer_types``, ``num_experts``,
``num_experts_per_tok``, ``moe_intermediate_size`` and
``shared_expert_intermediate_size`` keys give.  The interface is in
``benchmark/families/__init__.py``; the family is served, not trained, so
``train_flops_per_sample`` is what ``families.load`` requires and no cell
reads yet.

Every cost below counts the MODEL's least work, whatever implements it: a
decay broadcast over a head's channels, a key row repeated for its two
value heads or K and V heads copied for their query heads read LOW, and
nothing can read over 100 %.
"""

from __future__ import annotations

from benchmark.lib import costs


def layer_types(cfg: dict) -> list:
    """The kinds of the layers the configuration runs (``linear_attention``
    | ``full_attention``): the first ``num_hidden_layers`` of the published
    order."""
    return list(cfg["layer_types"][:cfg["num_hidden_layers"]])


def _count(cfg: dict, kind: str) -> int:
    return layer_types(cfg).count(kind + "_attention")


def linear_widths(cfg: dict) -> tuple:
    """(key channels, value channels) of a linear layer: 16 x 128 of q and
    of k, 32 x 128 of v (and of z)."""
    return (cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"],
            cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"])


def kv_bytes_per_token(cfg: dict) -> int:
    """bf16 bytes a cached token occupies over the gated-attention layers:
    K and V of every KV head."""
    return (_count(cfg, "full") * 2 * cfg["num_key_value_heads"]
            * cfg["head_dim"] * 2)


def matrix_bytes_per_page(cfg: dict) -> int:
    """bf16 bytes of ONE linear layer's matrices in one page's entry: a
    ``key dim x value dim`` matrix a VALUE head, as stored."""
    return (cfg["linear_num_value_heads"] * cfg["linear_key_head_dim"]
            * cfg["linear_value_head_dim"] * 2)


def state_bytes_per_page(cfg: dict) -> int:
    """bf16 bytes of the state entries of one page over the linear layers:
    the matrices, and the last ``linear_conv_kernel_dim - 1`` inputs of the
    filter over ``[q | k | v]``."""
    kn, vn = linear_widths(cfg)
    return _count(cfg, "linear") * (
        matrix_bytes_per_page(cfg)
        + (cfg["linear_conv_kernel_dim"] - 1) * (2 * kn + vn) * 2)


def mixer_params(cfg: dict) -> tuple:
    """(a linear mixer's, a gated-attention mixer's) matmul parameters a
    token meets: ``[q | k | v | z]``, ``[b | a]`` and the output
    projection; q with its gate, k, v and the output projection."""
    d, dh = cfg["hidden_size"], cfg["head_dim"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    kn, vn = linear_widths(cfg)
    linear = (d * (2 * kn + 2 * vn) + d * 2 * cfg["linear_num_value_heads"]
              + vn * d)
    full = d * (2 * hq + 2 * hkv) * dh + hq * dh * d
    return linear, full


def active_matmul_params(cfg: dict) -> int:
    """Parameters that meet one token in a matrix product: the mixer's
    projections of every layer, the router (over the published count), the
    shared expert with its gate and the chosen experts of every layer; the
    untied head onto the rows held."""
    d = cfg["hidden_size"]
    linear, full = mixer_params(cfg)
    expert = 3 * d * cfg["moe_intermediate_size"]
    routed = (d * cfg["published"]["num_experts"]
              + 3 * d * cfg["shared_expert_intermediate_size"] + d
              + cfg["num_experts_per_tok"] * expert)
    return (_count(cfg, "linear") * linear + _count(cfg, "full") * full
            + cfg["num_hidden_layers"] * routed + d * cfg["vocab_size"])


def _state_flops_per_token(cfg: dict) -> int:
    """A linear layer's state a token: three products of ``key dim x value
    dim`` a value head (the decayed state against the key, the rank-one
    write, the new state against the query)."""
    return (3 * 2 * cfg["linear_num_value_heads"] * cfg["linear_key_head_dim"]
            * cfg["linear_value_head_dim"])


def _attention_flops_per_key(cfg: dict) -> int:
    """A gated-attention layer, a (query, key): the score and the value
    sum over ``head_dim`` a query head."""
    return 2 * 2 * cfg["num_attention_heads"] * cfg["head_dim"]


def train_flops_per_sample(cfg: dict, traffic: dict) -> float:
    """One sequence of ``seq_len`` tokens, forward + backward: 6 FLOPs a
    matmul parameter a token activates, the linear layers' state, and
    causal attention (a token sees (S + 1) / 2 positions on average)."""
    s = traffic["seq_len"]
    state = _count(cfg, "linear") * _state_flops_per_token(cfg)
    attn = _count(cfg, "full") * _attention_flops_per_key(cfg) * (s + 1) / 2
    return 3.0 * (2.0 * active_matmul_params(cfg) + state + attn) * s


def expert_matmuls(cfg: dict, span: dict):
    """(FLOPs, bytes) of the GROUPED expert matmuls of one compiled call,
    from what the program counted on its span: every (token, expert) pair
    COMPUTED HERE (``assignments``: the pairs whose expert this device
    holds) meets gate, up and down once; every held expert some pair
    touched (``experts_touched``, summed over the layers) is read once
    (bf16, 6.29e6 B).  The shared expert's matmuls are XLA's: not counted.
    None where the span carries no counts."""
    if "assignments" not in span:
        return None
    per_expert = 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]
    return (2.0 * span["assignments"] * per_expert,
            2.0 * span["experts_touched"] * per_expert)


def linear_state_steps(cfg: dict, span: dict):
    """(FLOPs, bytes) of the state kernel of one decode step: for every
    (row, linear layer) whose entry went to a page of its own
    (``state_rows_advanced``, summed over the layers) the matrices are read
    once and written once AS STORED (2 x 1,048,576 B), and every value
    head's matrix meets the token in three products; the decay is one
    scalar a head and the key row one a PAIR of value heads, so the rows
    the kernel is handed beside the matrices are not counted at all.  Idle
    rows, which the kernel also moves through the scratch page, are not
    counted: the share reads low, never high.  None where the span carries
    no count."""
    if "state_rows_advanced" not in span:
        return None
    rows = span["state_rows_advanced"]
    return (float(_state_flops_per_token(cfg)) * rows,
            2.0 * matrix_bytes_per_page(cfg) * rows)


def paged_attention_reads(cfg: dict, span: dict):
    """(FLOPs, bytes) of the paged attention of one compiled call over the
    K and V pools of the gated-attention layers: every cached K and V row
    the call's queries attend (``kv_tokens_read_global`` counts those
    layers only) is read once, 2 KV heads of 256 in bf16, and meets every
    query head of every query of the call.  A chunk that starts at 0
    attends through the flash kernel and reads no page: None, as where the
    span carries no count."""
    if "kv_tokens_read_global" not in span or span.get("start") == 0:
        return None
    tokens = span["kv_tokens_read_global"]
    q_len = span.get("tokens", 1)       # a decode step: one query a row
    # of a chunk's own q_len keys a query sees half on average
    seen = tokens - (q_len - 1) / 2 * _count(cfg, "full")
    return (float(_attention_flops_per_key(cfg)) * seen * q_len,
            2.0 * 2 * tokens * cfg["num_key_value_heads"] * cfg["head_dim"])


def flash_first_chunks(cfg: dict, span: dict):
    """(FLOPs, bytes) of the flash forward of one FIRST chunk (start 0; any
    other chunk attends through the paged kernel: None): in each
    gated-attention layer the chunk's real queries meet the keys they may
    see under the causal rule, a query head a score and a value sum over
    256; q read and o written once a query head, K and V once a KV head —
    the copies of a KV head for its 8 query heads are the implementation's
    and are not counted."""
    if span.get("start") != 0 or "tokens" not in span:
        return None
    n = span.get("real_tokens", span["tokens"])
    hq, hkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    layers = _count(cfg, "full")
    return (float(layers * _attention_flops_per_key(cfg)
                  * costs.causal_keys(0, n)),
            2.0 * layers * n * (2 * hq + 2 * hkv) * dh)


def model_flops(cfg: dict, call: dict):
    """FLOPs the configuration's mathematics needs for one compiled call of
    the serving engine (``readers/span_mfu.py`` says what ``call`` holds):
    2 a matmul parameter a REAL token meets outside the chosen experts
    (mixers, the router over the published count, the shared expert and its
    gate), the head onto the rows held at the one position a chunk samples
    and at one a decoding row, and of a token's chosen experts the pairs
    COMPUTED HERE (``assignments``: the router's, not an implementation's;
    it runs over every row and padded position of the call, so the real
    tokens take their share of it; no count: none).  A linear layer's
    state: three products of 128 x 128 a value head a real token.  A
    gated-attention layer: a chunk's queries the keys they must see under
    the causal rule, a decode step's what the program counted
    (``kv_tokens_read_global``) less the one position it counts for each
    idle row.  None for a step whose rows nobody counted."""
    d = cfg["hidden_size"]
    expert = 3 * d * cfg["moe_intermediate_size"]
    head = d * cfg["vocab_size"]
    body = (active_matmul_params(cfg) - head
            - cfg["num_hidden_layers"] * cfg["num_experts_per_tok"] * expert)
    state = _count(cfg, "linear") * _state_flops_per_token(cfg)
    per_key = _attention_flops_per_key(cfg)
    if "tokens" in call:
        n = call["real_tokens"]
        pairs = call.get("assignments", 0) * n / call["tokens"]
        return ((2.0 * body + state) * n + 2.0 * head + 2.0 * expert * pairs
                + _count(cfg, "full") * per_key
                * costs.causal_keys(call["start"], n))
    if not call.get("rows"):
        return None
    rows = call["rows"]
    pairs = call.get("assignments", 0) * rows / call["slots"]
    keys = costs.step_keys(call, ("kv_tokens_read_global",),
                           _count(cfg, "full"))
    return ((2.0 * (body + head) + state) * rows + 2.0 * expert * pairs
            + per_key * keys)


SPAN_COSTS = {"expert_matmuls": expert_matmuls,
              "linear_state_steps": linear_state_steps,
              "paged_attention_reads": paged_attention_reads,
              "flash_first_chunks": flash_first_chunks,
              "model_flops": model_flops}

# rehearse.py's sizes: the shape of the thing — ONE period L L L A (the
# tests of the benchmark's files are held to a minute a file: two periods
# compile twice as long and show nothing more), 4 value heads of 8 over 2
# key heads behind a four-tap filter, 4 query heads over 2 KV heads of 16 of
# which the first 4 lanes turn, 16 experts top-4 of which the first 4 are
# held (rank 0 of 4), a gated shared expert
_TOY_MODEL = {"num_layers": 4, "d_model": 64, "num_heads": 4,
              "num_kv_heads": 2, "head_dim": 16, "rotary_dim": 4,
              "linear_heads": 4, "linear_key_heads": 2,
              "linear_head_dim": 8, "num_experts": 16,
              "experts_per_token": 4, "expert_width": 32,
              "shared_expert_width": 32, "experts_held": [0, 4],
              "max_seq_len": 192}
TOY = {
    "serve": {"model_kwargs": _TOY_MODEL,
              "vocab_size": 512,
              "engine": {"max_batch": 4, "max_seq_len": 192,
                         "kv_page_size": 16, "kv_pool_pages": 65,
                         "prefill_chunk": 32},
              # four layers of width 64: the toy's own limit (readings in
              # tests/benchmark_checks/test_qwen3_next.py's docstring).  97,
              # like the cell's 16,385: three whole chunks and a final one
              # of ONE real token, so that the sample's first compared
              # position reads the state and the filter across a chunk
              # boundary and enters a page by one token
              "agreement": {"prompt_lens": [16, 48, 96, 97],
                            "new_tokens": 24, "logit_rms_limit": 0.017},
              "traffic": {"ramp_s": 1, "drain_s": 10, "clients": 4,
                          "prepare_per_s": 200.0,
                          "prepare_block_per_s": 200.0,
                          "prompt_len": {"median": 48, "sigma": 0.5,
                                         "min": 16, "max": 96,
                                         "snap_to": [16, 48, 96, 97]},
                          "output_len": {"median": 6, "sigma": 0.4,
                                         "min": 3, "max": 12}}},
}
