"""The family ``glm_dsa``: the decoder ``dtf_tpu.models.routed_decoder``
builds with LATENT attention over the rows a LIGHTNING INDEXER chose — in a
``full`` layer (a configuration's ``indexer_types``) ``index_n_heads`` index
queries of ``index_head_dim`` off the query latent score every visible
token's ONE index key and the layer attends the ``index_topk`` best; a
``shared`` layer attends over the choice of the nearest ``full`` layer
below — a leading dense gated-SiLU layer, then layers of a shared expert
beside top-k-of-E gated-SiLU experts chosen by sigmoid scores plus a bias,
of which the device HOLDS ``n_routed_experts`` of the ``published`` count,
and an untied head onto the vocabulary rows held; at the sizes a
configuration's ``hidden_size``, ``num_attention_heads``, ``q_lora_rank``,
``kv_lora_rank``, ``qk_*`` and ``v_head_dim``, ``index_*``,
``moe_intermediate_size``, ``intermediate_size`` and ``mlp_layer_types``
keys give.  The layers run are ``num_hidden_layers`` of the published lists
from ``published.first_layer`` on.  The interface is in
``benchmark/families/__init__.py``; the family is served, not trained, so
``train_flops_per_sample`` is what ``families.load`` requires and no cell
reads yet.
"""

from __future__ import annotations

LANES = 128     # a cache row is stored in whole lane tiles


def _kept(cfg: dict, key: str) -> list:
    first = cfg["published"]["first_layer"]
    return list(cfg[key][first:first + cfg["num_hidden_layers"]])


def indexer_types(cfg: dict) -> list:
    """``full`` | ``shared`` of the layers the configuration runs."""
    return _kept(cfg, "indexer_types")


def mlp_types(cfg: dict) -> list:
    """``dense`` | ``sparse`` of the layers the configuration runs."""
    return _kept(cfg, "mlp_layer_types")


def row_lanes(cfg: dict) -> int:
    """Values a cached token occupies a layer, as stored (576 -> 640)."""
    return -(-(cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) // LANES) * LANES


def index_key_bytes(cfg: dict) -> int:
    """bf16 bytes of one token's index key in one ``full`` layer."""
    return cfg["index_head_dim"] * 2


def cache_bytes_per_token(cfg: dict) -> int:
    """bf16 bytes a cached token occupies as stored: a latent row a layer
    and an index key a ``full`` layer."""
    return (cfg["num_hidden_layers"] * row_lanes(cfg) * 2
            + indexer_types(cfg).count("full") * index_key_bytes(cfg))


def attention_params(cfg: dict) -> int:
    d, hq = cfg["hidden_size"], cfg["num_attention_heads"]
    rq, r = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    return (d * rq + rq * hq * (dn + dr) + d * (r + dr) + r * hq * (dn + dv)
            + hq * dv * d)


def indexer_params(cfg: dict) -> int:
    """The matrices of one ``full`` layer's indexer: queries off the query
    latent, one key and one weight a head off the hidden row."""
    hn, dh = cfg["index_n_heads"], cfg["index_head_dim"]
    return (cfg["q_lora_rank"] * hn * dh + cfg["hidden_size"] * dh
            + cfg["hidden_size"] * hn)


def expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def body_params(cfg: dict) -> int:
    """Matmul parameters one token meets in the layers OUTSIDE its chosen
    experts: attention, the ``full`` layers' indexers, the dense layers'
    MLP, and in an expert layer the router (over the published count) and
    the shared experts."""
    d = cfg["hidden_size"]
    mlps = mlp_types(cfg)
    return (cfg["num_hidden_layers"] * attention_params(cfg)
            + indexer_types(cfg).count("full") * indexer_params(cfg)
            + mlps.count("dense") * 3 * d * cfg["intermediate_size"]
            + mlps.count("sparse") * (
                d * cfg["published"]["n_routed_experts"]
                + cfg["n_shared_experts"] * expert_params(cfg)))


def held_matmul_params(cfg: dict) -> int:
    """Every matmul parameter the device holds: the body, the held experts
    of every expert layer, embedding and head over the rows held."""
    return (body_params(cfg) + mlp_types(cfg).count("sparse")
            * cfg["n_routed_experts"] * expert_params(cfg)
            + 2 * cfg["hidden_size"] * cfg["vocab_size"])


def _per_key(cfg: dict) -> int:
    """FLOPs a (query, attended row, layer): every head's score over
    ``kv_lora_rank + qk_rope_head_dim`` and value sum over
    ``kv_lora_rank`` (absorbed: the row is met as stored)."""
    return 2 * cfg["num_attention_heads"] * (
        2 * cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])


def _per_index_key(cfg: dict) -> int:
    return 2 * cfg["index_n_heads"] * cfg["index_head_dim"]


def train_flops_per_sample(cfg: dict, traffic: dict) -> float:
    """One sequence of ``seq_len`` tokens, forward + backward: 6 FLOPs a
    matmul parameter a token activates (all ``num_experts_per_tok`` of its
    experts), the indexers' scores over the causal half and attention over
    what a query attends."""
    s, top = traffic["seq_len"], cfg["index_topk"]
    active = (body_params(cfg) + mlp_types(cfg).count("sparse")
              * cfg["num_experts_per_tok"] * expert_params(cfg)
              + cfg["hidden_size"] * cfg["vocab_size"])
    seen = sum(min(p + 1, top) for p in range(s))
    scored = sum(p + 1 for p in range(s) if p + 1 > top)
    return 3.0 * (2.0 * active * s
                  + cfg["num_hidden_layers"] * _per_key(cfg) * seen
                  + indexer_types(cfg).count("full") * _per_index_key(cfg)
                  * scored)


def expert_matmuls(cfg: dict, span: dict):
    """(FLOPs, bytes) of the GROUPED expert matmuls of one compiled call,
    from what the program counted on its span: every (token, expert) pair
    COMPUTED HERE (``assignments``: the pairs whose expert this device
    holds) meets gate, up and down once; every held expert some pair
    touched is read once (bf16).  None where the span carries no counts."""
    if "assignments" not in span:
        return None
    return (2.0 * span["assignments"] * expert_params(cfg),
            2.0 * span["experts_touched"] * expert_params(cfg))


def _once(cfg: dict, span: dict, layers: int, row_bytes: int) -> float:
    """Bytes of the rows a chunk's queries can see, each ONCE a layer."""
    return float((span.get("start", 0) + span["tokens"]) * layers
                 * row_bytes)


def index_select_scores(cfg: dict, span: dict):
    """(FLOPs, bytes) of the ``index_select`` kernel in one compiled call,
    the LEAST any implementation does: every index key a (query, ``full``
    layer) scores (``index_keys_scored``) meets the query's
    ``index_n_heads`` heads; bytes — in a decode step each is read once a
    row as stored (256 B), in a chunk the keys its queries can see once a
    ``full`` layer, and never more than a read a (query, key).  None where
    the span carries no count or scored nothing."""
    if not span.get("index_keys_scored"):
        return None
    scored = span["index_keys_scored"]
    nbytes = float(scored * index_key_bytes(cfg))
    if span.get("tokens", 1) > 1:
        nbytes = min(nbytes, _once(cfg, span,
                                   indexer_types(cfg).count("full"),
                                   index_key_bytes(cfg)))
    return float(_per_index_key(cfg)) * scored, nbytes


def latent_sparse_reads(cfg: dict, span: dict):
    """(FLOPs, bytes) of the ``latent_sparse`` kernels in one compiled
    call, the LEAST any implementation does, never the copies one makes:
    every row a (query, layer) ATTENDS (``latent_rows_selected``: at most
    ``index_topk``, whatever the context) meets every query head in a score
    over 576 values and a value sum over 512; bytes — in a decode step the
    attended rows as stored (1,280 B each), in a chunk the rows its queries
    can see once a layer, and never more than a read a (query, row).  A
    chunk whose queries all attend everything goes through the dense
    kernel: nothing of this.  None where the span carries no count."""
    if "latent_rows_selected" not in span:
        return None
    if span.get("tokens", 1) > 1 and span.get("rows_dense_path"):
        return None
    rows = span["latent_rows_selected"]
    flops = 2.0 * cfg["num_attention_heads"] * (
        cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
        + cfg["kv_lora_rank"]) * rows
    nbytes = float(rows * row_lanes(cfg) * 2)
    if span.get("tokens", 1) > 1:
        nbytes = min(nbytes, _once(cfg, span, cfg["num_hidden_layers"],
                                   row_lanes(cfg) * 2))
    return flops, nbytes


def model_flops(cfg: dict, call: dict):
    """FLOPs the configuration's mathematics needs for one compiled call of
    the serving engine (``readers/span_mfu.py`` says what ``call`` holds):
    2 a matmul parameter a REAL token meets outside the chosen experts
    (``body_params``), of a token's chosen experts the pairs COMPUTED HERE
    (``assignments``: the router's count over every row and padded position
    of the call, so the real tokens take their share of it), the head onto
    the rows held at the one position a chunk samples and at one a decoding
    row; the choice: ``index_n_heads x index_head_dim`` a key a (query,
    ``full`` layer) scores, which is every visible key once the query sees
    more than ``index_topk`` and none before; attention over the rows a
    query MUST attend, min(visible, ``index_topk``) a layer, each met as
    stored.  A chunk's positions are known (``start``, ``real_tokens``), a
    decode step's are the program's counts over its real rows
    (``index_keys_scored``, ``latent_rows_selected``).  It counts what the
    MODEL needs, whichever kernel does it and however much of the cache
    that kernel streams.  None where nobody counted a step's rows."""
    top, layers = cfg["index_topk"], cfg["num_hidden_layers"]
    full = indexer_types(cfg).count("full")
    head = cfg["hidden_size"] * cfg["vocab_size"]
    body = body_params(cfg)
    if "tokens" in call:
        n, start = call["real_tokens"], call["start"]
        pairs = call.get("assignments", 0) * n / call["tokens"]
        seen = [p + 1 for p in range(start, start + n)]
        attended = sum(min(v, top) for v in seen)
        scored = sum(v for v in seen if v > top)
        sampled = 1
    else:
        n = call.get("rows")
        if not n or "latent_rows_selected" not in call:
            return None
        pairs = call.get("assignments", 0) * n / call["slots"]
        attended = call["latent_rows_selected"] / layers
        scored = call["index_keys_scored"] / max(full, 1)
        sampled = n
    return (2.0 * body * n + 2.0 * head * sampled
            + 2.0 * expert_params(cfg) * pairs
            + layers * _per_key(cfg) * attended
            + full * _per_index_key(cfg) * scored)


SPAN_COSTS = {"expert_matmuls": expert_matmuls,
              "index_select_scores": index_select_scores,
              "latent_sparse_reads": latent_sparse_reads,
              "model_flops": model_flops}

# rehearse.py's sizes: the shape of the thing — five layers, the first
# dense, F S S S F; 4 heads of nope/rope/v 16/8/16 over latents of rank 32
# (q) and 24 (kv); an indexer of 4 heads of 16 (rotary on 8) that chooses 32
# rows; 16 experts of which a token takes 4 beside a shared one, 8 held, a
# bias that moves the choice; pages of 32, chunks of 64
_TOY_MODEL = {"num_layers": 5, "d_model": 64, "num_heads": 4,
              "q_lora_rank": 32, "kv_lora_rank": 24,
              "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
              "v_head_dim": 16, "indexer": [4, 16, 32, 8],
              "layer_indexer": ["full", "shared", "shared", "shared",
                                "full"],
              "num_dense_layers": 1, "dense_width": 96, "num_experts": 16,
              "experts_per_token": 4, "expert_width": 32,
              "shared_expert_width": 32, "experts_held": [4, 8],
              "router_bias_stddev": 0.05, "max_seq_len": 256}
TOY = {
    "serve": {"model_kwargs": _TOY_MODEL,
              "vocab_size": 384,
              "engine": {"max_batch": 4, "max_seq_len": 256,
                         "kv_page_size": 32, "kv_pool_pages": 41,
                         "prefill_chunk": 64},
              # like the cell's three: 32 attends everything (the dense
              # path), 96 passes ``top`` inside its prompt, 129 is two
              # chunks and ONE token of a third (a page entered by one real
              # token).  The toy's own limit: readings in
              # tests/benchmark_checks/test_glm_dsa.py's docstring
              "agreement": {"prompt_lens": [32, 96, 129],
                            "new_tokens": 24, "logit_rms_limit": 0.034},
              "traffic": {"ramp_s": 1, "drain_s": 10, "clients": 4,
                          "prepare_per_s": 200.0,
                          "prepare_block_per_s": 200.0,
                          "prompt_len": {"median": 64, "sigma": 0.5,
                                         "min": 32, "max": 128,
                                         "snap_to": [32, 64, 96, 128, 129]},
                          "output_len": {"median": 8, "sigma": 0.4,
                                         "min": 4, "max": 16}}},
}
