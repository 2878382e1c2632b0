"""The family ``minicpm_sala``: the decoder ``dtf_tpu.models.routed_decoder``
builds with a MIXER KIND A LAYER — BLOCK-SPARSE grouped-query attention
that chooses, query by query through pooled keys, which 64-token blocks of
a row's cache it reads (``minicpm4`` in a configuration's ``mixer_types``),
or LIGHTNING linear attention whose state is a matrix a head under a
constant decay and rides the page table as one entry a page
(``lightning-attn``) — dense gated-SiLU MLPs, muP scalars and an untied
head; at the sizes a configuration's ``hidden_size``,
``num_attention_heads``, ``num_key_value_heads``, ``head_dim``,
``lightning_nh``, ``lightning_head_dim``, ``intermediate_size``,
``mixer_types`` and ``assumed.sparse_config`` give.  The interface is in
``benchmark/families/__init__.py``; the family is served, not trained, so
``train_flops_per_sample`` is what ``families.load`` requires and no cell
reads yet.
"""

from __future__ import annotations


def mixer_types(cfg: dict) -> list:
    """The kinds of the layers the configuration runs."""
    return list(cfg["mixer_types"][:cfg["num_hidden_layers"]])


def sparse_sizes(cfg: dict) -> dict:
    return cfg["assumed"]["sparse_config"]["sizes"]


def pooled_row_bytes(cfg: dict) -> int:
    """bf16 bytes of one pooled key as stored: every KV head's."""
    return cfg["num_key_value_heads"] * cfg["head_dim"] * 2


def kv_bytes_per_token(cfg: dict) -> int:
    """bf16 bytes a cached token occupies over the sparse layers: K and V
    of every KV head, and its share of the pooled keys (one a
    ``kernel_stride`` tokens)."""
    row = cfg["num_key_value_heads"] * cfg["head_dim"] * 2
    return mixer_types(cfg).count("minicpm4") * (
        2 * row
        + pooled_row_bytes(cfg) // sparse_sizes(cfg)["kernel_stride"])


def matrix_bytes_per_page(cfg: dict) -> int:
    """bf16 bytes of ONE lightning layer's matrices in one page's entry."""
    return cfg["lightning_nh"] * cfg["lightning_head_dim"] ** 2 * 2


def state_bytes_per_page(cfg: dict) -> int:
    return mixer_types(cfg).count("lightning-attn") * matrix_bytes_per_page(
        cfg)


def block_kv_bytes(cfg: dict, kv_heads: int) -> int:
    """bf16 bytes of one selection block's K and V rows of ``kv_heads`` KV
    heads in one sparse layer: 32,768 a head."""
    return (sparse_sizes(cfg)["block_size"] * 2 * kv_heads
            * cfg["head_dim"] * 2)


def matmul_params(cfg: dict) -> int:
    """Parameters that meet one token in a matrix product."""
    d, n = cfg["hidden_size"], cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    ln = cfg["lightning_nh"] * cfg["lightning_head_dim"]
    kinds = mixer_types(cfg)
    mlp = 3 * d * cfg["intermediate_size"]
    return (kinds.count("minicpm4") * (d * (n + 2 * kv) + 2 * d * n + mlp)
            + kinds.count("lightning-attn") * (5 * d * ln + mlp)
            + 2 * d * cfg["vocab_size"])


def train_flops_per_sample(cfg: dict, traffic: dict) -> float:
    """One sequence of ``seq_len`` tokens, forward + backward: 6 FLOPs a
    matmul parameter a token, the lightning layers' state (two products of
    ``head_dim x head_dim`` a head a token) and the sparse layers'
    attention over what a query reads (everything up to ``dense_len``)."""
    s = traffic["seq_len"]
    kinds = mixer_types(cfg)
    sizes = sparse_sizes(cfg)
    state = (kinds.count("lightning-attn") * 2 * 2 * cfg["lightning_nh"]
             * cfg["lightning_head_dim"] ** 2)
    seen = min((s + 1) / 2, sizes["dense_len"])
    attn = (kinds.count("minicpm4") * 4 * cfg["num_attention_heads"]
            * cfg["head_dim"] * seen)
    return 3.0 * (2.0 * (matmul_params(cfg) - cfg["hidden_size"]
                         * cfg["vocab_size"]) + state + attn) * s


def block_select_scores(cfg: dict, span: dict):
    """(FLOPs, bytes) of the ``block_select`` kernel of one decode step:
    every pooled key a (row past ``dense_len``, sparse layer) scores
    (``pooled_keys_scored``) is read once as stored — both KV heads' rows,
    512 B — and meets every query head.  None where the span
    carries no count or scored nothing."""
    if not span.get("pooled_keys_scored"):
        return None
    keys = span["pooled_keys_scored"]
    return (2.0 * keys * cfg["num_attention_heads"] * cfg["head_dim"],
            float(keys * pooled_row_bytes(cfg)))


def paged_block_reads(cfg: dict, span: dict):
    """(FLOPs, bytes) of the sparse layers' attention over the cache in one
    compiled call: the LEAST work any implementation does, not the copies
    one makes.  FLOPs: the keys of every block a (query, KV head, sparse
    layer) reads (``kv_blocks_read``: the chosen blocks are the model's)
    meet the head's query heads in a score and a value sum.  Bytes, what
    must move at least once: in a decode step the K and V rows of the
    query's OWN KV head, 32,768 B a block read; in a chunk past
    ``dense_len`` (no query of it took the dense path) the distinct blocks
    its queries can see, ``ceil((start + tokens) / block)``, every KV head's
    K and V a sparse layer, ONCE — and never more than the decode form's
    count for the same ``kv_blocks_read``.  A chunk at or under
    ``dense_len`` streams the row's pages, many queries a block: FLOPs
    alone.  A first chunk goes through the flash kernel: nothing of this.
    None where the span carries no count."""
    if "kv_blocks_read" not in span:
        return None
    block = sparse_sizes(cfg)["block_size"]
    kv_heads = cfg["num_key_value_heads"]
    group = cfg["num_attention_heads"] // kv_heads
    flops = 2 * 2.0 * block * group * cfg["head_dim"] * span["kv_blocks_read"]
    by_query = float(span["kv_blocks_read"] * block_kv_bytes(cfg, 1))
    if span.get("tokens", 1) <= 1:
        return flops, by_query
    if span.get("rows_dense_path"):
        return None if span.get("start", 0) == 0 else (flops, 0.0)
    visible = -(-(span.get("start", 0) + span["tokens"]) // block)
    once = float(visible * mixer_types(cfg).count("minicpm4")
                 * block_kv_bytes(cfg, kv_heads))
    return flops, min(once, by_query)


def linear_state_steps(cfg: dict, span: dict):
    """(FLOPs, bytes) of the state kernel of one decode step: for every
    (row, lightning layer) whose entry went to a page of its own
    (``state_rows_advanced``) the matrices are read once and written once
    AS STORED, and every head's matrix meets the token in two products
    (the rank-one write, the new state against the query).  Idle rows are
    not counted: the share reads low, never high."""
    if "state_rows_advanced" not in span:
        return None
    rows = span["state_rows_advanced"]
    return (2.0 * 2 * cfg["lightning_nh"] * cfg["lightning_head_dim"] ** 2
            * rows, 2.0 * matrix_bytes_per_page(cfg) * rows)


def model_flops(cfg: dict, call: dict):
    """FLOPs the configuration's mathematics needs for one compiled call of
    the serving engine (``readers/span_mfu.py`` says what ``call`` holds):
    2 a matmul parameter of the layers a REAL token, the head at the one
    position a chunk samples and at one a decoding row; a lightning layer's
    two products of ``head_dim x head_dim`` a head a real token
    (``linear_tokens``: the program's count over those layers); the choice:
    every pooled key a (query, sparse layer) scores meets every query head
    (``pooled_keys_scored``); the sparse layers' attention as 4 x the
    group's query heads x head width a key a (query, KV head, layer) must
    see — 64 a block of ``kv_blocks_read`` (all of a row's at or under
    ``dense_len``, the forced and the chosen past it) less the keys of the
    query's own block that lie after it: exactly in a chunk, whose
    positions are known, 63 a (row, KV head, layer) in a decode step, the
    most they can be.  It counts what the MODEL reads, whichever kernel
    reads it and however often.  None where the span carries no counts or
    nobody counted a step's rows."""
    if "kv_blocks_read" not in call:
        return None
    kinds, block = mixer_types(cfg), sparse_sizes(cfg)["block_size"]
    head = cfg["hidden_size"] * cfg["vocab_size"]
    body = matmul_params(cfg) - 2 * head        # the embedding is a look-up
    pairs = kinds.count("minicpm4") * cfg["num_key_value_heads"]
    state = (2 * 2 * cfg["lightning_nh"] * cfg["lightning_head_dim"] ** 2
             * call["linear_tokens"])
    choice = (2.0 * call["pooled_keys_scored"] * cfg["num_attention_heads"]
              * cfg["head_dim"])
    if "tokens" in call:
        n = call["linear_tokens"] // kinds.count("lightning-attn")
        after = sum(block - 1 - p % block
                    for p in range(call["start"], call["start"] + n))
        sampled = 1
    else:
        n = call.get("rows")
        if not n:
            return None
        after, sampled = (block - 1) * n, n
    keys = max(call["kv_blocks_read"] * block - pairs * after, 0)
    per_key = (2 * 2 * cfg["num_attention_heads"]
               // cfg["num_key_value_heads"] * cfg["head_dim"])
    return (2.0 * body * n + 2.0 * head * sampled + state + choice
            + per_key * keys)


SPAN_COSTS = {"block_select_scores": block_select_scores,
              "paged_block_reads": paged_block_reads,
              "linear_state_steps": linear_state_steps,
              "model_flops": model_flops}

# rehearse.py's sizes: the shape of the thing — S L L S, 8 query heads over
# 2 KV heads of 16, blocks of 8 tokens chosen through pooled keys over 4 at
# stride 2, 2 chosen beside the first block and a window of 2, dense up to
# 64; 4 lightning heads of 16 at published layers 9..12 of 32; pages of 32
# = 4 blocks, chunks of 64
_TOY_MODEL = {"num_layers": 4, "d_model": 64, "num_heads": 8,
              "num_kv_heads": 2, "head_dim": 16,
              "layer_mixer": ["sparse_block", "lightning", "lightning",
                              "sparse_block"],
              "sparse": [8, 4, 2, 2, 16, 1, 64, 3.0],
              "lightning": [4, 16, 9, 32], "mup": [12.0, 1.4, 32, 4.0],
              "num_dense_layers": 4, "dense_width": 96, "max_seq_len": 256}
TOY = {
    "serve": {"model_kwargs": _TOY_MODEL,
              "vocab_size": 384,
              "engine": {"max_batch": 4, "max_seq_len": 256,
                         "kv_page_size": 32, "kv_pool_pages": 41,
                         "prefill_chunk": 64},
              # like the cell's three: 32 stays on the dense path, 96 passes
              # dense_len inside its prompt, 129 is two chunks and ONE
              # token of a third (a page entered by one real token).  The
              # toy's own limit: readings in
              # tests/benchmark_checks/test_minicpm_sala.py's docstring
              "agreement": {"prompt_lens": [32, 96, 129],
                            "new_tokens": 24, "logit_rms_limit": 0.006},
              "traffic": {"ramp_s": 1, "drain_s": 10, "clients": 4,
                          "prepare_per_s": 200.0,
                          "prepare_block_per_s": 200.0,
                          "prompt_len": {"median": 64, "sigma": 0.5,
                                         "min": 32, "max": 128,
                                         "snap_to": [32, 64, 96, 128, 129]},
                          "output_len": {"median": 8, "sigma": 0.4,
                                         "min": 4, "max": 16}}},
}
