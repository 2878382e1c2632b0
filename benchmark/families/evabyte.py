"""The family ``evabyte``: the decoder ``dtf_tpu.models.routed_decoder``
builds with EVERY layer dense (a gated-SiLU MLP, no router) and every
attention layer of the WINDOW-AND-SUMMARIES kind — whole heads, rotate-half
RoPE at the true position, the exact keys of the query's own aligned
window of ``window_size`` positions beside one learned summary a
``chunk_size`` positions of every window before it, both in the one K and
V page pool, whose table is compact (``dtf_tpu/ops/window_summary.py``) —
RMSNorm with a unit offset, a byte vocabulary and an untied head; at the
sizes a configuration's ``hidden_size``, ``num_attention_heads``,
``intermediate_size``, ``window_size``, ``chunk_size`` and ``vocab_size``
keys give.  The interface is in ``benchmark/families/__init__.py``; the
family is served, not trained, so ``train_flops_per_sample`` is what
``families.load`` requires and no cell reads yet.
"""

from __future__ import annotations

from benchmark.lib import costs


def head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def kv_bytes_per_row(cfg: dict) -> int:
    """bf16 bytes one cached row — an exact token or one chunk's summary —
    occupies in ONE layer: K and V of every head."""
    return 2 * cfg["num_key_value_heads"] * head_dim(cfg) * 2


def matmul_params(cfg: dict) -> int:
    """Parameters that meet one token in a matrix product: every layer's
    projections and MLP, and the untied head."""
    d, n = cfg["hidden_size"], cfg["num_attention_heads"] * head_dim(cfg)
    layer = 4 * d * n + 3 * d * cfg["intermediate_size"]
    return cfg["num_hidden_layers"] * layer + d * cfg["vocab_size"]


def train_flops_per_sample(cfg: dict, traffic: dict) -> float:
    """One sequence of ``seq_len`` tokens, forward + backward: 6 FLOPs a
    matmul parameter a token, plus the attention over a token's window (half
    of it on average) and the summaries before it (half of the sequence's
    on average)."""
    s = traffic["seq_len"]
    seen = (min(s, cfg["window_size"]) + s / cfg["chunk_size"]) / 2
    attn = (cfg["num_hidden_layers"] * 2 * 2 * cfg["num_attention_heads"]
            * head_dim(cfg) * seen)
    return 3.0 * (2.0 * matmul_params(cfg) + attn) * s


def paged_attention_reads(cfg: dict, span: dict):
    """(FLOPs, bytes) of the paged attention of one compiled call, from
    what the program counted on its span: every cached row the call's
    queries attend — the open window's exact tokens and the closed
    windows' summaries alike, summed over rows and layers — is read once
    AS STORED (K and V of every head, bf16) and meets the call's queries of
    every head in a score and a value sum over the head's width.  A chunk
    that starts at 0 attends through the flash kernel and reads no page:
    None, as where the span carries no counts."""
    if "kv_exact_rows_read" not in span or (
            "start" in span and span["start"] == 0):
        return None
    rows = span["kv_exact_rows_read"] + span["kv_summary_rows_read"]
    q_len = span.get("tokens", 1)       # a decode step: one query a row
    # of a chunk's own q_len keys a query sees half on average
    seen = rows - (q_len - 1) / 2 * cfg["num_hidden_layers"]
    return (2 * 2.0 * seen * q_len * cfg["num_attention_heads"]
            * head_dim(cfg), float(rows * kv_bytes_per_row(cfg)))


def window_compactions(cfg: dict, span: dict):
    """(FLOPs, bytes) of the windows closed before one compiled call
    (``windows_closed``: the closes the decoder launched, one a row; every
    layer closes its own): a window's ``window_size`` exact rows are read
    and its ``window_size / chunk_size`` summaries written, as stored; a
    row meets ``phi`` in a score and weighs its key and its value.  None
    where the span carries no count or closed nothing."""
    if not span.get("windows_closed"):
        return None
    windows = span["windows_closed"] * cfg["num_hidden_layers"]
    w, c = cfg["window_size"], cfg["chunk_size"]
    return (windows * w * 3 * 2.0 * cfg["num_key_value_heads"]
            * head_dim(cfg),
            float(windows * (w + w // c) * kv_bytes_per_row(cfg)))


def rows_seen(cfg: dict, start: int, tokens: int) -> int:
    """Cached rows the ``tokens`` queries at positions ``start ...`` must
    see between them in ONE layer: a query's own aligned window up to
    itself, exact, and one summary a ``chunk_size`` positions of every
    window before it."""
    w, per = cfg["window_size"], cfg["window_size"] // cfg["chunk_size"]
    return sum(p - p // w * w + 1 + p // w * per
               for p in range(start, start + tokens))


def model_flops(cfg: dict, call: dict):
    """FLOPs the configuration's mathematics needs for one compiled call of
    the serving engine (``readers/span_mfu.py`` says what ``call`` holds):
    2 a matmul parameter a REAL token, the head at the one position a
    chunk samples and at one a decoding row, attention as 4 x heads x head
    width a layer a cached row a query must see — exact rows of its own
    window and the summaries before it (``rows_seen``) in a chunk; in a
    decode step what the program counted (``kv_exact_rows_read`` +
    ``kv_summary_rows_read``) less the one row it counts a layer for each
    idle row.  The closes' own work (a score and two weighted sums a row a
    window) is not counted: it reads low.  None for a step whose rows
    nobody counted."""
    head = cfg["hidden_size"] * cfg["vocab_size"]
    body = matmul_params(cfg) - head
    layers = cfg["num_hidden_layers"]
    per_key = 2 * 2 * cfg["num_attention_heads"] * head_dim(cfg)
    if "tokens" in call:
        n = call["real_tokens"]
        return (2.0 * body * n + 2.0 * head
                + layers * per_key * rows_seen(cfg, call["start"], n))
    if not call.get("rows"):
        return None
    keys = costs.step_keys(call, ("kv_exact_rows_read",
                                  "kv_summary_rows_read"), layers)
    return 2.0 * (body + head) * call["rows"] + per_key * keys


SPAN_COSTS = {"paged_attention_reads": paged_attention_reads,
              "window_compactions": window_compactions,
              "model_flops": model_flops}

# rehearse.py's sizes: the shape of the thing — four dense layers, 4 heads
# of 16, a window of 32 positions with a summary every 4 (8 summaries a
# window = one page of 8), a chunk of 16 that divides the window
_TOY_MODEL = {"num_layers": 4, "d_model": 64, "num_heads": 4,
              "num_kv_heads": 4, "head_dim": 16, "num_dense_layers": 4,
              "dense_width": 96, "summary_window": 32, "summary_chunk": 4,
              "max_seq_len": 256}
TOY = {
    "serve": {"model_kwargs": _TOY_MODEL,
              "vocab_size": 320,
              "engine": {"max_batch": 4, "max_seq_len": 256,
                         "kv_page_size": 8, "kv_pool_pages": 81,
                         "prefill_chunk": 16},
              # like the cell's three: 48 closes window 0 in a chunk, 64
              # (two whole windows) closes its second at the first decode
              # step and a third mid-answer, 161 is five closed windows and
              # ONE byte of the sixth.  The toy's own limit: readings in
              # tests/benchmark_checks/test_evabyte.py's docstring
              "agreement": {"prompt_lens": [48, 64, 161],
                            "new_tokens": 40, "logit_rms_limit": 0.006},
              "traffic": {"ramp_s": 1, "drain_s": 10, "clients": 4,
                          "prepare_per_s": 200.0,
                          "prepare_block_per_s": 200.0,
                          "prompt_len": {"median": 64, "sigma": 0.5,
                                         "min": 32, "max": 160,
                                         "snap_to": [32, 48, 64, 96, 128,
                                                     160, 161]},
                          "output_len": {"median": 24, "sigma": 0.4,
                                         "min": 8, "max": 48}}},
}
