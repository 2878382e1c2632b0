"""The routed decoder (``models/routed_decoder.py``) against its plain
reference and its own oracles, at a toy size that keeps the shape of the
thing: one period of 4 layers (global NoPE, then three window RoPE),
query/KV heads 4/2, a window shorter than the sequences, 8 experts of
which a token takes 3.  float32 throughout, so what is compared is the
mathematics and not a rounding."""

import importlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from dtf_tpu.models import build_model  # noqa: E402
from dtf_tpu.models import routed_decoder as rd  # noqa: E402
from dtf_tpu.serve.bridge import serving_memory_plan  # noqa: E402
from dtf_tpu.serve.decode import Decoder  # noqa: E402
from dtf_tpu.serve.engine import chunk_plan  # noqa: E402

TOY = dict(num_layers=4, d_model=64, num_heads=4, num_kv_heads=2, head_dim=16,
           num_experts=8, experts_per_token=3, expert_width=32, window=24,
           rope_theta=10000.0, rms_eps=1e-6,
           layer_window=[False, True, True, True],
           layer_rope=[False, True, True, True], max_seq_len=256)
VOCAB, PAGE, CHUNK = 128, 8, 16


@pytest.fixture(scope="module")
def toy():
    model, _ = build_model("routed_decoder", num_classes=VOCAB,
                           dtype=jnp.float32, **TOY)
    params = model.init(jax.random.key(3),
                        jnp.zeros((1, PAGE), jnp.int32))["params"]
    return model, params


@pytest.fixture(scope="module")
def reference():
    ref = importlib.import_module("benchmark.families.reference_smallthinker")
    return ref, ref.arch_of_model_kwargs(TOY)


def _ref_logits(reference, params, tokens):
    ref, arch = reference
    return np.asarray(ref._head(ref.hidden(params, jnp.asarray(tokens), arch),
                                params["lm_head"]))


def test_model_equals_reference(toy, reference):
    """Teacher-forced logits of the program's full forward against the
    plain reference's, 60 positions (the window is 24, so most queries
    have keys outside it).  1e-4 of the logit scale: both are float32, and
    differ in the order of their sums (the program's grouped expert rows
    against the reference's dense masked experts) — a wrong mask,
    position, head pairing or routing weight moves logits by their whole
    spread."""
    model, params = toy
    tokens = np.random.default_rng(0).integers(0, VOCAB, (2, 60),
                                               dtype=np.int32)
    got = np.asarray(model.apply({"params": params}, jnp.asarray(tokens)))
    want = _ref_logits(reference, params, tokens)
    assert np.abs(got - want).max() <= 1e-4 * np.abs(want).max()


def _serve(dec, rows, new_tokens):
    """Chunked prefill of every row (the engine's chunk plan), then
    ``new_tokens`` lockstep decode steps feeding the reference's inputs
    back: the logits at every position that would choose a token."""
    slots = dec.num_slots
    cache = dec.fresh_cache()
    tables = np.zeros((slots, dec.pages_per_slot), np.int32)
    nxt = 1
    out = [[] for _ in rows]
    for r, (prompt, _) in enumerate(rows):
        need = -(-(len(prompt) + new_tokens) // PAGE)
        tables[r, :need] = np.arange(nxt, nxt + need)
        nxt += need
        for start, clen in chunk_plan(len(prompt), CHUNK, PAGE):
            chunk = np.zeros((clen,), np.int32)
            real = prompt[start:start + clen]
            chunk[:len(real)] = real
            _, cache, last = dec.prefill_chunk(
                cache, chunk, tables[r], start, len(real) - 1, 0.0, seed=0)
        out[r].append(np.asarray(last))
    index = np.zeros((slots,), np.int32)
    index[:len(rows)] = [len(p) for p, _ in rows]
    for j in range(new_tokens - 1):
        tokens = np.zeros((slots,), np.int32)
        tokens[:len(rows)] = [cont[j] for _, cont in rows]
        _, cache, step = dec.decode_step(
            cache, tokens, index, np.zeros((slots,), np.float32),
            seeds=np.zeros((slots,), np.uint32), block_tables=tables)
        for r in range(len(rows)):
            out[r].append(np.asarray(step[r]))
        index[:len(rows)] += 1
    return [np.stack(o) for o in out]


@pytest.mark.parametrize("use_pallas", [False, "interpret"],
                         ids=["gather", "kernel"])
@pytest.mark.parametrize("lengths", [(61,), (5, 40, 61, 100)],
                         ids=["batch1", "batch4"])
def test_paged_serving_equals_reference(toy, reference, lengths, use_pallas):
    """Chunked prefill then decode through the paged cache (chunks of 16,
    pages of 8, window 24: contexts run past the window and across chunk
    and page boundaries; rows of different lengths in one decode batch)
    against the reference's full forward over prompt + continuation."""
    model, params = toy
    rng = np.random.default_rng(1)
    new = 6
    rows = [(rng.integers(0, VOCAB, n, dtype=np.int32),
             rng.integers(0, VOCAB, new, dtype=np.int32)) for n in lengths]
    dec = Decoder(model.clone(use_pallas=use_pallas), params, num_slots=4,
                  max_seq_len=128, kv_page_size=PAGE, kv_pool_pages=65)
    got = _serve(dec, rows, new)
    for (prompt, cont), g in zip(rows, got):
        seq = np.concatenate([prompt, cont])[None]
        want = _ref_logits(reference, params, seq)[0][
            len(prompt) - 1:len(prompt) - 1 + new]
        assert np.abs(g - want).max() <= 1e-4 * np.abs(want).max()
    assert dec.last_stats is not None and len(
        dec.last_stats["counts"]) == len(model.stats_names)


def _routing(case, t, e, k, rng):
    if case == "random":
        logits = rng.normal(size=(t, e))
    elif case == "all_to_one":          # every token's first choice: expert 2
        logits = rng.normal(size=(t, e))
        logits[:, 2] += 100.0
    else:                               # "one_empty": nobody takes expert 5
        logits = rng.normal(size=(t, e))
        logits[:, 5] -= 100.0
    vals, idx = jax.lax.top_k(jnp.asarray(logits, jnp.float32), k)
    return idx, jax.nn.softmax(vals, -1)


@pytest.mark.parametrize("use_pallas", [False, "interpret"],
                         ids=["ragged_dot", "pallas_gmm"])
@pytest.mark.parametrize("case", ["random", "all_to_one", "one_empty"])
@pytest.mark.parametrize("t", [5, 64])
def test_grouped_experts_equal_the_dense_oracle(t, case, use_pallas):
    """Nothing dropped: the sorted, grouped path gives what every expert
    applied to every token and masked by the routing weights gives, also
    when one expert takes a row of every token and when one takes none."""
    rng = np.random.default_rng(t)
    e, d, f, k = 8, 64, 128, 3
    x = jnp.asarray(rng.normal(size=(t, d)), jnp.float32)
    wgu = jnp.asarray(rng.normal(size=(e, d, 2 * f)) * 0.1, jnp.float32)
    wd = jnp.asarray(rng.normal(size=(e, f, d)) * 0.1, jnp.float32)
    idx, w = _routing(case, t, e, k, rng)
    got, sizes = rd.routed_experts(x, idx, w, wgu, wd, use_pallas=use_pallas)
    want = rd.routed_experts_dense(x, idx, w, wgu, wd)
    assert int(sizes.sum()) == t * k                    # every pair has a row
    if case == "all_to_one":
        assert int(sizes[2]) == t
    if case == "one_empty":
        assert int(sizes[5]) == 0
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("name,kwargs,want", [
    ("transformer", dict(num_layers=24, d_model=2048, num_heads=16,
                         d_ff=8192, max_seq_len=2048),
     dict(kv_heads=16, head_dim=128, per_token_kv_bytes=24 * 2 * 16 * 128 * 2,
          param_itemsize=4)),
    ("routed_decoder", dict(num_layers=12, d_model=2560, num_heads=28,
                            num_kv_heads=4, head_dim=128, num_experts=64,
                            experts_per_token=6, expert_width=768,
                            max_seq_len=16384, param_dtype="bfloat16"),
     dict(kv_heads=4, head_dim=128, per_token_kv_bytes=12 * 2 * 4 * 128 * 2,
          param_itemsize=2, param_bytes=11_122_897_920)),
], ids=["full_heads_f32", "grouped_heads_bf16"])
def test_serving_memory_plan_reads_the_models_geometry(name, kwargs, want):
    """KV heads, head size and parameter bytes are the model's own: 16
    full heads and float32 weights for the dense block, 4 of 28 heads and
    bfloat16 weights for the routed decoder (shapes only; nothing is
    materialised)."""
    vocab = 50257 if name == "transformer" else 151936
    model, _ = build_model(name, num_classes=vocab, dtype=jnp.bfloat16,
                           **kwargs)
    plan = serving_memory_plan(model, num_slots=16, max_seq_len=2048,
                               kv_page_size=16, kv_pool_pages=129)
    for key in ("kv_heads", "head_dim", "per_token_kv_bytes"):
        assert plan[key] == want[key], key
    shapes = jax.eval_shape(model.init, jax.random.key(0),
                            jnp.zeros((1, 16), jnp.int32))["params"]
    count = sum(int(np.prod(a.shape))
                for a in jax.tree_util.tree_leaves(shapes))
    assert plan["param_bytes"] == count * want["param_itemsize"]
    if "param_bytes" in want:
        assert plan["param_bytes"] == want["param_bytes"]
    assert plan["kv_bytes_paged"] == 128 * 16 * want["per_token_kv_bytes"]
