"""The routed decoder's GATED forms (``linear_decay`` head,
``linear_key_heads``, ``linear_gate`` silu; ``rotary_dim``,
``attention_output_gate``; ``shared_expert_gate``) — the family the
benchmark serves as ``qwen3_next`` — against the plain reference
(``benchmark/families/reference_qwen3_next.py``) and the layers' own
oracles.  The toy keeps the shape of the thing: the period L L L A, 4
value heads of 8 over 2 key heads behind a four-tap filter, 4 query heads
over 2 KV heads of 16 of which the first 4 lanes turn, 16 experts top-4
beside a gated shared expert.  float32 throughout, so what is compared is
the mathematics and not a rounding.  One period of the layers, so that a
file of this size stays a minute on one worker."""

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

TOY = dict(num_layers=4, d_model=64, num_heads=4, num_kv_heads=2, head_dim=16,
           layer_mixer=["linear_delta"] * 3 + ["attention"],
           layer_window=[False], layer_rope=[True], rope_theta=1e7,
           rotary_dim=4, qk_norm=True, qk_norm_gain=2.0,
           attention_output_gate=True,
           norm_unit_offset=True, linear_heads=4, linear_key_heads=2,
           linear_head_dim=8, linear_conv_taps=4, linear_decay="head",
           linear_gate="silu", num_experts=16, experts_per_token=4,
           expert_width=32, shared_expert_width=32, shared_expert_gate=True,
           routing="softmax_topk", activation="silu",
           router_input="post_attention", rms_eps=1e-6, max_seq_len=256)
VOCAB, PAGE, CHUNK = 128, 16, 32
F32 = jnp.float32


def _noisy(params, seed=7):
    """The tree with every vector moved off its initial value, so that a
    norm that scaled by ``w`` where ``1 + w`` is meant, or the other way
    about, shows."""
    rng = np.random.default_rng(seed)
    return jax.tree_util.tree_map(
        lambda a: a + 0.1 * rng.standard_normal(a.shape).astype(a.dtype)
        if a.ndim == 1 else a, params)


@pytest.fixture(scope="module")
def toy():
    model, _ = build_model("routed_decoder", num_classes=VOCAB, dtype=F32,
                           **TOY)
    params = jax.jit(model.init)(jax.random.key(3),
                                 jnp.zeros((1, PAGE), jnp.int32))["params"]
    return model, _noisy(params)


@pytest.fixture(scope="module")
def reference():
    """-> logits(params, tokens): the plain reference's, one program a
    shape."""
    ref = importlib.import_module(
        "benchmark.families.reference_qwen3_next")
    arch = ref.arch_of_model_kwargs(dict(TOY, experts_held=None))
    return jax.jit(lambda params, tokens: ref._head(
        ref.hidden(params, tokens, arch), params["lm_head"]))


def _ref_logits(reference, params, tokens):
    return np.asarray(reference(params, np.asarray(tokens)))


@pytest.fixture(scope="module")
def paged(toy):
    """use_pallas -> (the decode-mode model, its call jitted once a shape,
    an empty cache, the one row's table)."""
    model, params = toy
    table = jnp.arange(1, 6, dtype=jnp.int32)[None]

    def build(use_pallas):
        dm = model.clone(decode=True, kv_page_size=PAGE, kv_pool_pages=9,
                         use_pallas=use_pallas)
        cache = jax.jit(dm.init)(jax.random.key(0),
                                 jnp.zeros((1, PAGE), jnp.int32),
                                 cache_index=jnp.zeros((1,), jnp.int32),
                                 block_table=table)["cache"]

        @jax.jit
        def call(cache, piece, index, last):
            return dm.apply({"params": params, "cache": cache}, piece,
                            cache_index=index, block_table=table,
                            last_pos=last, mutable=["cache", "stats"])
        return dm, call, cache
    built = {}
    return lambda use_pallas: built.setdefault(use_pallas, build(use_pallas))


def _close(got, want, tol=2e-4):
    np.testing.assert_allclose(got, want, atol=tol * want.std() + 1e-6)


def test_the_tree_is_the_published_layers(toy):
    """Three layers in four carry the one projection ``[q | k | v | z]``,
    ``[b | a]`` and a decay a HEAD; the fourth the query projection with
    its gate; every layer the shared expert's scalar gate."""
    _, params = toy
    lin, att = params["layer0"]["linear"], params["layer3"]["attn"]
    assert sorted(lin) == ["a_log", "ba", "dt_bias", "out", "out_norm",
                           "qkvz", "taps"]
    assert lin["qkvz"].shape == (64, 2 * 16 + 2 * 32)
    assert (lin["ba"].shape, lin["dt_bias"].shape) == ((64, 8), (4,))
    assert lin["taps"].shape == (2 * 16 + 32, 4)
    assert sorted(att) == ["k_norm", "out", "q_norm", "qkv"]
    assert att["qkv"].shape == (64, (2 * 4 + 2 * 2) * 16)
    assert params["layer0"]["shared_gate"].shape == (64, 1)
    assert [("linear" in params[f"layer{i}"]) for i in range(4)] \
        == [True, True, True, False]


def test_model_equals_reference(toy, reference):
    """Two writings of the equations — the program's whole-sequence forward
    and the plain reference — give the same logits at every position."""
    model, params = toy
    tokens = np.random.default_rng(0).integers(0, VOCAB, (2, 50),
                                               dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(jax.jit(model.apply)({"params": params}, tokens))
    _close(got, _ref_logits(reference, params, tokens))


@pytest.mark.parametrize("plen,use_pallas", [
    (40, False), (48, False), (49, False), (49, "interpret")],
    ids=["inside_a_page", "on_a_pages_edge", "one_token_past_it",
         "one_token_past_it_kernels"])
def test_prefill_in_chunks_then_decode_is_the_references_forward(
        toy, reference, paged, plen, use_pallas):
    """Through the cache, as the engine runs it: chunks of 32 tokens (the
    last padded to whole pages and told its real length), then a decode
    step a token, each sampled position's logits against the reference's
    full forward: the state and the filter's inputs cross chunks and
    pages, K and V lie in pools of the heads as stored."""
    _, params = toy
    total = 56                          # one shape for the reference
    tokens = np.random.default_rng(plen).integers(0, VOCAB, (1, total),
                                                  dtype=np.int32)
    dm, call, cache = paged(use_pallas)
    names = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_leaves_with_path(cache)]
    assert sum("linear_state" in n for n in names) == 3
    assert sum("conv_state" in n for n in names) == 3
    assert sum("paged_key" in n for n in names) == 1
    got = []
    with jax.default_matmul_precision("highest"):
        for start in range(0, plen, CHUNK):
            real = min(CHUNK, plen - start)
            clen = -(-real // PAGE) * PAGE
            piece = np.zeros((1, clen), np.int32)
            piece[0, :real] = tokens[0, start:start + real]
            logits, mut = call(cache, jnp.asarray(piece),
                               jnp.asarray([start], jnp.int32),
                               jnp.asarray([real - 1], jnp.int32))
            cache = mut["cache"]
        got.append(np.asarray(logits)[:, real - 1])
        for at in range(plen, total):
            logits, mut = call(cache, jnp.asarray(tokens[:, at:at + 1]),
                               jnp.asarray([at], jnp.int32), None)
            cache = mut["cache"]
            got.append(np.asarray(logits)[:, 0])
    want = _ref_logits(reference, params, tokens)[:, plen - 1:]
    _close(np.stack(got, 1), want)
    counts = dict(zip(dm.stats_names, np.asarray(mut["stats"]["counts"])))
    # the last decode step: one row through three state layers, the
    # attention layer reads its whole history
    assert counts["state_rows_advanced"] == 3
    assert counts["kv_tokens_read_global"] == total
    assert 1 <= counts["experts_touched"] <= 4 * 4


@pytest.mark.parametrize("field,value", [
    ("linear_decay", "row"), ("linear_gate", "tanh"),
    ("linear_key_heads", 3)])
def test_a_form_nobody_built_is_refused(field, value):
    model, _ = build_model("routed_decoder", num_classes=VOCAB, dtype=F32,
                           **dict(TOY, **{field: value}))
    with pytest.raises(ValueError):
        model.init(jax.random.key(0), jnp.zeros((1, PAGE), jnp.int32))
