"""What a ``Decoder`` holds of the tree it is given (``Decoder._held``).

A leaf that its module rounds to the compute dtype on every call is held
rounded, once; every other leaf is the caller's array.  Rounding once or
per call hands the same operands to the same ops, so the logits of every
compiled body are held BIT-equal to those of the parent's arithmetic:
the same bodies reading the caller's tree (``_params`` set past the
setter).  Toy sizes, CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dtf_tpu.models import build_model
from dtf_tpu.models.transformer import TransformerLM
from dtf_tpu.serve import Decoder, ServeEngine, serving_mesh
from dtf_tpu.serve.bridge import tp_param_shardings
from dtf_tpu.serve.decode import teacher_forced_logits
from test_routed_decoder import TOY as ROUTED_TOY

VOCAB, SEQ, PAGE, CHUNK = 64, 64, 8, 16


def gpt2_toy(dtype):
    model = TransformerLM(vocab_size=VOCAB, num_layers=2, d_model=32,
                          num_heads=4, d_ff=64, max_seq_len=SEQ, dtype=dtype)
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, SEQ), jnp.int32))["params"]
    # trained-looking: an initial norm scale of 1 or bias of 0 is exact in
    # bf16 and would hide a leaf that must stay wide
    leaves, treedef = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.key(1), len(leaves))
    return model, jax.tree_util.tree_unflatten(treedef, [
        x + 0.05 * jax.random.normal(k, x.shape, x.dtype)
        for x, k in zip(leaves, keys)])


def routed_toy():
    """The routed decoder as it is served: parameters declared, and so
    arriving, in the compute dtype."""
    model, _ = build_model("routed_decoder", num_classes=VOCAB,
                           dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
                           **ROUTED_TOY)
    return model, model.init(jax.random.key(3),
                             jnp.zeros((1, PAGE), jnp.int32))["params"]


CASES = {"gpt2_f32_params_bf16_compute": lambda: gpt2_toy(jnp.bfloat16),
         "routed_bf16_tree": routed_toy,
         "gpt2_f32_compute": lambda: gpt2_toy(jnp.float32)}


def _leaves(tree):
    return {jax.tree_util.keystr(p): x
            for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]}


def _body_logits(dec):
    """The sampled-position logits of a first chunk, a continuation chunk
    (its last page padding) and a decode step after them."""
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, VOCAB, (CHUNK + PAGE + 3,)).astype(np.int32)
    table = 1 + np.arange(dec.pages_per_slot, dtype=np.int32)
    cache, out = dec.fresh_cache(), {}
    _, cache, out["first_chunk"] = dec.prefill_chunk(
        cache, prompt[:CHUNK], table, 0, CHUNK - 1, 0.0, seed=0)
    rest = np.zeros((2 * PAGE,), np.int32)
    rest[:PAGE + 3] = prompt[CHUNK:]
    _, cache, out["continuation_chunk"] = dec.prefill_chunk(
        cache, rest, table, CHUNK, PAGE + 2, 0.0, seed=0)
    tables = np.zeros((2, dec.pages_per_slot), np.int32)
    tables[0] = table
    _, cache, step = dec.decode_step(
        cache, np.array([7, 0], np.int32),
        np.array([len(prompt), 0], np.int32), np.zeros((2,), np.float32),
        np.zeros((2,), np.uint32), tables)
    out["decode_step"] = step[0]
    return {k: np.asarray(v) for k, v in out.items()}


@pytest.fixture(scope="module", params=list(CASES))
def both(request):
    model, params = CASES[request.param]()
    kw = dict(num_slots=2, max_seq_len=SEQ, kv_page_size=PAGE)
    held, parent = Decoder(model, params, **kw), Decoder(model, params, **kw)
    parent._params = params             # the parent: the bodies cast per call
    return (request.param, params, held, _body_logits(held),
            _body_logits(parent))


@pytest.mark.parametrize("body", ["first_chunk", "continuation_chunk",
                                  "decode_step"])
def test_logits_bit_equal_to_the_parents_arithmetic(both, body):
    _, _, _, held, parent = both
    assert np.isfinite(parent[body]).all() and np.ptp(parent[body]) > 0
    np.testing.assert_array_equal(held[body], parent[body])


def test_held_leaves(both):
    """f32 parameters under bf16 compute: Dense, Embed and position leaves
    are held in bf16, LayerNorm's stay the caller's f32 arrays.  Of a tree
    already in the compute dtype (the routed decoder's; an f32 model's)
    every leaf is the caller's array itself: no copy."""
    case, params, dec, _, _ = both
    given, held = _leaves(params), _leaves(dec.params)
    assert given.keys() == held.keys()
    narrowed = []
    if case == "gpt2_f32_params_bf16_compute":
        narrowed = [k for k in held if "['ln" not in k]
        assert len(held) - len(narrowed) == 2 * (2 * 2 + 1)    # the norms
    for k, x in held.items():
        if k in narrowed:
            assert x.dtype == jnp.bfloat16, k
            np.testing.assert_array_equal(
                np.asarray(x), np.asarray(given[k].astype(jnp.bfloat16)))
        else:
            assert x is given[k], k


def test_callers_tree_alive_f32_and_unchanged():
    """The engine neither donates nor deletes what it was given: after it
    has served, a reference forward over the caller's tree still runs and
    reads what it read before."""
    model, params = gpt2_toy(jnp.bfloat16)
    tokens = np.random.default_rng(2).integers(0, VOCAB, (1, 12))
    before = np.asarray(teacher_forced_logits(model, params, tokens))
    copies = jax.tree_util.tree_map(np.array, params)
    engine = ServeEngine(model, params, max_batch=2, max_seq_len=SEQ,
                         kv_page_size=PAGE, max_delay_s=0.0)
    try:
        served = engine.submit(tokens[0].astype(np.int32),
                               max_new_tokens=4).result(timeout=120)
    finally:
        engine.stop()
    assert len(served.tokens) == 4
    for k, x in _leaves(params).items():
        assert not x.is_deleted() and x.dtype == jnp.float32, k
    jax.tree_util.tree_map(np.testing.assert_array_equal, params, copies)
    np.testing.assert_array_equal(
        np.asarray(teacher_forced_logits(model, params, tokens)), before)
    # greedy, so the engine's first token is the reference's choice
    assert served.tokens[0] == int(np.argmax(before[0, -1]))


def test_assigning_params_holds_them_by_the_same_rule():
    """``decoder.params = tree`` (the benchmark's control swaps seeds'
    weights under one engine) holds the new tree as construction does, so
    the compiled bodies keep their argument types."""
    model, params = gpt2_toy(jnp.bfloat16)
    dec = Decoder(model, params, num_slots=2, max_seq_len=SEQ,
                  kv_page_size=PAGE)
    first = _body_logits(dec)
    compiled = dec.compiled_count
    dec.params = None
    dec.params = jax.tree_util.tree_map(np.asarray, params)   # host leaves
    assert _leaves(dec.params)["['lm_head']['kernel']"].dtype == jnp.bfloat16
    again = _body_logits(dec)
    assert dec.compiled_count == compiled
    for body in first:
        np.testing.assert_array_equal(first[body], again[body])


def test_held_leaves_keep_their_shardings_under_serve_tp(eight_devices):
    model, params = gpt2_toy(jnp.bfloat16)
    mesh = serving_mesh(2)
    dec = Decoder(model, params, num_slots=2, max_seq_len=SEQ,
                  kv_page_size=PAGE, mesh=mesh)
    _, want = tp_param_shardings(params, mesh)
    want, held = _leaves(want), _leaves(dec.params)
    assert any("model" in tuple(s.spec) for s in want.values())
    for k, x in held.items():
        assert x.sharding.is_equivalent_to(want[k], x.ndim), k
        assert x.dtype == (jnp.float32 if "['ln" in k else jnp.bfloat16), k
    # and the sharded bodies run on what is held
    assert np.isfinite(_body_logits(dec)["decode_step"]).all()
