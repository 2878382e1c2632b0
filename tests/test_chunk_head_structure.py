"""What the compiled serve bodies hold of the head, in place of their text.

Since the chunk body computes its head at the one position it samples from
(``test_chunk_head.py``), for every family the benchmark serves, at the
family's toy size and the configuration's dtype, on the CPU:

  - the chunk body's lowered text holds no tensor with both a ``C`` and a
    ``V`` dimension and ONE head product, of result ``[1, 1, V]``;
  - the decode body takes no ``head_pos``: its one head product is ``[B, 1,
    V]``, and it still lowers to the text ``tests/benchmark_checks``
    recorded (the ``*.decode`` keys; the ``chunk_*`` keys of those records
    differ since that change, by design, until a benchmark PR re-records or
    retires them).
"""

import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import pytest

from benchmark.lib.runtime import ROOT, load_json
from dtf_tpu.serve.decode import _seed_row_keys, position_key
from test_chunk_head import FAMILIES, _serving_cells, toy_decoder

CHECKS = os.path.join(ROOT, "tests", "benchmark_checks")
DIMS = r"tensor<((?:\d+x)+)[a-z]"


def _shape(dims: str) -> tuple:
    return tuple(int(d) for d in dims.split("x") if d)


def _tensor_shapes(text) -> set:
    """The shape of every tensor type the text names."""
    return {_shape(dims) for dims in re.findall(DIMS, text)}


def _head_products(text, model) -> list:
    """Result shapes of the head's products: the ``dot_general``s against a
    weight of the head's shape (the embedding's where the head is tied) whose
    result's last dimension is the vocabulary."""
    d, v = model.d_model, model.vocab_size
    weight = (v, d) if getattr(model, "tie_head", False) else (d, v)
    products = [[_shape(dims) for dims in re.findall(DIMS, line)]
                for line in text.splitlines()
                if "stablehlo.dot_general" in line]
    return [p[-1] for p in products if p[1] == weight and p[-1][-1] == v]


def _decode_text(dec) -> str:
    i32, n = jnp.int32, dec.num_slots
    zeros = jnp.zeros((n,), i32)
    return dec._decode.lower(
        dec.params, jax.eval_shape(dec.fresh_cache), jnp.zeros((n, 1), i32),
        zeros, jnp.zeros((n, dec.pages_per_slot), i32),
        jnp.zeros((n,), jnp.float32),
        _seed_row_keys(jnp.zeros((n,), jnp.uint32), zeros)).as_text()


@pytest.mark.parametrize("family", FAMILIES)
def test_the_chunk_body_holds_one_row_of_the_vocabulary(family):
    """Nothing in a continuation chunk's body is ``C`` wide and ``V`` wide
    at once and its one head product is ``[1, 1, V]``; the decode body keeps
    its head over every row."""
    dtype = {"bf16": "bfloat16", "fp32": "float32"}[
        _serving_cells()[family].config["dtype"]]
    toy, c = toy_decoder(family, dtype)
    # a vocabulary and a chunk length that are no other size of the toy's
    # (lfm2's vocabulary is its q, k and v together, three prefill chunks
    # are d_model): some rows more, and whole pages after the one page of
    # the prompt before, within a window where windows close
    known = set().union(*_tensor_shapes(_decode_text(toy)))
    dec, _ = toy_decoder(family, dtype, more_vocab=next(
        k for k in range(8, 129, 8) if toy.model.vocab_size + k not in known))
    start = dec.page_size
    limit = (dec.summary[0] if dec.summary else dec.max_seq_len) - start
    c = next(k for k in range(c, limit + 1, dec.page_size) if k not in known)
    i32, vocab, n, m = (jnp.int32, dec.model.vocab_size, dec.num_slots,
                        dec.pages_per_slot)
    cache = jax.eval_shape(dec.fresh_cache)
    window = (int(dec.table_index(start)) + c) // dec.page_size
    chunk = dec._chunk.lower(
        dec.params, cache, jnp.zeros((1, c), i32), jnp.zeros((1, m), i32),
        jnp.asarray(0, i32), jnp.asarray(0.0, jnp.float32),
        position_key(0, 0), jnp.asarray(start, i32), window, False).as_text()
    assert [s for s in _tensor_shapes(chunk) if c in s and vocab in s] == []
    assert _head_products(chunk, dec.model) == [(1, 1, vocab)]
    assert _head_products(_decode_text(dec), dec.model) == [(n, 1, vocab)]
    # ... which is not vacuous: the same reading finds the head at every
    # position where the model is handed none
    every = jax.jit(lambda p, cache: dec._apply_model(
        p, cache, jnp.zeros((1, c), i32), jnp.zeros((1,), i32),
        jnp.zeros((1, m), i32), False, window,
        jnp.zeros((1,), i32) if dec.carries_state else None)[0]).lower(
            dec.params, cache).as_text()
    assert _head_products(every, dec.model) == [(1, c, vocab)]
    assert (1, c, vocab) in _tensor_shapes(every)


def _checks_module(name):
    """A module of ``tests/benchmark_checks`` by path: the directory is no
    package, and nothing of it is edited from here."""
    spec = importlib.util.spec_from_file_location(
        "benchmark_checks_" + name, os.path.join(CHECKS, name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize("module,hashes,records", [
    ("test_joyai", "body_hashes", ("serve_bodies_lowered.json",)),
    ("test_lfm2", "body_hashes", ("serve_bodies_lowered.json",
                                  "serve_bodies_lowered_pr34.json")),
    ("test_ling", "state_body_hashes", ("serve_bodies_lowered_pr37.json",)),
])
def test_the_decode_bodies_lower_to_the_recorded_text(module, hashes,
                                                      records):
    """The half of the benchmark's text pins that still holds: the decode
    step takes no ``head_pos``, so every ``*.decode`` key equals its
    record.  The ``chunk_*`` keys are expected to differ and are not read."""
    got = getattr(_checks_module(module), hashes)()
    decode = {k: v for k, v in got.items() if k.endswith(".decode")}
    assert decode
    for record in records:
        recorded = load_json(os.path.join(CHECKS, "data", record))
        kept = {k: v for k, v in recorded.items() if k.endswith(".decode")}
        assert kept and {k: decode[k] for k in kept} == kept
