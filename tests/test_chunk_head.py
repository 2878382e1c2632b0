"""A prefill chunk computes its head at the ONE position it samples from.

``Decoder._chunk_impl`` hands the model ``head_pos`` and both model classes
take that hidden row before the final norm and the head, so the product is
``[1, 1, d] x [d, V]`` and no ``[C, V]`` value exists; the decode body hands
none and is the body it was.  Held here for every family the benchmark
serves, at the family's own toy size (``TOY`` in
``benchmark/families/<family>.py``), on the CPU: the row of logits is the
row the all-positions head gives.  What the compiled bodies hold is
``test_chunk_head_structure.py``'s.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib.runtime import load_benchmark, load_cell
from dtf_tpu.models import build_model
from dtf_tpu.serve.decode import Decoder

FAMILIES = ("gpt2", "smallthinker", "joyai", "lfm2", "ling", "evabyte",
            "minicpm_sala", "glm_dsa", "qwen3_next")


@functools.lru_cache(maxsize=None)
def _serving_cells() -> dict:
    """family -> the first cell of BENCHMARK.json that serves it."""
    bench, out = load_benchmark(), {}
    for w in bench["workloads"]:
        cell = load_cell(bench, w["name"])
        if cell.workload["driver"] == "serve":
            out.setdefault(cell.config["family"], cell)
    return out


@functools.lru_cache(maxsize=None)
def toy_decoder(family: str, dtype: str, more_vocab: int = 0):
    """(decoder, prefill chunk) of the family's toy: the configuration's own
    ``build_model`` call at the toy's sizes (``more_vocab`` rows more to the
    vocabulary) and engine settings, as the serve driver builds a
    rehearsal's, in ``dtype``.  The weights are drawn on the host over the
    shapes ``init`` would give (compiling ``init`` is most of a rehearsal's
    set-up): N(0, 0.05), about 1 where a leaf is a vector — a norm's scale,
    which then differs a channel."""
    cell = _serving_cells()[family]
    toy = cell.family.TOY["serve"]
    engine = dict(cell.workload["engine"], **toy["engine"])
    model, _ = build_model(
        cell.config["build_model"]["name"],
        num_classes=toy["vocab_size"] + more_vocab,
        dtype=jnp.dtype(dtype), **dict(cell.config["build_model"]["kwargs"],
                                       **toy["model_kwargs"]))
    shapes = jax.eval_shape(
        model.clone(use_pallas=False).init, jax.random.key(0),
        jnp.zeros((1, engine["kv_page_size"]), jnp.int32))["params"]
    rng = np.random.default_rng(51)
    params = jax.tree_util.tree_map(
        lambda s: jnp.asarray((s.ndim == 1) + 0.05 * rng.standard_normal(
            s.shape), s.dtype), shapes)
    dec = Decoder(model, params, num_slots=engine["max_batch"],
                  max_seq_len=engine["max_seq_len"],
                  kv_page_size=engine["kv_page_size"],
                  kv_pool_pages=engine["kv_pool_pages"])
    return dec, int(engine["prefill_chunk"])


def test_every_serving_family_is_held_here():
    assert sorted(_serving_cells()) == sorted(FAMILIES)


@functools.lru_cache(maxsize=None)
def _both_heads(family: str, first: bool):
    """One compiled call a family and kind of body (the first chunk's goes
    through ``flash_prefill``): the head at every position, the head at
    ``pos``, and the cache the call wrote."""
    dec, c = toy_decoder(family, "float32")
    window = (None if dec._kernel_attn
              else (1 if first else 2) * c // dec.page_size)

    @jax.jit
    def both(params, cache, tokens, start, block_row, pos):
        def call(head_pos):
            return dec._apply_model(
                params, cache, tokens, start, block_row, first, window,
                pos if dec.carries_state else None, head_pos)
        (every, mut), (one, _) = call(None), call(pos)
        return every, one, mut["cache"]
    return both


# chunk kind -> (chunks already in the cache, real tokens short of C)
KINDS = {"first": (0, 0), "continuation": (1, 0), "padded_final": (1, 5)}


@pytest.mark.parametrize("kind", list(KINDS))
@pytest.mark.parametrize("family", FAMILIES)
def test_the_head_at_one_position_is_that_row_of_the_head_at_all(family,
                                                                  kind):
    """``apply(tokens, head_pos=p)[b, 0] == apply(tokens)[b, p]`` to 1e-5 in
    float32 (in bfloat16 the CPU's compiler keeps or drops a rounding by how
    it fuses, which is not the head's doing): the first chunk of a prompt
    (``p = C - 1``), a continuation chunk over the cache the first wrote,
    and a tail-padded final chunk (``p`` < ``C - 1``; a model whose cache
    carries state takes the same position as ``last_pos``)."""
    dec, c = toy_decoder(family, "float32")
    before, short = KINDS[kind]
    vocab = dec.model.vocab_size
    rng = np.random.default_rng(short)
    pages = 1 + np.arange(dec.pages_per_slot, dtype=np.int32)[None]
    assert pages[0, -1] < dec.pool_pages
    cache = dec.fresh_cache()
    for i in range(before + 1):
        p = c - 1 - (short if i == before else 0)
        tokens = np.zeros((1, c), np.int32)
        tokens[0, :p + 1] = rng.integers(0, vocab, size=p + 1)
        every, one, cache = _both_heads(family, i == 0)(
            dec.params, cache, tokens, np.full((1,), i * c, np.int32), pages,
            np.full((1,), p, np.int32))
    assert every.shape == (1, c, vocab) and one.shape == (1, 1, vocab)
    assert every.dtype == one.dtype == jnp.float32
    assert float(jnp.std(every[0, p])) > 1e-3      # a head that says something
    np.testing.assert_allclose(one[0, 0], every[0, p], rtol=0, atol=1e-5)
    # ... and no neighbour's: the position is the one asked for
    assert float(jnp.max(jnp.abs(one[0, 0] - every[0, p - 1]))) > 1e-3
