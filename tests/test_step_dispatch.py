"""The HOST side of ``Decoder.decode_step``: what a step needs besides its
body reaches the device as ONE host array through ONE cached program
(``_step_operands``: it splits the array into the body's operands and makes
the rows' sampling keys) — ``tests/test_chunk_dispatch.py``'s sibling.

Held here: that program's keys are ``_seed_row_keys``' and the eager
``fold_in(key(seed), position)`` bit for bit; a step's tokens and logits are
those of the body called the way the parent called it (a ``jnp.asarray`` an
argument, ``_seed_row_keys``); no VALUE of tokens, index, seeds,
temperatures or tables compiles anything once a step is warm; and a step is
two programs on the device.  Toy sizes, CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dtf_tpu.models import build_model
from dtf_tpu.serve import Decoder, serving_mesh
from dtf_tpu.serve import decode as sd
from dtf_tpu.serve.engine import chunk_plan
from test_chunk_dispatch import (POSITIONS, SEEDS, TOYS, _Compiles,
                                 _eager_key, _programs_run)
from test_held_params import gpt2_toy
from test_window_summary import KW as SUMMARY_TOY
from test_window_summary import WINDOW

PAGE, CHUNK, SEQ, ROWS = 8, 16, 64, 3
# 1e-40: a float32 denormal, which a round trip through arithmetic could
# flush and a round trip through its bits cannot
TEMPERATURES = [0.0, 0.8, 1e-40, 3.0e38]


def _summary_toy():
    """The routed decoder that keeps a window of exact keys beside one
    summary a chunk (a close is a launch of its own, before the body's);
    float32."""
    model, _ = build_model("routed_decoder", num_classes=128,
                           dtype=jnp.float32, **SUMMARY_TOY)
    return model, jax.jit(model.clone(use_pallas=False).init)(
        jax.random.key(3), jnp.zeros((1, PAGE), jnp.int32))["params"]


STEP_TOYS = dict(TOYS, summary=_summary_toy)


def _parents_step(dec, cache, tokens, index, temperature, seeds, tables):
    """``decode_step`` as the parent made the call: three ``jnp.asarray``
    and a ``reshape`` (a program of its own), the keys from
    ``_seed_row_keys`` (another), the tables' transfer, into the jitted
    body."""
    toks = jnp.asarray(tokens, jnp.int32).reshape(-1, 1)
    idx = jnp.asarray(index, jnp.int32)
    temperature = jnp.asarray(temperature, jnp.float32)
    rowkeys = sd._seed_row_keys(jnp.asarray(seeds, jnp.uint32), idx)
    if dec.summary is not None:
        cache = dec._close_windows(cache, np.asarray(index),
                                   np.asarray(tables))
    out, cache, last, _ = dec._decode(
        dec.params, cache, toks, idx, jnp.asarray(tables, jnp.int32),
        temperature, rowkeys)
    return out, cache, last


# -- (a) the keys, and the operands beside them ---------------------------
@pytest.mark.parametrize("position", POSITIONS)
@pytest.mark.parametrize("seed", SEEDS)
def test_the_one_program_gives_the_row_keys_and_the_host_values(seed,
                                                                position):
    """Row 0 holds the case; the rows beside it the rest of the grid's
    corners, so every case also mixes seeds, positions and temperatures in
    one step."""
    seeds = np.array([seed] + SEEDS[::-1], np.uint32)
    index = np.array([position] + POSITIONS, np.int32)
    temps = np.array([TEMPERATURES[SEEDS.index(seed)]] + TEMPERATURES,
                     np.float32)
    tokens = np.arange(7, 7 + seeds.size, dtype=np.int32)
    tables = np.arange(seeds.size * 6, dtype=np.int32).reshape(-1, 6)[::-1]
    assert temps[3] != 0 and temps[3] < np.finfo(np.float32).tiny
    got = sd._step_operands(
        sd._pack_step_operands(tokens, index, temps, seeds, tables),
        seeds.size)
    rows = sd._seed_row_keys(jnp.asarray(seeds), jnp.asarray(index))
    assert (got[4].dtype, got[4].shape) == (rows.dtype, rows.shape)
    np.testing.assert_array_equal(np.asarray(jax.random.key_data(got[4])),
                                  np.asarray(jax.random.key_data(rows)))
    np.testing.assert_array_equal(
        np.asarray(jax.random.key_data(got[4][0])),
        np.asarray(jax.random.key_data(_eager_key(seed, position))))
    # the other four: the host's values at the body's avals, strong-typed,
    # the temperatures to the bit
    for x, value in zip(got[:4], (tokens[:, None], index, tables, temps)):
        assert (x.shape, x.dtype, x.weak_type) == (
            value.shape, value.dtype, False)
        np.testing.assert_array_equal(
            np.asarray(x).view(np.int32), np.ascontiguousarray(value).view(
                np.int32))


# -- (b) the same step ----------------------------------------------------
STEPS = ["first", "second", "at_a_windows_close", "after"]
PROMPTS = {0: WINDOW - 2, 2: WINDOW - 10}      # row -> its prompt's length


@pytest.fixture(scope="module", params=list(STEP_TOYS))
def decoder(request):
    model, params = STEP_TOYS[request.param]()
    return Decoder(model, params, num_slots=ROWS, max_seq_len=SEQ,
                   kv_page_size=PAGE, kv_pool_pages=1 + ROWS * SEQ // PAGE)


def _prefilled(dec, tables, seed):
    """A cache holding rows 0 and 2's prompts (row 1 stays all zeros), and
    the token each row decodes from."""
    vocab = dec.model.vocab_size
    rng = np.random.default_rng(11)
    cache, tokens = dec.fresh_cache(), np.zeros((ROWS,), np.int32)
    for row, plen in PROMPTS.items():
        prompt = rng.integers(0, vocab, (plen,)).astype(np.int32)
        for start, clen in chunk_plan(plen, CHUNK, PAGE):
            piece = np.zeros((clen,), np.int32)
            real = prompt[start:start + clen]
            piece[:len(real)] = real
            tok, cache, _ = dec.prefill_chunk(
                cache, piece, tables[row], start, len(real) - 1, 0.0,
                seed=seed)
        tokens[row] = int(tok)
    return cache, tokens


@pytest.fixture(scope="module", params=[0.0, 0.8])
def both_ways(request, decoder):
    """{step: ((tokens, logits) of ``decode_step``, of the parent's call)},
    each way on a cache of its own: two rows decoding beside an all-zero
    row, row 0 crossing its window's end at the third step."""
    dec = decoder
    tables = np.zeros((ROWS, dec.pages_per_slot), np.int32)
    for row in PROMPTS:
        tables[row] = 1 + row * dec.pages_per_slot + np.arange(
            dec.pages_per_slot)
    temps = np.array([request.param, 0.0, request.param], np.float32)
    seeds = np.array([2**32 - 5, 0, 2**31 + 3], np.uint32)
    index = np.array([PROMPTS.get(r, 0) for r in range(ROWS)], np.int32)
    (mine, tokens), (theirs, ptokens) = (_prefilled(dec, tables, 7)
                                         for _ in range(2))
    closed, out = dec.windows_closed, {}
    for name in STEPS:
        tokens, mine, last = dec.decode_step(
            mine, tokens, index, temps, seeds=seeds, block_tables=tables)
        ptokens, theirs, plast = _parents_step(
            dec, theirs, ptokens, index, temps, seeds, tables)
        out[name] = ((np.asarray(tokens), np.asarray(last)),
                     (np.asarray(ptokens), np.asarray(plast)))
        index = index + np.array([r in PROMPTS for r in range(ROWS)])
    # the window's close ran where the model has one, once a way
    assert dec.windows_closed - closed == 2 * (dec.summary is not None)
    return out


@pytest.mark.parametrize("step", STEPS)
def test_tokens_and_logits_bit_equal_to_the_parents_call(both_ways, step):
    (toks, last), (ptoks, plast) = both_ways[step]
    assert np.isfinite(plast).all() and np.ptp(plast[0]) > 0
    np.testing.assert_array_equal(last, plast)
    np.testing.assert_array_equal(toks, ptoks)


# -- (c) no value compiles ------------------------------------------------
def test_no_value_of_a_steps_arguments_compiles():
    """After one warm step, 50 steps that differ in tokens, index, seeds,
    temperatures and tables ask for no compilation."""
    model, params = gpt2_toy(jnp.bfloat16)
    dec = Decoder(model, params, num_slots=ROWS, max_seq_len=SEQ,
                  kv_page_size=PAGE)
    cache = dec.fresh_cache()
    pages = dec.pages_per_slot
    sd._step_operands.clear_cache()         # another test may have warmed it
    with _Compiles() as warm:
        _, cache, _ = dec.decode_step(
            cache, np.zeros(ROWS, np.int32), np.zeros(ROWS, np.int32),
            np.zeros(ROWS, np.float32), seeds=np.zeros(ROWS, np.uint32),
            block_tables=np.zeros((ROWS, pages), np.int32))
    # the counter is live: the body and the operands' program
    assert warm.backend >= 2 and dec.compiled_count == 1
    rng = np.random.default_rng(5)
    with _Compiles() as window:
        for i in range(50):
            toks, cache, _ = dec.decode_step(
                cache, rng.integers(0, 64, ROWS).astype(np.int32),
                rng.integers(0, SEQ, ROWS).astype(np.int32),
                (0.01 * i * rng.random(ROWS)).astype(np.float32),
                seeds=(2**32 - 1 - 7919 * i
                       - np.arange(ROWS)).astype(np.uint32),
                block_tables=rng.integers(
                    0, dec.pool_pages, (ROWS, pages)).astype(np.int32))
        np.asarray(toks)
    assert (window.requests, window.backend) == (0, 0)
    assert dec.compiled_count == 1


# -- (d) two programs a step ----------------------------------------------
def test_a_step_is_two_programs(tmp_path, decoder):
    dec = decoder
    tables = np.zeros((ROWS, dec.pages_per_slot), np.int32)
    tables[0] = 1 + np.arange(dec.pages_per_slot)
    box = [dec.fresh_cache()]

    def steps(step, n=4):
        for i in range(n):          # positions 1..4: no window closes
            toks, box[0], _ = step(
                box[0], np.full(ROWS, 3 + i, np.int32),
                np.array([1 + i, 0, 0], np.int32),
                np.full(ROWS, 0.5 * i, np.float32),
                np.full(ROWS, 3 + i, np.uint32), tables)
        return toks

    def parents(*a):
        return _parents_step(dec, *a)

    steps(dec.decode_step, 1), steps(parents, 1)            # warm
    assert _programs_run(tmp_path, lambda: steps(dec.decode_step),
                         "steps") == 2 * 4
    # the parent's way: the reshape and the keys each a program beside it
    assert _programs_run(tmp_path, lambda: steps(parents),
                         "parents") >= 3 * 4


# -- (e) under serve_tp ---------------------------------------------------
@pytest.mark.parametrize("temperature", [0.0, 0.8])
def test_the_sharded_body_takes_the_programs_operands(temperature):
    """Two devices on the model axis: the body, compiled inside
    ``shard_map``, takes the operands program's results as it took the
    ``jnp.asarray`` ones — the same tokens and logits, step after step."""
    model, params = gpt2_toy(jnp.float32)
    dec = Decoder(model, params, num_slots=ROWS, max_seq_len=SEQ,
                  kv_page_size=PAGE, mesh=serving_mesh(2))
    assert dec.tp == 2
    tables = np.zeros((ROWS, dec.pages_per_slot), np.int32)
    tables[0] = 1 + np.arange(dec.pages_per_slot)
    temps = np.array([temperature, 0.0, 0.0], np.float32)
    seeds = np.array([2**32 - 5, 0, 0], np.uint32)
    mine, theirs = dec.fresh_cache(), dec.fresh_cache()
    tokens = ptokens = np.array([9, 0, 0], np.int32)
    for i in range(3):
        index = np.array([i, 0, 0], np.int32)
        tokens, mine, last = dec.decode_step(
            mine, tokens, index, temps, seeds=seeds, block_tables=tables)
        ptokens, theirs, plast = _parents_step(
            dec, theirs, ptokens, index, temps, seeds, tables)
        np.testing.assert_array_equal(np.asarray(last), np.asarray(plast))
        np.testing.assert_array_equal(np.asarray(tokens),
                                      np.asarray(ptokens))
    assert np.isfinite(np.asarray(last)).all()
