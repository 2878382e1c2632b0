"""``paged_flash_decode`` with grouped-query heads and a window, through
the Pallas interpreter, against the gather oracle (``paged_attention``
with the same two arguments)."""

import importlib

import jax.numpy as jnp
import numpy as np
import pytest

pa = importlib.import_module("dtf_tpu.ops.paged_attention")

D = 128


def _case(b, s, hq, hkv, page, m, index, dtype, seed=0):
    rng = np.random.default_rng(seed)
    pool = 1 + b * m
    pk = jnp.asarray(rng.normal(size=(pool, page, hkv, D)), dtype)
    pv = jnp.asarray(rng.normal(size=(pool, page, hkv, D)), dtype)
    q = jnp.asarray(rng.normal(size=(b, s, hq, D)), dtype)
    table = jnp.asarray(1 + rng.permutation(b * m).reshape(b, m), jnp.int32)
    return q, pk, pv, table, jnp.asarray(index, jnp.int32)


@pytest.mark.parametrize("window", [None, 24], ids=["full", "window24"])
@pytest.mark.parametrize("hq,hkv", [(14, 2), (4, 4)],
                         ids=["group7", "group1"])
@pytest.mark.parametrize("s,index", [(1, [0, 37, 62]), (16, [16, 48, 0])],
                         ids=["decode", "chunk"])
def test_kernel_equals_gather_oracle(s, index, hq, hkv, window):
    """Group 7 (the seven query heads of a KV head as rows of one
    stream) and group 1, window set and unset, a decode step of rows at
    different lengths and a continuation chunk, float32."""
    args = _case(3, s, hq, hkv, 8, 8, index, jnp.float32)
    got = pa.paged_flash_decode(*args, interpret=True, window=window)
    want = pa.paged_attention(*args, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-6, atol=2e-6)


@pytest.mark.parametrize("s,window,index", [
    (1, 24, [900, 300]), (8, 100, [896, 304]), (8, None, [896, 304])],
    ids=["decode_window", "chunk_window", "chunk_full"])
def test_window_starts_at_its_first_block(s, window, index):
    """Histories of several blocks (128 pages of 8: blocks of 256
    tokens): under a window the page loop starts at the block the chunk's
    first query can see — blocks 3 and 1 here — and the result is the
    oracle's over the whole table."""
    args = _case(2, s, 14, 2, 8, 128, index, jnp.float32, seed=1)
    got = pa.paged_flash_decode(*args, interpret=True, window=window)
    want = pa.paged_attention(*args, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-6, atol=2e-6)


@pytest.mark.parametrize("window", [None, 200], ids=["full", "window200"])
def test_a_chunk_whose_rows_outgrow_vmem_goes_in_row_blocks(window):
    """28 query heads over 4 KV heads at a 128-token chunk are 896 rows a
    KV head: more than the budget holds, so the grid's second axis walks
    blocks of them (bf16, as served)."""
    args = _case(1, 128, 28, 4, 16, 64, [768], jnp.bfloat16, seed=2)
    got = pa.paged_flash_decode(*args, interpret=True, window=window)
    want = pa.paged_attention(*args, window=window)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-2, atol=2e-2)
