"""A window of exact keys beside one summary a chunk, in ONE page pool.

An attention layer of this kind reads, at position ``t``, the exact keys
and values of its own window — positions ``[window * w, t]`` with ``w = t
// window`` — and, for every CLOSED window before it, one learned summary
a chunk of ``chunk`` positions (``window // chunk`` summaries a window):

    a_m = softmax over m in chunk c of (k_m . phi)
    k~_c = sum_m a_m k_m + mu            v~_c = sum_m a_m v_m

with ``phi``, ``mu`` [H, D] learned a head.  A summary is a (key, value)
row like any other, so the attention is ONE softmax over a row's
summaries and its window's tokens alike, and the cache is the whole-head
K and V pools ``[P, page, H, D]`` every other layer kind keeps.

**The table.**  With ``n = window // chunk // page`` pages of summaries a
window and ``window // page`` pages of tokens, a row at position ``window
* w + r`` uses table entries ``[0, n * w)`` for summaries and ``[n * w,
n * w + r // page + 1)`` for its open window: the ATTENDED rows are a
prefix of the table in table order, ``compact_index(t) + 1`` of them, so
the paged write, the paged kernel and a chunk's causal rule run on the
compact index as they are, and only the rotary position needs the true
one.  CLOSING window ``w`` (:func:`compact_window`, before the first
write into window ``w + 1``) reads its ``window // page`` pages and writes
the summaries over the first ``n`` of them; the next window's tokens then
overwrite the others, so a row grows by ``n`` pages a window.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def compact_index(index, window: int, chunk: int):
    """The table row (``entry * page + offset``) of position ``index``:
    ``window // chunk`` rows a closed window, then the offset in the open
    one.  Ints, numpy and jax arrays alike."""
    return index - (window - window // chunk) * (index // window)


def pages_for_length(length: int, page: int, window: int, chunk: int) -> int:
    """The most table entries a row of ``length`` positions ever uses:
    its last position's, or those of the last window it filled whole."""
    if (window // chunk) % page or window % page:
        raise ValueError(
            f"a window of {window} positions and its {window // chunk} "
            f"summaries are not whole pages of {page}")
    n, last = window // chunk // page, int(length) - 1
    w, r = last // window, last % window
    filled = n * (w - 1) + window // page if w else 0
    return max(n * w + r // page + 1, filled)


def row_pages(model, length: int, page: int) -> int:
    """Pages a row of ``length`` positions of ``model`` ever holds at pages
    of ``page``: every page of its length, or the model's own count where
    it keeps summaries (``summary_window``)."""
    window = getattr(model, "summary_window", None)
    if window is None:
        return -(-int(length) // page)
    return pages_for_length(length, page, window, model.summary_chunk)


def chunk_summaries(k, v, phi, mu, chunk: int):
    """k, v [..., T, H, D] (``T`` whole chunks, keys as cached: rotated);
    phi, mu [H, D] -> (k~, v~) [..., T // chunk, H, D] in k's dtype, the
    arithmetic in f32."""
    *lead, t, h, d = k.shape
    shape = (*lead, t // chunk, chunk, h, d)
    k32 = k.astype(jnp.float32).reshape(shape)
    v32 = v.astype(jnp.float32).reshape(shape)
    a = jax.nn.softmax(jnp.sum(k32 * phi.astype(jnp.float32), -1), axis=-2)
    ks = jnp.sum(a[..., None] * k32, -3) + mu.astype(jnp.float32)
    vs = jnp.sum(a[..., None] * v32, -3)
    return ks.astype(k.dtype), vs.astype(v.dtype)


def compact_window_reference(pool_k, pool_v, pages, phi, mu, *, chunk: int):
    """The oracle of :func:`compact_window`: a gather of the window's
    pages, :func:`chunk_summaries`, a scatter of the summaries' pages.  (On
    the TPU the gather of whole pages compiles to a ``while`` of one page a
    trip, K and V each: what the kernel is there to avoid.)"""
    page = pool_k.shape[1]
    tokens = pages.shape[0] * page
    ks, vs = chunk_summaries(
        pool_k[pages].reshape((tokens,) + pool_k.shape[2:]),
        pool_v[pages].reshape((tokens,) + pool_v.shape[2:]), phi, mu, chunk)
    n = tokens // chunk // page
    return (pool_k.at[pages[:n]].set(ks.reshape((n,) + pool_k.shape[1:])),
            pool_v.at[pages[:n]].set(vs.reshape((n,) + pool_v.shape[1:])))


def _window_compact_kernel(pages_ref, k_ref, v_ref, phi_ref, mu_ref,
                           ko_ref, vo_ref, *, chunk: int):
    """Grid (the window's pages): one page of K and of V in, ``[1, page,
    H, D]`` as stored, its ``page // chunk`` summaries out."""
    del pages_ref
    phi = phi_ref[...].astype(jnp.float32)
    mu = mu_ref[...].astype(jnp.float32)

    def summary(j, carry):
        rows = pl.ds(j * chunk, chunk)
        k = k_ref[0, rows].astype(jnp.float32)              # [chunk, H, D]
        v = v_ref[0, rows].astype(jnp.float32)
        score = jnp.sum(k * phi, axis=-1, keepdims=True)    # [chunk, H, 1]
        e = jnp.exp(score - jnp.max(score, axis=0, keepdims=True))
        a = e / jnp.sum(e, axis=0, keepdims=True)
        ko_ref[0, j] = (jnp.sum(a * k, axis=0) + mu).astype(ko_ref.dtype)
        vo_ref[0, j] = jnp.sum(a * v, axis=0).astype(vo_ref.dtype)
        return carry
    jax.lax.fori_loop(0, k_ref.shape[1] // chunk, summary, 0)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def window_compact(pool_k, pool_v, pages, phi, mu, *, chunk: int,
                   interpret: bool = False):
    """The kernel: the pools stay in HBM as stored and are updated IN
    PLACE (aliased).  The grid walks the window's pages, their ids
    scalar-prefetched: a grid point's K and V page are one block each, and
    its ``page // chunk`` summaries one block of the summaries' page.  A
    summaries' page is read (as the window's page it was) at a grid point
    no later than the first that writes it, and a grid point's blocks are
    in VMEM before its body runs: nothing is read after it was
    overwritten."""
    page = pool_k.shape[1]
    per_page = page // chunk            # summaries a page of tokens gives
    fill = page // per_page             # pages of tokens a summaries' page
    block = (1, page) + pool_k.shape[2:]
    out = (1, per_page) + pool_k.shape[2:]

    def whole(i, pages_ref):
        return (pages_ref[i], 0, 0, 0)

    def part(i, pages_ref):
        return (pages_ref[i // fill], i % fill, 0, 0)

    def const(i, pages_ref):
        return (0, 0)
    return pl.pallas_call(
        functools.partial(_window_compact_kernel, chunk=chunk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(pages.shape[0],),
            in_specs=[pl.BlockSpec(block, whole), pl.BlockSpec(block, whole),
                      pl.BlockSpec(phi.shape, const),
                      pl.BlockSpec(mu.shape, const)],
            out_specs=[pl.BlockSpec(out, part), pl.BlockSpec(out, part)]),
        out_shape=[jax.ShapeDtypeStruct(pool_k.shape, pool_k.dtype),
                   jax.ShapeDtypeStruct(pool_v.shape, pool_v.dtype)],
        # operands count the scalar-prefetched ids: pools 1, 2 -> outs 0, 1
        input_output_aliases={1: 0, 2: 1},
        interpret=interpret, name="window_compact",
    )(pages, pool_k, pool_v, phi, mu)


def compact_window(pool_k, pool_v, pages, phi, mu, *, chunk: int,
                   use_pallas=None):
    """Close one window of one layer: ``pages`` [window // page] int32 are
    its pages in table order; their tokens' summaries go over the first
    ``window // chunk // page`` of them.  Returns the two pools.
    ``use_pallas``: None = the kernel on the TPU and the oracle elsewhere,
    True, "interpret", False."""
    if pool_k.shape[1] % chunk:
        raise ValueError(f"a page of {pool_k.shape[1]} tokens is not whole "
                         f"chunks of {chunk}")
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if not use_pallas:
        return compact_window_reference(pool_k, pool_v, pages, phi, mu,
                                        chunk=chunk)
    return window_compact(pool_k, pool_v, pages, phi, mu, chunk=chunk,
                          interpret=use_pallas == "interpret")
