"""Delta-rule linear attention with a decay a channel: the state of a head
is a MATRIX, rewritten by every token.

For one head, keys and queries of ``dk`` channels and values of ``dv``,
``S_0 = 0``:

    S_t = (I - beta_t k_t k_t^T) Diag(exp(a_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

with ``a_t`` [dk] <= 0 the log of the decay a channel and ``beta_t`` in
[0, 1] the write strength.  A token with ``beta = 0`` and ``a = 0`` leaves
the state as it was: that is how a tail-padded chunk pads.

Every form below keeps the state TRANSPOSED, ``M = S^T`` ``[..., dv, dk]``:
the decay scales the key channel, which is then the lane axis, so it
broadcasts over rows and the kernel needs no transpose.  All arithmetic is
float32 at full matmul precision; a pool stores ``M`` in its own dtype.

Three forms, the same numbers (``tests/test_linear_state.py``):

``recurrent``   the definition, a ``lax.scan`` over tokens;
``chunked``     blocks of ``block`` tokens: inside a block the tokens meet
                through ``[block, block]`` matrices, between blocks the
                state is carried — a prefill chunk's form.  ``exp`` of a
                block's summed log decay is taken relative to the block's
                own middle, so that at a floor of -5 a token a block of 16
                stays inside float32 (``exp(+-40)``) in both directions;
``step``        one token; :func:`paged_step` runs it through a pool of
                state entries indexed by page id, and
                :func:`linear_state_decode` is that as a kernel.

**The kernel's arithmetic.**  With ``M`` the entry AS STORED, ``alpha =
exp(a)``, ``kb = beta k`` and ``w = v - u``, both reductions of ``step`` can
be taken over ``M`` before the decay and before the write:

    u = (M . alpha) k = M (alpha . k)
    o = new q         = M (alpha . q) + w (kb . q),   new = M . alpha + w kb^T

so a head's two reductions are ONE product on the MXU, of the token's rows
``alpha . k`` and ``alpha . q`` against the head's matrix as the copy left
it, and ``u``, ``v``, ``w`` and ``o`` are ROWS over ``dv``; the VPU keeps
what is elementwise (upcast, decay, the rank-one write, round).  The
product is float32 arithmetic, not bfloat16's: a float32 is cut into three
bfloat16 pieces that sum to it exactly (:func:`_pieces`), a bfloat16 x
bfloat16 product is exact in float32 and the MXU accumulates in float32,
so ``M x`` of a bfloat16 ``M`` against the three pieces of ``x`` is what
``Precision.HIGHEST`` gives at half its passes — the left operand has no
residue.  **The form follows ``pool.dtype``**: a bfloat16 pool's matrix
goes to the MXU as stored (kernel ``linear_state_decode_mxu1x3``); any
other pool's is cut in three as well, nine exact products
(``..._mxu3x3``).  No flag chooses.

**No erase term** (``beta`` None in every form: a linear-attention layer
with a constant decay a head and no write gate):

    S_t = Diag(exp(a_t)) S_{t-1} + k_t v_t^T,    o_t = S_t^T q_t

is the same arithmetic with ``u = 0`` (so ``w = v``) and ``kb = k``; the
blocked form needs no triangular inverse and the kernel ONE reduction, ``M
(alpha . q)``, where the delta rule has two (kernel
``linear_state_decode_noerase_mxu1x3`` / ``..._mxu3x3``).  A token with
``k = 0`` and ``a = 0`` leaves the state as it was.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HI = jax.lax.Precision.HIGHEST
BLOCK = 16      # tokens a block of the chunked form: 16 x 5 = 80 < 87
# ... and of its no-erase form, whose block costs no inverse: a log decay
# of at most 1 a token (lightning attention's slopes) is 32 from the middle
NOERASE_BLOCK = 64


def step(state, q, k, v, a, beta=None):
    """One token.  state [..., dv, dk] f32; q, k, a [..., dk]; v [..., dv];
    beta [...] (None: no erase term).  Returns (o [..., dv], new state)."""
    md = state * jnp.exp(a)[..., None, :]
    if beta is None:
        new = md + v[..., None] * k[..., None, :]
        return jnp.einsum("...vk,...k->...v", new, q, precision=_HI), new
    u = jnp.einsum("...vk,...k->...v", md, k, precision=_HI)
    new = md + (beta[..., None] * (v - u))[..., None] * k[..., None, :]
    return jnp.einsum("...vk,...k->...v", new, q, precision=_HI), new


def recurrent(q, k, v, a, beta=None, state=None):
    """The definition.  q, k, a [B, S, H, dk]; v [B, S, H, dv]; beta
    [B, S, H] (None: no erase term); state [B, H, dv, dk] (None: zeros).
    Returns (o [B, S, H, dv], the state after the last token)."""
    b, _, h, dk = q.shape
    if state is None:
        state = jnp.zeros((b, h, v.shape[-1], dk), jnp.float32)

    def one(m, xs):
        o, m = step(m, *xs)
        return m, o
    xs = (q, k, v, a) if beta is None else (q, k, v, a, beta)
    state, o = jax.lax.scan(
        one, state, tuple(jnp.moveaxis(x.astype(jnp.float32), 1, 0)
                          for x in xs))
    return jnp.moveaxis(o, 0, 1), state


def _unit_lower_inverse(low):
    """``(I + low)^-1`` for strictly lower-triangular ``low`` [..., C, C]:
    ``low`` is nilpotent, so the inverse is the finite product ``(I - L)(I
    + L^2)(I + L^4)...`` — matrix products only."""
    c = low.shape[-1]
    eye = jnp.eye(c, dtype=low.dtype)
    inv, power, n = eye - low, low, 2
    while n < c:
        power = jnp.matmul(power, power, precision=_HI)
        inv = jnp.matmul(inv, eye + power, precision=_HI)
        n *= 2
    return inv


def chunked(q, k, v, a, beta=None, state=None, *, block: int = BLOCK,
            emit_every: int = 0):
    """The blocked form of :func:`recurrent` for ``S`` a multiple of
    ``block``.  ``emit_every`` (tokens, a multiple of ``block`` that
    divides S; 0: S) also returns the state after every that many tokens.
    ``beta`` None: no erase term — a block's tokens meet through ``seen``
    alone, no triangular inverse, and ``block`` may be as long as its
    summed log decay stays inside float32 from the middle.
    Returns (o [B, S, H, dv], states [B, S // emit_every, H, dv, dk]); the
    last of ``states`` is the state after the call."""
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    emit_every = emit_every or s
    if s % block or emit_every % block or s % emit_every:
        raise ValueError(f"{s} tokens in blocks of {block}, states every "
                         f"{emit_every}")
    n, c = s // block, block
    if state is None:
        state = jnp.zeros((b, h, dv, dk), jnp.float32)

    def blocks(x):      # [B, S, H, ...] -> [N, B, H, C, ...]
        x = x.astype(jnp.float32).reshape((b, n, c, h) + x.shape[3:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 2), 1, 0)
    erase = beta is not None
    q, k, v, a = (blocks(x) for x in (q, k, v, a))
    run = jnp.cumsum(a, axis=3)                     # log decay, block start
    mid = run - run[:, :, :, c // 2:c // 2 + 1]     # ... from the middle
    k_up, k_down = k * jnp.exp(mid), k * jnp.exp(-mid)
    lower = jnp.tril(jnp.ones((c, c), jnp.float32), -1)
    gamma = jnp.exp(run)
    if erase:
        beta = blocks(beta)
        meet = jnp.einsum("nbhik,nbhjk->nbhij", k_up, k_down, precision=_HI)
        inv = _unit_lower_inverse(beta[..., None] * meet * lower)
        w = jnp.matmul(inv, beta[..., None] * k * gamma, precision=_HI)
        u0 = jnp.matmul(inv, beta[..., None] * v, precision=_HI)
    else:
        u0 = v
    seen = jnp.einsum("nbhik,nbhjk->nbhij", q * jnp.exp(mid), k_down,
                      precision=_HI) * (lower + jnp.eye(c))
    q_in = q * gamma
    total = run[:, :, :, -1]                        # [N, B, H, dk]
    k_out = k * jnp.exp(total[:, :, :, None] - run)

    def one(m, xs):
        *w_, u, seen_, q_, k_, total_ = xs
        if erase:
            u = u - jnp.einsum("bhck,bhvk->bhcv", w_[0], m, precision=_HI)
        o = (jnp.einsum("bhck,bhvk->bhcv", q_, m, precision=_HI)
             + jnp.matmul(seen_, u, precision=_HI))
        m = (m * jnp.exp(total_)[:, :, None, :]
             + jnp.einsum("bhcv,bhck->bhvk", u, k_, precision=_HI))
        return m, o

    def page(m, xs):
        m, o = jax.lax.scan(one, m, xs)
        return m, (o, m)
    per = emit_every // block
    _, (o, states) = jax.lax.scan(
        page, state, tuple(x.reshape((n // per, per) + x.shape[1:])
                           for x in ((w,) if erase else ())
                           + (u0, seen, q_in, k_out, total)))
    o = o.reshape((n,) + o.shape[2:])               # [N, B, H, C, dv]
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3).reshape(b, s, h, dv)
    return o, jnp.moveaxis(states, 0, 1)


def _pages(block_table, index, page_size: int):
    """(page a row's carry is read from, page its entry goes to) for a
    token at ``index`` [B]: the page of the position before it, and its
    own — the same page except at a boundary."""
    m = block_table.shape[1]
    src = jnp.take_along_axis(
        block_table, (jnp.maximum(index - 1, 0) // page_size)[:, None], 1)
    dst = jnp.take_along_axis(
        block_table, jnp.minimum(index // page_size, m - 1)[:, None], 1)
    return src[:, 0], dst[:, 0]


def paged_step(pool, q, k, v, a, beta, block_table, index, *,
               page_size: int):
    """One token a row through a pool of state entries ``[P, H, dv, dk]``
    whose entry for page ``p`` is the state at the newest token written in
    ``p``: a row's carry is the entry of the page that holds ``index - 1``
    (zeros at ``index`` 0), its new state goes to the page that holds
    ``index``.  q, k, a [B, H, dk]; v [B, H, dv]; beta [B, H] (None: no
    erase term).  Returns (o [B, H, dv] f32, the pool).  The oracle of
    :func:`linear_state_decode`."""
    src, dst = _pages(block_table, index, page_size)
    carry = jnp.where((index > 0)[:, None, None, None],
                      pool[src].astype(jnp.float32), 0.0)
    xs = (q, k, v, a) if beta is None else (q, k, v, a, beta)
    o, new = step(carry, *(x.astype(jnp.float32) for x in xs))
    return o, pool.at[dst].set(new.astype(pool.dtype))


def _pieces(x):
    """Three bfloat16 arrays that sum to float32 ``x`` EXACTLY: its top
    eight significant bits, the top eight of what is left, and the (at
    most eight) left after that.  Cut by a mask on the bits and not by a
    rounding: a compiler that allows itself excess precision drops an
    ``f32 -> bf16 -> f32`` round trip, and the two lower pieces with it
    (the TPU's does: the first sweep of PR 43 read bfloat16's error)."""
    def top(t):
        bits = jax.lax.bitcast_convert_type(t, jnp.uint32)
        return jax.lax.bitcast_convert_type(
            bits & jnp.uint32(0xFFFF0000), jnp.float32)
    x = x.astype(jnp.float32)
    p1 = top(x)
    p2 = top(x - p1)
    return tuple(p.astype(jnp.bfloat16) for p in (p1, p2, x - p1 - p2))


def _row_pipeline(tbl_ref, idx_ref, pool_hbm, pool_out, sbuf, obuf, sem_in,
                  sem_out, advance, *, page_size: int):
    """Grid (B,): row ``b``'s state — every head's ``[dv, dk]`` entry, one
    contiguous block of its page — is copied in to ``sbuf[slot]``,
    ``advance(slot)`` leaves its successor in ``obuf[slot]``, and that is
    copied out to the page that holds ``index``.  Row ``b + 1``'s copy-in
    is started before row ``b``'s arithmetic and row ``b``'s copy-out is
    waited for two rows later, so the copies' latency hides behind the
    neighbours' work: the kernel's floor is the bytes of the state, read
    once and written once.  A row at ``index`` 0 has no carry: its
    ``sbuf[slot]`` is zeroed before ``advance``."""
    b, rows = pl.program_id(0), pl.num_programs(0)
    m_pages = tbl_ref.shape[1]
    slot = b % 2

    def fetch(r, s):
        page = tbl_ref[r, jnp.maximum(idx_ref[r] - 1, 0) // page_size]
        return pltpu.make_async_copy(pool_hbm.at[page], sbuf.at[s],
                                     sem_in.at[s])

    def store(r, s):
        page = tbl_ref[r, jnp.minimum(idx_ref[r] // page_size, m_pages - 1)]
        return pltpu.make_async_copy(obuf.at[s], pool_out.at[page],
                                     sem_out.at[s])

    @pl.when(b == 0)
    def _first():
        fetch(0, 0).start()

    @pl.when(b + 1 < rows)
    def _next():
        fetch(b + 1, 1 - slot).start()

    fetch(b, slot).wait()

    @pl.when(b >= 2)
    def _reuse():
        store(b - 2, slot).wait()

    @pl.when(idx_ref[b] <= 0)
    def _no_carry():
        sbuf[slot] = jnp.zeros(sbuf.shape[1:], sbuf.dtype)

    advance(slot)
    store(b, slot).start()

    @pl.when(b == rows - 1)
    def _drain():
        store(b, slot).wait()

        @pl.when(b >= 1)
        def _():
            store(b - 1, 1 - slot).wait()


def _state_call(kernel, pool, block_table, index, operands, in_specs,
                out_spec, out_shape, *, name: str, interpret: bool = False):
    """``kernel`` over a grid of rows, table and positions prefetched, the
    pool left in HBM and aliased to the second result, two slots of a
    row's matrices in and two out."""
    entry = pool.shape[1:]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(out_shape[0],),
        in_specs=list(in_specs) + [pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[out_spec, pl.BlockSpec(memory_space=pl.ANY)],
        scratch_shapes=[pltpu.VMEM((2,) + entry, pool.dtype),
                        pltpu.VMEM((2,) + entry, pool.dtype),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SemaphoreType.DMA((2,))])
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(out_shape, jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={2 + len(operands): 1},
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=32 * 2 ** 20),
        name=name,
    )(jnp.asarray(block_table, jnp.int32), jnp.asarray(index, jnp.int32),
      *operands, pool)


_NT = (((1,), (1,)), ((), ()))      # [r, k] x [v, k] -> [r, v]


def _decode_kernel(tbl_ref, idx_ref, q_ref, k_ref, kb_ref, alpha_ref, v_ref,
                   pool_hbm, o_ref, pool_out, sbuf, obuf, sem_in, sem_out,
                   *, page_size: int, split: bool):
    """One row's heads, ``th`` (8: a vreg's sublanes) a tile.  A tile's
    ``alpha . k`` and ``alpha . q`` are two ``[th, dk]`` vregs; their six
    pieces stacked are the ``[6 th, dk]`` bfloat16 operand that meets EVERY
    head of the tile (head ``j`` reads row ``j`` of each piece's block of
    the result, already on sublane ``j`` of the tile's ``u`` and of its
    ``M (alpha . q)``: no shuffle, and the MXU has rows to spare beside a
    128-row weight load).  ``v``, ``w`` and ``o`` are then ONE ``[th, dv]``
    tile each, ``w``'s tile is transposed ONCE for the tile's ``th``
    columns, and each head's entry is decayed, written and rounded on the
    VPU.  ``split``: the matrix is cut in three pieces too (a pool that is
    not bfloat16).  48 lane reductions a head (PR 39's form) became one
    weight load, an eighth of a transposition and 16 lane broadcasts: 0.699
    -> 0.315 ms a call of 96 rows x 32 heads x 128 x 128 bfloat16, 35 ->
    78 % of the bytes' roofline (``docs/pr43_state_kernel_sweep.jsonl``);
    two tiles an iteration of the loop so that one's products run under
    the other's writes (one: 0.336 ms; the per-head form of the same
    arithmetic without unrolling: 1.40 ms, the MXU's latency a head)."""
    heads, dv = sbuf.shape[1:3]
    th = math.gcd(heads, 8)
    tiles = 1 if heads // th % 2 else 2
    sub = jax.lax.broadcasted_iota(jnp.int32, (th, dv), 0)
    f32 = jnp.float32

    def advance(slot):
        def tile(t):
            at = pl.multiple_of(t * th, th)
            rows = pl.ds(at, th)
            alpha, kb, q = alpha_ref[rows, :], kb_ref[rows, :], q_ref[rows, :]
            lhs = jnp.concatenate(
                _pieces(alpha * k_ref[rows, :]) + _pieces(alpha * q), 0)
            u = p = jnp.zeros((th, dv), f32)
            for j in range(th):
                m = sbuf[slot, at + j]
                res = sum(jax.lax.dot_general(lhs, part, _NT,
                                              preferred_element_type=f32)
                          for part in (_pieces(m) if split else (m,)))
                res = res.reshape(6, th, dv)
                u = jnp.where(sub == j, res[0] + res[1] + res[2], u)
                p = jnp.where(sub == j, res[3] + res[4] + res[5], p)
            w = v_ref[rows, :] - u
            o_ref[rows, :] = p + w * jnp.sum(kb * q, axis=1, keepdims=True)
            wt = jnp.transpose(w)                       # [dv, th]
            for j in range(th):
                row = pl.ds(at + j, 1)
                new = (sbuf[slot, at + j].astype(f32) * alpha_ref[row, :]
                       + wt[:, j:j + 1] * kb_ref[row, :])
                obuf[slot, at + j] = new.astype(obuf.dtype)

        def group(i, carry):
            for j in range(tiles):
                tile(i * tiles + j)
            return carry
        jax.lax.fori_loop(0, heads // (th * tiles), group, 0)
    _row_pipeline(tbl_ref, idx_ref, pool_hbm, pool_out, sbuf, obuf, sem_in,
                  sem_out, advance, page_size=page_size)


def _decode_kernel_noerase(tbl_ref, idx_ref, q_ref, k_ref, alpha_ref, v_ref,
                           pool_hbm, o_ref, pool_out, sbuf, obuf, sem_in,
                           sem_out, *, page_size: int, split: bool):
    """:func:`_decode_kernel` without the erase term (``u = 0``, so ``w =
    v``, and ``kb = k``): a tile's ONE reduction ``M (alpha . q)`` is the
    ``[3 th, dk]`` bfloat16 pieces of ``alpha . q`` against every head of
    the tile; ``o = M (alpha . q) + v (k . q)``, ``new = M . alpha + v
    k^T``.  The same tiles, the same pipeline."""
    heads, dv = sbuf.shape[1:3]
    th = math.gcd(heads, 8)
    tiles = 1 if heads // th % 2 else 2
    sub = jax.lax.broadcasted_iota(jnp.int32, (th, dv), 0)
    f32 = jnp.float32

    def advance(slot):
        def tile(t):
            at = pl.multiple_of(t * th, th)
            rows = pl.ds(at, th)
            k, q, v = k_ref[rows, :], q_ref[rows, :], v_ref[rows, :]
            lhs = jnp.concatenate(_pieces(alpha_ref[rows, :] * q), 0)
            p = jnp.zeros((th, dv), f32)
            for j in range(th):
                m = sbuf[slot, at + j]
                res = sum(jax.lax.dot_general(lhs, part, _NT,
                                              preferred_element_type=f32)
                          for part in (_pieces(m) if split else (m,)))
                res = res.reshape(3, th, dv)
                p = jnp.where(sub == j, res[0] + res[1] + res[2], p)
            o_ref[rows, :] = p + v * jnp.sum(k * q, axis=1, keepdims=True)
            vt = jnp.transpose(v)                       # [dv, th]
            for j in range(th):
                row = pl.ds(at + j, 1)
                new = (sbuf[slot, at + j].astype(f32) * alpha_ref[row, :]
                       + vt[:, j:j + 1] * k_ref[row, :])
                obuf[slot, at + j] = new.astype(obuf.dtype)

        def group(i, carry):
            for j in range(tiles):
                tile(i * tiles + j)
            return carry
        jax.lax.fori_loop(0, heads // (th * tiles), group, 0)
    _row_pipeline(tbl_ref, idx_ref, pool_hbm, pool_out, sbuf, obuf, sem_in,
                  sem_out, advance, page_size=page_size)


@functools.partial(jax.jit, static_argnames=("page_size", "interpret"))
def linear_state_decode(pool, q, k, v, a, beta, block_table, index, *,
                        page_size: int, interpret: bool = False):
    """:func:`paged_step` as a kernel, the pool updated IN PLACE (it is
    aliased to the result; the serving body donates its cache).  Rows with
    an all-zero table read and write the scratch page 0.  Jitted, so that
    the layers of a model share one lowering.  The kernel's name says which
    form ``pool.dtype`` chose, and ``noerase`` where ``beta`` is None (the
    module's docstring)."""
    b, h, dk = q.shape
    dv = v.shape[-1]
    f32 = jnp.float32
    split = pool.dtype != jnp.bfloat16

    def rows(lanes):
        return pl.BlockSpec((None, h, lanes), lambda r, tbl, idx: (r, 0, 0))
    if beta is None:
        q, k, v, a = (x.astype(f32) for x in (q, k, v, a))
        return _state_call(
            functools.partial(_decode_kernel_noerase, page_size=page_size,
                              split=split),
            pool, block_table, index, (q, k, jnp.exp(a), v),
            [rows(dk)] * 3 + [rows(dv)], rows(dv), (b, h, dv),
            name="linear_state_decode_noerase_mxu" + ("3x3" if split
                                                      else "1x3"),
            interpret=interpret)
    q, k, v, a, beta = (x.astype(f32) for x in (q, k, v, a, beta))
    return _state_call(
        functools.partial(_decode_kernel, page_size=page_size, split=split),
        pool, block_table, index,
        (q, k, beta[..., None] * k, jnp.exp(a), v),
        [rows(dk)] * 4 + [rows(dv)], rows(dv), (b, h, dv),
        name="linear_state_decode_mxu" + ("3x3" if split else "1x3"),
        interpret=interpret)
