"""Pallas paged flash-decode kernel: interpret-mode validation on CPU.

The kernel streams each row's LIVE pages out of the pool as it is
stored ([P, page, H, Dh], in HBM): blocks of whole pages by manual DMA
into a double buffer, page ids from the scalar-prefetched block table,
a loop whose trip count is the row's own length.  Tier-1 pins, per the
same contract the flash kernels use (ops/flash_attention.py):

  - kernel ≡ blockwise reference to float ulps (identical accumulation
    order — the reference takes the kernel's own block size — and
    identical math; only the order inside one matmul's sum differs);
  - kernel ≡ the `paged_attention` gather oracle to float ulps with
    argmax equality — the sampling-visible quantity;
  - the dispatch (`paged_attention_auto`) routes kernel-on-TPU /
    gather-elsewhere, with "interpret" forcing the kernel through the
    Pallas interpreter (this file's mode);
  - end-to-end: an engine generation with use_pallas="interpret"
    reproduces the gather path's exact greedy tokens.

Geometry matrix: index values 1 / page−1 / page / 3·page+7 — the same
page-boundary edges the paged gather tests pin — at decode (S=1) and
chunk (S=page-multiple) query shapes; then what a streaming kernel can
get wrong: lengths around a BLOCK boundary, a full row beside an idle
one, page ids in any order, a page two rows share, chunks that start
inside a block, and NaN in every page no row should read (the
interpreter hands out NaN-filled scratch, so a stale buffer shows).
f32 and bf16 pools (bf16 packs two heads' rows to a word), one and
several head groups, head counts that tile and that do not.

Two forms of the block's math share the kernel (``_plan`` names which):
few query rows over many heads score the stored ``[T·H, Dh]`` block all
heads at once, the other heads' columns masked; everything else goes
head by head.  At this file's 4 and 8 heads a decode step (S = 1) takes
the first at 8 and the second at 4, so the streaming cases above run
each; the cases at the end pin the all-heads form itself — every head
count, grouped queries, a window, each side of ``_plan``'s bounds, and
what the mask alone must keep apart.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import importlib

from dtf_tpu.models.transformer import TransformerLM
from dtf_tpu.serve import ServeEngine

# the ops package re-exports the `paged_attention` FUNCTION under the
# module's name — import the module itself for the kernel symbols
pa = importlib.import_module("dtf_tpu.ops.paged_attention")

PAGE = 8
LENS = (1, PAGE - 1, PAGE, 3 * PAGE + 7)        # 1, 7, 8, 31
POOL, M, H, D = 24, 6, 4, 16                     # M pages cover 48 tokens


def _case(seed, b, s):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((b, s, H, D)), jnp.float32)
    pk = jnp.asarray(rng.standard_normal((POOL, PAGE, H, D)), jnp.float32)
    pv = jnp.asarray(rng.standard_normal((POOL, PAGE, H, D)), jnp.float32)
    # distinct non-scratch pages WITHIN each row (an engine block row
    # never repeats a page); rows may overlap — that's prefix sharing
    tbl = np.stack([rng.choice(np.arange(1, POOL), M, replace=False)
                    for _ in range(b)])
    return q, pk, pv, jnp.asarray(tbl, jnp.int32)


@pytest.mark.parametrize("index", LENS)
def test_kernel_matches_reference_decode(index):
    """S=1 (decode step) at every page-geometry edge vs the blockwise
    reference: same per-page online-softmax math, so agreement is at
    XLA's batched-vs-per-program einsum reassociation level (float
    ulps — the reference docstring's documented-only divergence), with
    identical argmax."""
    q, pk, pv, tbl = _case(index, 3, 1)
    idx = jnp.full((3,), index, jnp.int32)
    kern = np.asarray(
        pa.paged_flash_decode(q, pk, pv, tbl, idx, interpret=True))
    ref = np.asarray(pa.paged_flash_decode_reference(q, pk, pv, tbl, idx))
    np.testing.assert_allclose(kern, ref, rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(kern.argmax(-1), ref.argmax(-1))


@pytest.mark.parametrize("index", LENS)
def test_kernel_matches_gather_oracle_decode(index):
    """Kernel vs the materialized-gather oracle: float-ulp close, and
    the argmax over the head-output features — the quantity greedy
    sampling consumes downstream — identical."""
    q, pk, pv, tbl = _case(100 + index, 3, 1)
    idx = jnp.full((3,), index, jnp.int32)
    kern = np.asarray(
        pa.paged_flash_decode(q, pk, pv, tbl, idx, interpret=True))
    oracle = np.asarray(pa.paged_attention(q, pk, pv, tbl, idx))
    np.testing.assert_allclose(kern, oracle, rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(kern.argmax(-1), oracle.argmax(-1))


@pytest.mark.parametrize("start", [0, PAGE, 3 * PAGE])
def test_kernel_matches_gather_oracle_chunk(start):
    """S=page-multiple (continuation prefill chunk) at several chunk
    starts; the gather arm gets the STATIC window trim the engine
    would pass, the kernel's fused dynamic skip must agree."""
    s = 2 * PAGE
    q, pk, pv, tbl = _case(start + 7, 2, s)
    idx = jnp.full((2,), start, jnp.int32)
    window = (start + s) // PAGE
    kern = np.asarray(
        pa.paged_flash_decode(q, pk, pv, tbl, idx, interpret=True))
    oracle = np.asarray(pa.paged_attention(
        q, pk, pv, tbl[:, :window], idx))
    np.testing.assert_allclose(kern, oracle, rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(kern.argmax(-1), oracle.argmax(-1))


def test_kernel_mixed_row_lengths_and_idle_rows():
    """One batch mixing all geometry edges plus an idle row (all-zeros
    block table, index 0 — the engine's inactive-slot shape): each
    row's output matches the oracle's."""
    b = len(LENS) + 1
    q, pk, pv, tbl = _case(42, b, 1)
    tbl = tbl.at[-1].set(0)                      # idle row → scratch page
    idx = jnp.asarray(list(LENS) + [0], jnp.int32)
    kern = np.asarray(
        pa.paged_flash_decode(q, pk, pv, tbl, idx, interpret=True))
    oracle = np.asarray(pa.paged_attention(q, pk, pv, tbl, idx))
    np.testing.assert_allclose(kern, oracle, rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# what a kernel that streams blocks of live pages can get wrong
# ---------------------------------------------------------------------------

def _geometry(seed, lens, s, *, heads=H, page=PAGE, m=M, pool=None,
              dtype=jnp.float32):
    """Rows of the given cached lengths (``index``), each with its own
    pages in shuffled order: (q, pool_k, pool_v, table, index)."""
    rng = np.random.default_rng(seed)
    b = len(lens)
    pool = pool or 1 + b * m
    q = jnp.asarray(rng.standard_normal((b, s, heads, D)), dtype)
    pk = jnp.asarray(rng.standard_normal((pool, page, heads, D)), dtype)
    pv = jnp.asarray(rng.standard_normal((pool, page, heads, D)), dtype)
    tbl = rng.permutation(np.arange(1, 1 + b * m)).reshape(b, m)
    return (q, pk, pv, jnp.asarray(tbl, jnp.int32),
            jnp.asarray(lens, jnp.int32))


def _pages_per_block(monkeypatch, args, ppb):
    """Make the plan take ``ppb`` pages a block for these shapes: the
    budget is the plan's one knob, so the test turns that."""
    q, pk = args[0], args[1]
    (_, s, h, d), page, itemsize = q.shape, pk.shape[1], pk.dtype.itemsize
    h = pa._tiled_heads(h, itemsize)
    monkeypatch.setattr(pa, "_VMEM_BUDGET",
                        2 * 4 * ppb * page * h * d * itemsize)
    plan = pa._plan(s, h, d, page, args[3].shape[1], itemsize)
    assert plan[0] == ppb, plan
    return plan


# the streaming cases run in both forms of the block's math: a decode step
# goes head by head at 4 heads and all heads at once at 8 (``_plan``)
both_forms = pytest.mark.parametrize("heads", [4, 8],
                                     ids=["head_by_head", "all_heads"])


def _kernel(*args, **kw):
    """The kernel through the interpreter, traced anew: the jitted entry
    would hand back the trace of an earlier test with these shapes,
    whatever budget or bound this test has set since."""
    return pa.paged_flash_decode.__wrapped__(*args, interpret=True, **kw)


def _check(args, rtol=1e-6, atol=1e-6):
    """Kernel against the gather oracle AND the blockwise reference."""
    kern = np.asarray(_kernel(*args), np.float32)
    oracle = np.asarray(pa.paged_attention(*args), np.float32)
    ref = np.asarray(pa.paged_flash_decode_reference(*args), np.float32)
    assert np.isfinite(kern).all()
    np.testing.assert_allclose(kern, oracle, rtol=rtol, atol=atol)
    np.testing.assert_allclose(kern, ref, rtol=rtol, atol=atol)
    return kern


@both_forms
@pytest.mark.parametrize("ppb", [2, 4])
@pytest.mark.parametrize("edge", [-1, 0, 1])
def test_kernel_lengths_around_a_block_boundary(monkeypatch, ppb, edge,
                                                heads):
    """Keys one under, on and one over a block of ``ppb`` pages: the
    trip count steps from 1 to 2 exactly there, and the first page of
    the second block holds one live key."""
    keys = ppb * PAGE + edge
    args = _geometry(50 + edge, [keys - 1, 2 * ppb * PAGE - 1 + edge], 1,
                     m=16, heads=heads)
    assert _pages_per_block(monkeypatch, args, ppb)[2] is (heads == 8)
    _check(args)


@both_forms
def test_kernel_full_row_beside_an_idle_row(monkeypatch, heads):
    """A 2,048-token row (all 128 pages of 16, 16 blocks of 8) beside
    an idle row (all-zeros table, index 0: position 0 of the scratch
    page, as the engine passes it)."""
    args = list(_geometry(3, [2047, 0], 1, page=16, m=128, heads=heads))
    args[3] = args[3].at[1].set(0)
    assert _pages_per_block(monkeypatch, args, 8)[2] is (heads == 8)
    _check(args)


@both_forms
@pytest.mark.parametrize("order", ["descending", "strided"])
def test_kernel_page_ids_in_any_order(monkeypatch, order, heads):
    """Page ids descending, and every third page of a pool three times
    the rows' need: the kernel reads ids from the table, never
    neighbours in the pool."""
    lens = [45, 20, 33]
    args = list(_geometry(8, lens, 1, pool=1 + 9 * M, heads=heads))
    ids = np.arange(1, 1 + 3 * M)
    ids = ids[::-1] if order == "descending" else 3 * ids - 1
    args[3] = jnp.asarray(ids.reshape(3, M), jnp.int32)
    assert _pages_per_block(monkeypatch, args, 2)[2] is (heads == 8)
    _check(args)


@both_forms
def test_kernel_page_shared_by_two_rows(monkeypatch, heads):
    """Prefix sharing: rows 0 and 1 hold the same two first pages and
    differ after; both read them, at different lengths."""
    args = list(_geometry(21, [40, 19, 30], 1, heads=heads))
    args[3] = args[3].at[1, :2].set(args[3][0, :2])
    assert _pages_per_block(monkeypatch, args, 2)[2] is (heads == 8)
    kern = _check(args)
    assert not np.allclose(kern[0], kern[1])


@pytest.mark.parametrize("pages,start_page", [(2, 1), (2, 3), (2, 7),
                                              (4, 1), (4, 2), (4, 6)])
def test_kernel_chunk_starting_inside_a_block(monkeypatch, pages, start_page):
    """Continuation chunks of 2 and 4 pages whose start is no multiple
    of the 4-page block (one is, for contrast), causal inside the
    chunk.  The small budget also splits the 8 heads into groups: the
    grid's second axis, each group streaming the pages again."""
    s = pages * PAGE
    args = _geometry(pages * 10 + start_page, [start_page * PAGE] * 2, s,
                     heads=8, m=16)
    _, heads_per_group, _ = _pages_per_block(monkeypatch, args, 4)
    assert heads_per_group < 8
    _check(args)


@pytest.mark.parametrize("s", [1, 4 * PAGE])
def test_kernel_ignores_nan_in_pages_past_a_rows_length(monkeypatch, s):
    """Every pool page no row may read — the scratch page, the table's
    entries past each row's length, the unused rest of the pool — is
    NaN.  The output is finite and bit-equal to the clean pool's: a
    loop one block too long, a dead page copied, or a stale half of the
    double buffer would each bring a NaN to a matmul (0 x NaN = NaN)."""
    lens = [1, 2 * PAGE - 1, 4 * PAGE, 7 * PAGE + 3]
    args = list(_geometry(77, lens, s, heads=8, m=16, pool=80))
    # a decode step all heads at once, the chunk head by head
    assert _pages_per_block(monkeypatch, args, 2)[2] is (s == 1)
    clean = _check(args)
    tbl = np.asarray(args[3])
    live = {int(p) for row, n in zip(tbl, lens)
            for p in row[:-(-(n + s) // PAGE)]}
    dead = np.array(sorted(set(range(80)) - live))
    assert 0 in dead and len(dead) > 80 - 4 * 16
    args[1] = args[1].at[dead].set(jnp.nan)
    args[2] = args[2].at[dead].set(jnp.nan)
    poisoned = np.asarray(_kernel(*args))
    np.testing.assert_array_equal(poisoned, clean)


@pytest.mark.parametrize("heads,s", [(4, 1), (4, 64), (6, 1), (3, 1),
                                     (6, 32)])
def test_kernel_bf16_pools_and_untiled_head_counts(monkeypatch, heads, s):
    """bf16 pools pack two heads' rows to a 32-bit word (the strided
    head load splits them by shift and mask); 6 and 3 heads do not fill
    a tile and are padded with zero heads.  bf16 resolution: one ulp of
    an O(1) output."""
    args = _geometry(heads + s, [PAGE + 5, 0, 4 * PAGE - 1], s, heads=heads,
                     m=16, dtype=jnp.bfloat16)
    _pages_per_block(monkeypatch, args, 2)
    _check(args, rtol=2 ** -7, atol=2 ** -7)


@pytest.mark.parametrize("heads", [6, 3])
def test_kernel_untiled_head_counts_f32(heads):
    """f32 pools, 6 and 3 heads (8 and 4 after padding), decode."""
    _check(_geometry(heads, [1, PAGE, 3 * PAGE + 7], 1, heads=heads))


# ---------------------------------------------------------------------------
# the all-heads form: one [R, T·H] score tile a block
# ---------------------------------------------------------------------------

def _form(args):
    """Which form ``_plan`` names for this call's shapes."""
    (_, s, hq, d), pk = args[0].shape, args[1]
    h = pa._tiled_heads(pk.shape[2], pk.dtype.itemsize)
    return pa._tiling(s, hq // pk.shape[2] * h, h, d, pk.shape[1],
                      args[3].shape[1], pk.dtype.itemsize)[-1]


def _grouped(args, hq):
    """The same pools and table under ``hq`` query heads."""
    q = args[0]
    rng = np.random.default_rng(hq)
    args = list(args)
    args[0] = jnp.asarray(
        rng.standard_normal(q.shape[:2] + (hq, q.shape[3])), q.dtype)
    return args


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("hq,heads,window", [
    (16, 16, None), (28, 4, None), (28, 4, 20), (16, 8, 20), (6, 6, None),
    (3, 3, None)],
    ids=["16", "28over4", "28over4_window", "16over8_window", "6", "3"])
def test_all_heads_form_matches_the_oracles(monkeypatch, dtype, hq, heads,
                                            window):
    """A decode step in the all-heads form against the gather oracle (and
    the blockwise reference where it has the case): 16 heads; grouped
    queries, 7 to a KV head, with and without a window; 6 and 3 heads,
    padded to 8 and 4.  Four heads take the form only with ``_plan``'s
    head bound lowered (measured slower on the chip there): the math
    holds at any count."""
    monkeypatch.setattr(pa, "_ALL_HEADS_MIN_HEADS", 1)
    lens = [1, 2 * PAGE - 1, 2 * PAGE, 7 * PAGE + 3, 0]
    args = _grouped(_geometry(hq + heads, lens, 1, heads=heads, m=16,
                              dtype=dtype), hq)
    args[3] = args[3].at[-1].set(0)                 # an idle row
    _pages_per_block(monkeypatch, args, 2)
    assert _form(args)
    tol = 1e-6 if dtype == jnp.float32 else 2 ** -7
    if window is None and hq == heads:
        _check(args, rtol=tol, atol=tol)
        return
    kern = np.asarray(_kernel(*args, window=window), np.float32)
    oracle = np.asarray(pa.paged_attention(*args, window=window), np.float32)
    np.testing.assert_allclose(kern, oracle, rtol=2 * tol, atol=2 * tol)


@pytest.mark.parametrize("s,heads,all_heads", [
    (1, 8, True), (1, 4, False), (8, 16, True), (16, 16, False)],
    ids=["8heads", "4heads", "128rows", "256rows"])
def test_plan_names_the_form_each_side_of_its_bounds(s, heads, all_heads):
    """``_plan``'s rule reads the static shapes: at least 8 heads in the
    grid point, at most 128 query rows between them.  One shape each side
    of each bound, and the kernel is the oracle's on all four."""
    args = _geometry(s * heads, [3 * PAGE + 5, PAGE, 0], s, heads=heads,
                     m=16)
    assert _form(args) is all_heads
    assert pa.decode_scores_all_heads(heads, heads, D, PAGE, 16, 4) is (
        heads >= 8)
    _check(args)


def test_plan_keeps_chunks_head_by_head():
    """The benchmark's shapes: a decode step of 16 heads of 128 takes the
    all-heads form, its 128- and 256-token chunks and the grouped decoder
    (4 KV heads) do not."""
    assert pa._tiling(1, 16, 16, 128, 16, 128, 2)[2:] == (8, 16, True)
    assert pa._tiling(128, 16, 16, 128, 16, 128, 2)[2:] == (8, 4, False)
    assert pa._tiling(256, 16, 16, 128, 16, 128, 2)[2:] == (8, 2, False)
    assert pa._tiling(1, 28, 4, 128, 64, 256, 2) == (7, 1, 8, 4, False)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_all_heads_form_keeps_heads_apart_by_the_mask_alone(monkeypatch,
                                                            dtype):
    """Every other head's LIVE rows — of pages the row does read — made
    1e4 times larger in K and V: head 5's output is bit-equal to the clean
    pool's.  Another head's column is scored in the same matmul and
    reaches the row only through a probability that must be exactly 0.
    (Finite on purpose: 0 x NaN is NaN in the P·V matmul, as it is for a
    masked position of the row's own head; a head whose live rows hold
    NaN has poisoned the layer through its out-projection already.)"""
    lens = [PAGE + 3, 5 * PAGE - 1, 0]
    args = list(_geometry(5, lens, 1, heads=8, m=16, dtype=dtype))
    _pages_per_block(monkeypatch, args, 2)
    assert _form(args)
    clean = np.asarray(_kernel(*args))
    others = jnp.asarray([1e4] * 5 + [1.0] + [1e4] * 2, dtype)[:, None]
    args[1], args[2] = args[1] * others, args[2] * others
    loud = np.asarray(_kernel(*args))
    assert np.isfinite(loud.astype(np.float32)).all()
    np.testing.assert_array_equal(loud[:, :, 5], clean[:, :, 5])
    assert not np.array_equal(loud[:, :, 4], clean[:, :, 4])


def test_engine_publishes_the_form_its_decode_body_takes():
    """``serve_paged_decode_allheads``: 1 for a decode body whose paged
    kernel scores all heads at once — the dense block at 8 heads, grouped
    queries over 8 KV heads — and 0 head by head (4 KV heads) or where no
    kernel runs (the gather path)."""
    from dtf_tpu.models import build_model

    def gauge(model):
        params = jax.eval_shape(
            model.clone(use_pallas=False).init, jax.random.key(0),
            jnp.zeros((1, PAGE), jnp.int32))["params"]
        params = jax.tree_util.tree_map(
            lambda a: jnp.zeros(a.shape, a.dtype), params)
        eng = ServeEngine(model, params, max_batch=2, max_seq_len=32,
                          kv_page_size=PAGE, max_delay_s=0.0)
        try:
            value = eng.metrics.get("serve_paged_decode_allheads").value
            assert value == eng.decoder.decode_all_heads
            return value
        finally:
            eng.stop(drain=False)

    dense = TransformerLM(vocab_size=64, num_layers=1, d_model=64,
                          num_heads=8, d_ff=64, max_seq_len=32,
                          use_pallas="interpret")
    assert gauge(dense) == 1
    assert gauge(dense.clone(use_pallas=False)) == 0
    assert gauge(dense.clone(num_heads=4)) == 0
    routed = dict(num_layers=1, d_model=64, num_heads=16, head_dim=8,
                  num_experts=4, experts_per_token=2, expert_width=16,
                  window=16, layer_window=[False], layer_rope=[True],
                  max_seq_len=32)
    for kv_heads, want in ((8, 1), (4, 0)):
        model, _ = build_model("routed_decoder", num_classes=64,
                               dtype=jnp.float32, num_kv_heads=kv_heads,
                               **routed)
        assert gauge(model.clone(use_pallas="interpret")) == want


def test_auto_dispatch_routes_by_flag(monkeypatch):
    """use_pallas=False → gather; "interpret"/True → kernel; None on a
    CPU backend → gather (the TPU default-on is the same branch,
    keyed off jax.default_backend())."""
    calls = []
    monkeypatch.setattr(pa, "paged_flash_decode",
                        lambda *a, **k: calls.append(
                            ("kernel", k.get("interpret"))))
    monkeypatch.setattr(pa, "paged_attention",
                        lambda *a, **k: calls.append(("gather", None)))
    args = (None, None, None, None, None)
    pa.paged_attention_auto(*args, use_pallas=False)
    pa.paged_attention_auto(*args, use_pallas="interpret")
    pa.paged_attention_auto(*args, use_pallas=True)
    pa.paged_attention_auto(*args, use_pallas=None)   # CPU here
    assert calls == [("gather", None), ("kernel", True),
                     ("kernel", False), ("gather", None)]


def test_auto_gather_applies_window_trim(monkeypatch):
    """The gather arm still gets the static window trim (the kernel
    ignores it — its dynamic predicate skips the same pages)."""
    seen = {}

    def fake_gather(q, pk, pv, table, index):
        seen["cols"] = table.shape[1]
        return None

    monkeypatch.setattr(pa, "paged_attention", fake_gather)
    tbl = jnp.zeros((2, 6), jnp.int32)
    pa.paged_attention_auto(None, None, None, tbl, None,
                            window_pages=3, use_pallas=False)
    assert seen["cols"] == 3


def test_engine_generation_interpret_kernel_token_exact():
    """End-to-end: the full engine pipeline with the model's attention
    routed through the interpret-mode kernel reproduces the gather
    path's exact greedy tokens — the kernel slots into write-then-
    attend, chunked prefill, and continuous batching unchanged."""
    model = TransformerLM(vocab_size=64, num_layers=2, d_model=32,
                          num_heads=2, d_ff=64, max_seq_len=32)
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, 32), jnp.int32))["params"]
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, 64, (n,)).astype(np.int32)
               for n in (1, PAGE - 1, PAGE, 3 * PAGE + 1)]
    results = {}
    for mode, m in [("gather", model),
                    ("kernel", model.clone(use_pallas="interpret"))]:
        eng = ServeEngine(m, params, max_batch=4, max_seq_len=32,
                          kv_page_size=PAGE, max_delay_s=0.0)
        try:
            hs = [eng.submit(p, max_new_tokens=4) for p in prompts]
            results[mode] = [h.result(timeout=300).tokens for h in hs]
        finally:
            eng.stop(drain=False)
    assert results["kernel"] == results["gather"]


# ---------------------------------------------------------------------------
# The tile kernel: a chunk whose queries each read a subset of the blocks
# ---------------------------------------------------------------------------

def _tile_case(seed, dtype, *, b=2, s=32, hq=8, hkv=2, page=16, block=4,
               m=6, starts=(32, 48), density=0.3):
    """Random pools under shuffled tables, a chunk of ``s`` queries a row
    at ``starts`` and a RANDOM membership a (query, KV head) — any set of
    blocks up to the query's own, the first always — packed at the unit
    ``tile_keys`` names."""
    rng = np.random.default_rng(seed)
    pages = 1 + b * m
    table = rng.permutation(np.arange(1, pages)).reshape(b, m).astype(
        np.int32)
    pk = jnp.asarray(rng.standard_normal((pages, page, hkv, D)), dtype)
    pv = jnp.asarray(rng.standard_normal((pages, page, hkv, D)), dtype)
    q = jnp.asarray(rng.standard_normal((b, s, hq, D)), dtype)
    index = np.asarray(starts, np.int32)
    unit = pa.tile_keys(page, block)
    blocks = m * page // block
    member = rng.uniform(size=(b, hkv, s, blocks)) < density
    member[..., 0] = True
    own = (index[:, None] + np.arange(s)[None, :]) // block     # [B, S]
    member &= np.arange(blocks)[None, None, None, :] <= own[:, None, :,
                                                            None]
    member[np.arange(b)[:, None], :, np.arange(s)[None, :], own] = True
    per = unit // block
    bits = np.sum(member.reshape(b, hkv, s, -1, per).astype(np.int32)
                  << np.arange(per), -1).astype(np.int32)
    return (q, pk, pv, jnp.asarray(table), jnp.asarray(index),
            jnp.asarray(bits)), member, block


def _tile_expected(args, member, block):
    """Softmax attention, written out in float64: query ``i`` of row ``b``
    over the keys of ITS blocks at positions up to its own."""
    q, pk, pv, table, index, _ = (np.asarray(x, np.float64) for x in args)
    table, index = table.astype(int), index.astype(int)
    b, s, hq, d = q.shape
    page, hkv = pk.shape[1], pk.shape[2]
    out = np.zeros((b, s, hq, d))
    for r in range(b):
        k = pk[table[r]].reshape(-1, hkv, d)
        v = pv[table[r]].reshape(-1, hkv, d)
        for i in range(s):
            t = index[r] + i
            for head in range(hq):
                g = head // (hq // hkv)
                pos = [p for p in range(t + 1) if member[r, g, i, p // block]]
                sc = k[pos, g] @ q[r, i, head] / np.sqrt(d)
                w = np.exp(sc - sc.max())
                out[r, i, head] = (w / w.sum()) @ v[pos, g]
    return out


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("tile_q,kw", [
    (16, {}),                                   # two tiles a (row, KV head)
    (32, {}),                                   # one
    (8, dict(hkv=4, hq=8)),                     # two words of bf16 heads
    (16, dict(block=8, page=32, m=3)),          # a page of one unit
    (16, dict(starts=(0, 64))),                 # a chunk from position 0
    (16, dict(density=0.004, m=8, starts=(64, 96)))],    # tiles skip units
    ids=["two_tiles", "one_tile", "four_kv_heads", "blocks_of_8",
         "from_zero", "few_blocks"])
def test_tile_kernel_reads_each_querys_blocks(dtype, tile_q, kw):
    """The tile kernel (interpret mode) = the softmax over exactly the keys
    a query's membership names, up to the query: the KV heads of a row hold
    different sets, a unit no query of the tile reads is not streamed
    (NaN there would show: the interpreter's scratch is NaN-filled, and the
    count falls under the table's), the gathered oracle agrees, and both
    report the same count of copied blocks."""
    args, member, block = _tile_case(5, dtype, **kw)
    assert (member[:, 0] != member[:, 1]).any()
    got, streamed = pa.paged_tile_attention(
        *args, block=block, use_pallas="interpret", tile_q=tile_q)
    oracle, same = pa.paged_tile_attention(
        *args, block=block, use_pallas=False, tile_q=tile_q)
    want = _tile_expected(args, member, block)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float64), want, atol=tol)
    np.testing.assert_allclose(np.asarray(oracle, np.float64), want,
                               atol=tol)
    b, hkv, s, _ = member.shape
    per = pa.tile_keys(args[1].shape[1], block) // block
    units = member.reshape(b, hkv, s // tile_q, tile_q, -1, per).any(
        axis=(3, 5))
    assert int(streamed) == int(same) == units.sum() * per
    if kw.get("density", 1) < 0.1:
        unit = per * block
        visible = hkv * sum((int(start) + i + tile_q - 1) // unit + 1
                            for start in args[4]
                            for i in range(0, s, tile_q))
        assert units.sum() < 0.75 * visible      # units ARE skipped


def test_tile_kernel_never_reads_a_unit_no_query_chose():
    """NaN in every unit outside the tiles' unions: the output stays
    finite and equal, so those units are neither copied nor multiplied."""
    args, member, block = _tile_case(6, jnp.float32, density=0.004, m=8,
                                     starts=(64, 96))
    q, pk, pv, table, index, bits = args
    unit = pa.tile_keys(pk.shape[1], block)
    per = unit // block
    units_read = member.reshape(member.shape[:3] + (-1, per)).any(
        axis=(1, 2, 4))                                     # [B, units]
    spoiled_k, spoiled_v = np.asarray(pk).copy(), np.asarray(pv).copy()
    per_page = pk.shape[1] // unit
    for r in range(len(table)):
        for u in np.flatnonzero(~units_read[r]):
            page, off = int(table[r, u // per_page]), (u % per_page) * unit
            spoiled_k[page, off:off + unit] = np.nan
            spoiled_v[page, off:off + unit] = np.nan
    assert np.isnan(spoiled_k).any()
    want, _ = pa.paged_tile_attention(*args, block=block,
                                      use_pallas="interpret", tile_q=32)
    got, _ = pa.paged_tile_attention(
        q, jnp.asarray(spoiled_k), jnp.asarray(spoiled_v), table, index,
        bits, block=block, use_pallas="interpret", tile_q=32)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("page,block,want", [
    (2048, 64, 256), (32, 8, 32), (16, 4, 16), (4096, 8, 128),
    (1024, 128, 256)])
def test_tile_keys_is_whole_blocks_of_a_page(page, block, want):
    assert pa.tile_keys(page, block) == want


def test_tile_kernel_refuses_heads_that_do_not_tile():
    args, _, block = _tile_case(7, jnp.bfloat16, hkv=1, hq=4)
    with pytest.raises(ValueError, match="does not tile"):
        pa.paged_tile_attention(*args, block=block, use_pallas="interpret",
                                tile_q=16)
    with pytest.raises(ValueError, match="whole units"):
        pa.tile_keys(48, 32)
