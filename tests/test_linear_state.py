"""The routed decoder's delta-rule linear-attention layers (``layer_mixer``
``linear_delta``: a matrix of state a head that rides the page table)
beside ONE latent-attention layer in six with a direct query projection, a
norm a query head and a gate a head, two leading dense layers,
sigmoid-plus-bias routing under a group limit, an expert layer that holds
a block of the experts and a sliced vocabulary — against the plain
reference (``benchmark/families/reference_ling.py``) and its own oracles.
The toy keeps the shape of the thing: two dense + six routed layers in the
order K K K K K M K K, 4 linear heads of 8, 16 experts in 4 groups of which
2 are kept, top-4, the first 8 held, a non-zero bias.  float32 throughout
(the state's pool too), so what is compared is the mathematics and not a
rounding."""

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
from dtf_tpu.ops import linear_state as ls  # noqa: E402
from dtf_tpu.serve import migrate  # noqa: E402
from dtf_tpu.serve.bridge import serving_memory_plan  # noqa: E402
from dtf_tpu.serve.decode import (PAGE_STATE, Decoder,  # noqa: E402
                                  cache_leaves)
from dtf_tpu.serve.engine import ServeEngine, chunk_plan  # noqa: E402

MIXERS = ["linear_delta"] * 5 + ["attention"] + ["linear_delta"] * 2
TOY = dict(num_layers=8, d_model=64, num_heads=4, layer_mixer=MIXERS,
           linear_heads=4, linear_head_dim=8, linear_conv_taps=4,
           linear_decay_floor=-5.0, q_lora_rank=None, kv_lora_rank=24,
           qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
           q_head_norm=True, attention_head_gate=True, rope_theta=6e6,
           rope_interleave=False, rms_eps=1e-6, num_dense_layers=2,
           dense_width=96, num_experts=16, experts_per_token=4,
           expert_width=32, shared_expert_width=32, routing="sigmoid_bias",
           routed_scale=2.5, router_bias_stddev=0.05, route_groups=4,
           route_groups_kept=2, experts_held=[0, 8], activation="silu",
           router_input="post_attention", max_seq_len=256)
pa = importlib.import_module("dtf_tpu.ops.paged_attention")
VOCAB, PAGE, CHUNK = 128, 16, 32
N_LINEAR = MIXERS.count("linear_delta")


@pytest.fixture(scope="module")
def toy():
    model, _ = build_model("routed_decoder", num_classes=VOCAB,
                           dtype=jnp.float32, **TOY)
    params = model.init(jax.random.key(3),
                        jnp.zeros((1, PAGE), jnp.int32))["params"]
    return model, params


@pytest.fixture(scope="module")
def reference():
    ref = importlib.import_module("benchmark.families.reference_ling")
    return ref, ref.arch_of_model_kwargs(TOY)


@pytest.fixture(scope="module")
def decoders(toy):
    """One decoder a path, shared by the tests (each starts from a fresh
    cache): a body compiles once a chunk shape, not once a test."""
    model, params = toy
    return {up: Decoder(model.clone(use_pallas=up), params, num_slots=4,
                        max_seq_len=160, kv_page_size=PAGE, kv_pool_pages=41)
            for up in (False, "interpret")}


_REF_LEN = 160


def _ref_logits(reference, params, tokens, **controls):
    """The reference's logits of ``tokens`` [B, S]: causal, so the
    sequences are padded to one length and one program serves every
    test."""
    ref, arch = reference
    key = tuple(sorted(controls))
    if key not in _ref_logits.__dict__.setdefault("fns", {}):
        _ref_logits.fns[key] = jax.jit(lambda p, t: ref._head(
            ref.hidden(p, t, arch, **controls), p["lm_head"]))
    tokens = np.asarray(tokens)
    b, s = tokens.shape
    padded = np.zeros((b, _REF_LEN), np.int32)
    padded[:, :s] = tokens
    return np.asarray(_ref_logits.fns[key](params, jnp.asarray(padded))
                      )[:, :s]


def _close(got, want, tol=1e-4):
    """1e-4 of the logit scale: both sides are float32 and differ in the
    order of their sums (grouped expert rows against dense masked experts,
    a blocked or carried state against the token-by-token recurrence) — a
    wrong carry, decay, mask, position, norm or routing weight moves
    logits by their whole spread."""
    assert np.abs(got - want).max() <= tol * np.abs(want).max()


def test_model_equals_reference(toy, reference):
    """(a) Teacher-forced logits of the program's full forward against the
    plain reference's: the three filters and their SiLU, both L2 norms,
    the decay a channel, beta, the output norm and gate; the latent
    layer's direct query, head norm and head gate; the group limit, the
    bias in the choice and not in the weight, the held block, the shared
    expert, the final norm, the sliced head."""
    model, params = toy
    assert model.carries_state and model.kv_lora_rank is not None
    assert params["lm_head"].shape == (64, VOCAB)
    assert params["layer2"]["gate_up"].shape == (8, 64, 64)   # 8 of 16 held
    assert params["layer2"]["router"].shape == (64, 16)
    assert params["layer5"]["attn"]["q"].shape == (64, 4 * 24)
    assert params["layer5"]["attn"]["q_head_norm"].shape == (24,)
    assert params["layer5"]["attn"]["gate"].shape == (64, 4)
    assert params["layer0"]["linear"]["taps"].shape == (3 * 32, 4)
    assert "attn" not in params["layer4"] and "linear" not in params["layer5"]
    assert float(jnp.abs(params["layer2"]["router_bias"]).max()) > 0.01
    tokens = np.random.default_rng(0).integers(0, VOCAB, (2, 60),
                                               dtype=np.int32)
    got = np.asarray(model.apply({"params": params}, jnp.asarray(tokens)))
    _close(got, _ref_logits(reference, params, tokens))


# ------------------------- (b) the three forms of the linear layer ------
def _draw(rng, b, s, h=4, d=8, floor=False):
    q, k = (rng.normal(size=(b, s, h, d)).astype(np.float32)
            for _ in range(2))
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * d ** 0.5
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.normal(size=(b, s, h, d)).astype(np.float32)
    a = -5.0 * rng.uniform(size=(b, s, h, d)).astype(np.float32)
    if floor:
        a = np.full_like(a, -4.999)
    return q, k, v, a, rng.uniform(size=(b, s, h)).astype(np.float32)


@pytest.mark.parametrize("s,block,floor", [
    (64, 16, False), (64, 16, True), (48, 8, False), (96, 16, True)],
    ids=["16", "16-floor", "8", "16-floor-long"])
def test_blocked_form_equals_the_recurrence(s, block, floor):
    """``chunked`` ≡ ``recurrent``, across block boundaries, from a carried
    state; with every decay at the floor (``a`` = -5 a token: ``exp`` of a
    block's sum is 2e-35) the blocked form stays finite and equal."""
    x = _draw(np.random.default_rng(s), 2, s, floor=floor)
    start = jnp.asarray(np.random.default_rng(1).normal(
        size=(2, 4, 8, 8)).astype(np.float32))
    want, end = ls.recurrent(*x, start)
    got, states = ls.chunked(*x, start, block=block, emit_every=2 * block)
    assert np.isfinite(np.asarray(got)).all()
    assert np.abs(np.asarray(got - want)).max() <= 2e-5
    assert np.abs(np.asarray(states[:, -1] - end)).max() <= 2e-5
    # a state emitted on the way is the recurrence's at that token
    _, mid = ls.recurrent(*(t[:, :2 * block] for t in x), start)
    assert np.abs(np.asarray(states[:, 0] - mid)).max() <= 2e-5


def test_padded_tokens_leave_the_state_alone():
    """``beta`` = 0 and ``a`` = 0 past a row's last real token: the state
    after a tail-padded call is the state at that token."""
    q, k, v, a, beta = _draw(np.random.default_rng(5), 2, 32)
    real = np.arange(32)[None, :] <= np.asarray([20, 3])[:, None]
    a = np.where(real[..., None, None], a, 0.0)
    beta = np.where(real[..., None], beta, 0.0)
    _, states = ls.chunked(q, k, v, a, beta, block=16)
    for r, n in enumerate((21, 4)):
        _, want = ls.recurrent(*(t[r:r + 1, :n] for t in (q, k, v, a, beta)))
        assert np.abs(np.asarray(states[r, -1] - want[0])).max() <= 1e-5


# --------------------------------------- (d) the decode kernel ----------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_kernel_equals_the_step_in_place(dtype):
    """``linear_state_decode`` in interpret mode ≡ ``paged_step``: a row
    inside a page, a row AT a page boundary (its carry comes from one page
    and its entry goes to the next), a row at position 0 (no carry), and
    an idle row (an all-zero table: the scratch page); every page no row
    writes keeps its entry, bit for bit."""
    rng = np.random.default_rng(7)
    pool = jnp.asarray(rng.normal(size=(9, 4, 8, 8)), dtype)
    tables = jnp.asarray([[1, 2, 3], [0, 0, 0], [4, 5, 6], [7, 8, 0]],
                         jnp.int32)
    index = jnp.asarray([2 * PAGE, 0, 5, 0], jnp.int32)
    q, k, v, a, beta = (jnp.asarray(t[0]) for t in _draw(rng, 1, 4))
    want_o, want = ls.paged_step(pool, q, k, v, a, beta, tables, index,
                                 page_size=PAGE)
    got_o, got = ls.linear_state_decode(pool, q, k, v, a, beta, tables,
                                        index, page_size=PAGE,
                                        interpret=True)
    assert np.abs(np.asarray(got_o - want_o)).max() <= 1e-6
    got, want, was = (np.asarray(t, np.float32) for t in (got, want, pool))
    # the kernel weighs the key by beta, the oracle the value: an ulp
    np.testing.assert_allclose(got, want, rtol=2e-2 if dtype ==
                               jnp.bfloat16 else 1e-5, atol=1e-6)
    written = sorted(np.flatnonzero((got != was).any((1, 2, 3))).tolist())
    assert written == [0, 3, 4, 7]      # scratch; 32 // 16 -> 3; 4; 7


@pytest.mark.parametrize("case", ["no_carry", "page_boundary", "idle_row",
                                  "decay_floor", "inside_a_page"])
def test_the_bf16_pools_form_is_float32_arithmetic(case):
    """The form a bfloat16 pool takes — the stored matrix against the three
    bfloat16 pieces of ``alpha k`` and ``alpha q`` on the MXU — held to the
    float32 statement: against ``paged_step`` evaluated in float64 (numpy)
    on the same bfloat16 pool, ``o`` within the bound the float32 pool's
    case holds, and every stored entry within ONE bfloat16 spacing of the
    oracle's rounded entry (plus float32's own rounding of the terms it
    adds, 2^-22 of them: it shows only where they cancel to less than a
    spacing).  One bfloat16 pass (the pieces dropped) reads 1e-3 here.
    16 heads of 32 x 32: two tiles of eight, the cell's loop.  Row 0 is
    the case, row 1 an ordinary row beside it."""
    rng = np.random.default_rng(43)
    h, d = 16, 32
    pool = jnp.asarray(rng.normal(size=(9, h, d, d)), jnp.bfloat16)
    table0, index0 = {"no_carry": ([1, 2, 3], 0),
                      "page_boundary": ([1, 2, 3], 2 * PAGE),
                      "idle_row": ([0, 0, 0], 0),
                      "decay_floor": ([1, 2, 3], PAGE + 3),
                      "inside_a_page": ([1, 2, 3], 5)}[case]
    tables = np.asarray([table0, [4, 5, 6]], np.int32)
    index = np.asarray([index0, PAGE + 1], np.int32)
    q, k, v, a, beta = (t[:, 0] for t in _draw(rng, 2, 1, h, d))
    if case == "decay_floor":
        a[0] = -5.0
    got_o, got = ls.linear_state_decode(
        pool, *(jnp.asarray(t) for t in (q, k, v, a, beta)),
        jnp.asarray(tables), jnp.asarray(index), page_size=PAGE,
        interpret=True)

    q, k, v, a, beta = (np.asarray(t, np.float64)
                        for t in (q, k, v, a, beta))
    was = np.asarray(pool.astype(jnp.float32), np.float64)
    rows = np.arange(2)
    src = tables[rows, np.maximum(index - 1, 0) // PAGE]
    dst = tables[rows, index // PAGE]
    md = (np.where((index > 0)[:, None, None, None], was[src], 0.0)
          * np.exp(a)[:, :, None, :])
    u = np.einsum("bhvk,bhk->bhv", md, k)
    rank = (beta[..., None] * (v - u))[..., None] * k[:, :, None, :]
    want_o = np.einsum("bhvk,bhk->bhv", md + rank, q)
    np.testing.assert_allclose(np.asarray(got_o, np.float64), want_o,
                               rtol=1e-5, atol=1e-6)
    got = np.asarray(got.astype(jnp.float32), np.float64)
    want = np.asarray(jnp.asarray(md + rank, jnp.float32).astype(
        jnp.bfloat16).astype(jnp.float32), np.float64)
    spacing = 2.0 ** (np.floor(np.log2(np.maximum(np.abs(want), 1e-30)))
                      - 7)
    f32_rounding = 2.0 ** -22 * (np.abs(md) + np.abs(rank) + np.abs(
        (beta[..., None] * np.einsum("bhvk,bhk->bhv", np.abs(md), np.abs(k))
         )[..., None] * k[:, :, None, :]))
    assert (np.abs(got[dst] - want) <= spacing + f32_rounding).all()
    untouched = np.setdiff1d(np.arange(9), dst)
    assert (got[untouched] == was[untouched]).all()
    assert sorted(set(dst.tolist())) == {
        "no_carry": [1, 5], "page_boundary": [3, 5], "idle_row": [0, 5],
        "decay_floor": [2, 5], "inside_a_page": [1, 5]}[case]


# ------------------ (b) the whole model through pages and entries ------
def _prefill(dec, cache, prompt, table, start=0):
    for s, clen in chunk_plan(len(prompt), CHUNK, PAGE, start):
        chunk = np.zeros((clen,), np.int32)
        real = prompt[s:s + clen]
        chunk[:len(real)] = real
        _, cache, last = dec.prefill_chunk(
            cache, chunk, table, s, len(real) - 1, 0.0, seed=0)
    return cache, np.asarray(last)


def _serve(dec, rows, new_tokens, slots_of=None):
    """Chunked prefill of every row (the engine's chunk plan), then
    ``new_tokens`` lockstep decode steps feeding the given continuation
    back: the logits at every position that would choose a token.
    ``slots_of``: the decode row of each request (idle rows between)."""
    slots = dec.num_slots
    slots_of = slots_of or list(range(len(rows)))
    cache = dec.fresh_cache()
    tables = np.zeros((slots, dec.pages_per_slot), np.int32)
    nxt = 1
    out = [[] for _ in rows]
    for r, (prompt, _) in enumerate(rows):
        need = -(-(len(prompt) + new_tokens) // PAGE)
        tables[slots_of[r], :need] = np.arange(nxt, nxt + need)
        nxt += need
        cache, last = _prefill(dec, cache, prompt, tables[slots_of[r]])
        out[r].append(last)
    index = np.zeros((slots,), np.int32)
    for r, (p, _) in enumerate(rows):
        index[slots_of[r]] = len(p)
    for j in range(new_tokens - 1):
        tokens = np.zeros((slots,), np.int32)
        for r, (_, cont) in enumerate(rows):
            tokens[slots_of[r]] = cont[j]
        _, cache, step = dec.decode_step(
            cache, tokens, index, np.zeros((slots,), np.float32),
            seeds=np.zeros((slots,), np.uint32), block_tables=tables)
        for r in range(len(rows)):
            out[r].append(np.asarray(step[slots_of[r]]))
            index[slots_of[r]] += 1
    return [np.stack(o) for o in out], cache


@pytest.mark.parametrize("lengths,slots_of,use_pallas", [
    ((1,), None, "interpret"), ((PAGE - 1,), None, "interpret"),
    ((PAGE,), None, "interpret"), ((PAGE + 1,), None, "interpret"),
    ((CHUNK - 1,), None, "interpret"), ((CHUNK,), None, "interpret"),
    ((CHUNK + 1,), None, "interpret"), ((CHUNK + 1,), None, False),
    ((5, 40, 61, 100), None, "interpret"), ((33, 70), [1, 3], "interpret"),
    ((9, 33), [0, 2], False),
], ids=["one", "page-1", "page", "page+1", "chunk-1", "chunk", "chunk+1",
        "chunk+1-gather", "batch4", "idle_rows_between",
        "idle_rows_between-gather"])
def test_paged_serving_equals_reference(toy, reference, decoders, lengths,
                                        slots_of, use_pallas):
    """(b) Chunked prefill (the blocked form) then decode (the kernel, or
    its oracle on the gather path) through pages and state entries (chunks
    of 32, pages of 16) against the reference's token-by-token recurrence
    over prompt + continuation: carries across block, chunk and page
    boundaries, a tail-padded final chunk whose entries are taken at its
    last real token, steps that cross page boundaries, rows of different
    lengths in one decode batch, idle rows whose entries go to the scratch
    page."""
    model, params = toy
    rng = np.random.default_rng(1)
    new = PAGE + 3              # steps on either side of a page end
    rows = [(rng.integers(0, VOCAB, n, dtype=np.int32),
             rng.integers(0, VOCAB, new, dtype=np.int32)) for n in lengths]
    dec = decoders[use_pallas]
    got, _ = _serve(dec, rows, new, slots_of)
    for (prompt, cont), g in zip(rows, got):
        seq = np.concatenate([prompt, cont])[None]
        want = _ref_logits(reference, params, seq)[0][
            len(prompt) - 1:len(prompt) - 1 + new]
        _close(g, want)
    counts = dict(zip(model.stats_names,
                      (int(c) for c in dec.last_stats["counts"])))
    assert counts["linear_tokens"] == 4 * N_LINEAR
    # only the live rows' entries went to pages of their own
    assert counts["state_rows_advanced"] == len(rows) * N_LINEAR
    # the one latent layer alone reads the latent cache
    assert counts["latent_tokens_read"] == sum(
        len(p) + new - 1 for p, _ in rows) + (4 - len(rows))
    # the pairs computed HERE: no more than were chosen, and some
    assert 0 < counts["assignments"] <= 4 * 4 * 6


def test_idle_rows_move_nobodys_state(decoders):
    """(b) A decode step in which a row is idle (index 0, an all-zero
    table) changes no state entry but the scratch page's and the live
    rows' own."""
    dec = decoders["interpret"]
    rng = np.random.default_rng(2)
    rows = [(rng.integers(0, VOCAB, 19, dtype=np.int32),
             rng.integers(0, VOCAB, 2, dtype=np.int32))]
    _, cache = _serve(dec, rows, 1, [2])
    before = {n: np.asarray(cache["layer0"]["linear"][n])
              for n in ("linear_state", "conv_state")}
    tables = np.zeros((4, dec.pages_per_slot), np.int32)
    tables[2, :2] = [1, 2]
    index = np.asarray([0, 0, 19, 0], np.int32)
    _, cache, _ = dec.decode_step(
        cache, np.asarray([5, 6, 7, 8], np.int32), index,
        np.zeros((4,), np.float32), seeds=np.zeros((4,), np.uint32),
        block_tables=tables)
    for name, was in before.items():
        now = np.asarray(cache["layer0"]["linear"][name])
        moved = (was != now).reshape(was.shape[0], -1).any(-1)
        # scratch, and the page that holds position 19
        assert sorted(np.flatnonzero(moved).tolist()) == [0, 2]


# ------------- (b') the filter inputs' entry: its values and their order --
_LAYER = dict(heads=4, head_dim=8, taps=4, decay_floor=-5.0, rms_eps=1e-6,
              dtype=jnp.float32, param_dtype=jnp.float32, use_pallas=False,
              decode=True, kv_page_size=PAGE, kv_pool_pages=9)


@pytest.fixture(scope="module")
def layer():
    """``LinearDelta`` alone over 9 pages of 16, its parameters, and the
    filters' inputs ``u`` [2, 40, 96] of two rows of hidden states."""
    mod = rd.LinearDelta(**_LAYER)
    h = jnp.asarray(np.random.default_rng(6).normal(size=(2, 40, 64)),
                    jnp.float32)
    zeros = jnp.zeros((1,), jnp.int32)
    variables = mod.init(jax.random.key(1), h[:1, :PAGE], zeros,
                         jnp.zeros((1, 3), jnp.int32), zeros)
    u = np.asarray(jnp.einsum("bsd,dn->bsn", h, variables["params"]["qkv"]))
    return mod, variables, h, u


def _flat_entry(u_row, t):
    """The entry as the flat ``[P, W]`` leaf held it: ``[u_{t-2} | u_{t-1}
    | u_t]``, oldest first, zeros before the sequence."""
    return np.concatenate([u_row[j] if j >= 0 else np.zeros_like(u_row[0])
                           for j in (t - 2, t - 1, t)])


def _apply(mod, variables, cache, h, index, tables, last_pos):
    _, mut = mod.apply(
        {"params": variables["params"], "cache": cache}, h,
        jnp.asarray(index, jnp.int32), jnp.asarray(tables, jnp.int32),
        jnp.asarray(last_pos, jnp.int32), mutable=["cache"])
    return mut["cache"]


@pytest.mark.parametrize("case", ["chunk_across_two_pages", "padded_chunk",
                                  "step", "page_first_token", "idle_row"])
def test_an_entry_flattened_is_the_flat_layouts_entry(layer, case):
    """The ``conv_state`` leaf is ``[P, sublanes, W / sublanes]``; page
    ``p``'s entry, flattened row-major, is value for value what the flat
    leaf held: after a chunk that crosses two pages (each page's entry
    taken at its last token), after a tail-padded chunk (at the row's last
    real token), after a step inside a page, after a step AT a page's first
    token (the carry read from the page before, the entry written to the
    new page), and for an idle row, whose entry goes to the scratch page
    and to nobody's."""
    mod, variables, h, u = layer
    fresh = variables["cache"]
    assert fresh["conv_state"].shape == (9, 8, 3 * 96 // 8)
    want = {}
    if case == "chunk_across_two_pages":
        cache = _apply(mod, variables, fresh, h[:1, :2 * PAGE], [0],
                       [[1, 2, 3]], [2 * PAGE - 1])
        want = {1: _flat_entry(u[0], PAGE - 1),
                2: _flat_entry(u[0], 2 * PAGE - 1)}
    elif case == "padded_chunk":
        cache = _apply(mod, variables, fresh, h[:1, :2 * PAGE], [0],
                       [[1, 2, 3]], [PAGE + 4])
        want = {1: _flat_entry(u[0], PAGE - 1),
                2: _flat_entry(u[0], PAGE + 4)}
    else:
        # both rows prefill two pages; then one token
        cache = fresh
        for r, table in enumerate(([1, 2, 3], [4, 5, 6])):
            cache = _apply(mod, variables, cache, h[r:r + 1, :2 * PAGE],
                           [0], [table], [2 * PAGE - 1 if r == 0 else 20])
        if case == "step":          # row 1 at position 21, inside page 5
            cache = _apply(mod, variables, cache, h[:, 21:22], [0, 21],
                           [[0, 0, 0], [4, 5, 6]], [0, 0])
            want = {5: _flat_entry(u[1], 21), 0: _flat_entry(u[0, 21:], 0),
                    2: _flat_entry(u[0], 2 * PAGE - 1)}
        elif case == "page_first_token":    # row 0 enters page 3
            cache = _apply(mod, variables, cache, h[:, 32:33], [32, 0],
                           [[1, 2, 3], [0, 0, 0]], [0, 0])
            want = {3: _flat_entry(u[0], 32),
                    2: _flat_entry(u[0], 2 * PAGE - 1)}
        else:                       # both rows idle: the scratch page alone
            before = np.asarray(cache["conv_state"])
            cache = _apply(mod, variables, cache, h[:, 21:22], [0, 0],
                           np.zeros((2, 3), np.int32), [0, 0])
            now = np.asarray(cache["conv_state"])
            moved = (before != now).reshape(9, -1).any(-1)
            assert np.flatnonzero(moved).tolist() == [0]
            assert np.abs(now[0].reshape(-1)[:2 * 96]).max() == 0
            assert np.abs(now[0].reshape(-1)[2 * 96:]).max() > 0
            return
    got = np.asarray(cache["conv_state"])
    for page, entry in want.items():
        np.testing.assert_allclose(got[page].reshape(-1), entry, rtol=1e-5,
                                   atol=1e-6)


def test_an_entry_that_is_no_whole_tiles_is_refused():
    """3 x 1 x 4 float32 values a tap kept do not divide into a tile's 8
    sublanes: the layer says so where it declares the leaf."""
    mod = rd.LinearDelta(**dict(_LAYER, heads=1, head_dim=4, taps=2))
    zeros = jnp.zeros((1,), jnp.int32)
    with pytest.raises(ValueError, match="does not divide into the 8"):
        jax.eval_shape(mod.init, jax.random.key(0),
                       jnp.zeros((1, PAGE, 64)), zeros,
                       jnp.zeros((1, 3), jnp.int32), zeros)


# ------------------------------------- (c) the state rides the pages ----
def test_a_shared_prefix_and_a_copied_page_carry_the_state(toy, reference,
                                                           decoders):
    """A row whose table names another row's first pages and prefills only
    the rest, and a row that continues on a COPY of the last shared page
    (``copy_page``), both read the logits of the prompt prefilled whole:
    a full page's entry is the snapshot at its end, matrices and all."""
    _, params = toy
    dec = decoders["interpret"]
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, VOCAB, 2 * PAGE + 5, dtype=np.int32)
    want = _ref_logits(reference, params, prompt[None])[0, -1]
    cache = dec.fresh_cache()
    owner = np.zeros((dec.pages_per_slot,), np.int32)
    owner[:3] = [1, 2, 3]
    cache, last = _prefill(dec, cache, prompt, owner)
    _close(last, want)
    sharer = owner.copy()
    sharer[2] = 9
    cache, last = _prefill(dec, cache, prompt, sharer, start=2 * PAGE)
    _close(last, want)
    cache = dec.copy_page(cache, 2, 11)
    for name in ("linear_state", "conv_state"):
        leaf = np.asarray(cache["layer0"]["linear"][name])
        assert np.array_equal(leaf[11], leaf[2]) and leaf[2].any()
    copier = np.zeros_like(owner)
    copier[:3] = [1, 11, 12]
    cache, last = _prefill(dec, cache, prompt, copier, start=2 * PAGE)
    _close(last, want)
    # and a zeroed carry at that boundary is NOT the same logits
    wrong = np.zeros_like(owner)
    wrong[:3] = [1, 20, 13]         # page 20 was never written
    _, bad = _prefill(dec, cache, prompt, wrong, start=2 * PAGE)
    assert np.abs(bad - want).max() > 1e-2 * np.abs(want).max()


def test_exported_pages_carry_the_state(toy, reference, decoders):
    """``read_page`` / ``write_page`` (what ``serve/migrate.py`` moves,
    through its wire form) bring a page's matrices and filter inputs with
    its latent rows: another cache continues the prompt to the same
    logits."""
    _, params = toy
    src = dst = decoders["interpret"]       # two caches of one decoder
    rng = np.random.default_rng(4)
    prompt = rng.integers(0, VOCAB, 2 * PAGE + 3, dtype=np.int32)
    want = _ref_logits(reference, params, prompt[None])[0, -1]
    table = np.zeros((src.pages_per_slot,), np.int32)
    table[:3] = [4, 5, 6]
    cache, _ = _prefill(src, src.fresh_cache(), prompt, table)
    there = dst.fresh_cache()
    moved = np.zeros_like(table)
    moved[:3] = [7, 8, 9]
    for page, to in ((4, 7), (5, 8)):
        leaves = migrate.decode_page(migrate.encode_page(
            src.read_page(cache, page)))
        # a layer's filter inputs as whole tiles [8, 288 / 8], its matrices
        # [4, 8, 8]; the latent layer's rows [page, 128]
        assert sorted(a.shape for a in leaves) == sorted(
            [(8, 36), (4, 8, 8)] * N_LINEAR + [(PAGE, 128)])
        there = dst.write_page(there, to, leaves)
        # the wire form brought every leaf of the page bit for bit
        for was, now in zip(leaves, dst.read_page(there, to)):
            assert was.dtype == now.dtype and np.array_equal(was, now)
    _, last = _prefill(dst, there, prompt, moved, start=2 * PAGE)
    _close(last, want)


ENGINE = dict(max_batch=2, max_seq_len=160, max_delay_s=0.0,
              kv_page_size=PAGE, kv_pool_pages=25, prefill_chunk=CHUNK,
              seed=3)


@pytest.mark.parametrize("plen", [2 * PAGE, 2 * PAGE + 5],
                         ids=["whole_prompt_registered", "prefix_registered"])
def test_the_engine_serves_a_registered_prefix_as_an_unshared_run(toy, plen):
    """Through ``ServeEngine`` with prefix sharing on (the default): the
    second admit of a prompt hits the ``PrefixRegistry`` and serves the
    tokens of an engine that shares nothing.  Where the WHOLE prompt is
    registered the engine prefills the last page again from the entry of
    the page before it, and copies nothing (``carries_state``)."""
    model, params = toy
    prompt = np.random.default_rng(plen).integers(1, VOCAB, plen,
                                                  dtype=np.int32)
    plain = ServeEngine(model, params, prefix_sharing=False, **ENGINE)
    shared = ServeEngine(model, params, **ENGINE)
    try:
        want = plain.generate(prompt, max_new_tokens=PAGE + 2).tokens
        assert shared.generate(prompt, max_new_tokens=PAGE + 2).tokens == want
        before = shared.metrics.get("serve_prefix_hit_pages_total").value
        assert shared.generate(prompt, max_new_tokens=PAGE + 2).tokens == want
        hits = shared.metrics.get("serve_prefix_hit_pages_total").value
        assert hits - before == (plen - 1) // PAGE
        assert shared.metrics.get("serve_prefix_cow_total").value == 0
        gauge = shared.metrics.get("serve_state_bytes_per_page").value
        assert gauge == N_LINEAR * (4 * 8 * 8 + 3 * 3 * 32) * 4
    finally:
        plain.stop()
        shared.stop()


def test_a_migrated_row_serves_what_the_source_served(toy):
    """Through ``serve/migrate.py``'s engine surface: a chain exported
    from one engine and imported into a cold one is a prefix hit there,
    and the row serves the same tokens."""
    model, params = toy
    src, dst = (ServeEngine(model, params, **ENGINE),
                ServeEngine(model, params, **ENGINE))
    try:
        prompt = np.random.default_rng(7).integers(1, VOCAB, 2 * PAGE + 4,
                                                   dtype=np.int32)
        want = src.generate(prompt, max_new_tokens=PAGE).tokens
        pages, digests = src.export_chain_begin(prompt)
        try:
            payloads = [migrate.decode_page(migrate.encode_page(leaves))
                        for leaves in src.export_chain_read(pages, 0,
                                                            len(pages))]
        finally:
            src.export_chain_end(pages)
        assert digests == migrate.expected_chain(prompt, PAGE)
        assert dst.import_chain(prompt, payloads) == 2
        assert dst.generate(prompt, max_new_tokens=PAGE).tokens == want
        assert dst.metrics.get("serve_prefix_hit_pages_total").value == 2
    finally:
        src.stop()
        dst.stop()


# ------------------------------------------------- (e) the routing ------
def _route_inputs(seed=0, t=64, d=32, e=16):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.normal(size=(t, d)), jnp.float32),
            jnp.asarray(rng.normal(size=(d, e)) * 0.3, jnp.float32),
            jnp.asarray(rng.normal(size=(e,)) * 0.2, jnp.float32))


def test_one_group_is_the_rule_without_a_limit():
    """``route_groups`` 1: the numbers of the sigmoid-plus-bias rule the
    other latent configuration runs."""
    h, w, bias = _route_inputs()
    idx, wts = rd.route(h, w, 4, bias, 2.5)
    idx1, wts1 = rd.route(h, w, 4, bias, 2.5, 0.0, 1, 1)
    assert np.array_equal(idx, idx1) and np.array_equal(wts, wts1)


def test_the_group_limit_keeps_the_choice_inside_the_kept_groups():
    """4 groups of which 2 are kept: no chosen expert lies outside the two
    groups whose two largest biased scores sum highest; the limit binds
    (without it some token chooses elsewhere); the bias moves the choice
    and never the weight."""
    h, w, bias = _route_inputs(1)
    idx, wts = (np.asarray(t) for t in rd.route(h, w, 4, bias, 2.5, 0.0, 4,
                                                2))
    scores = np.asarray(jax.nn.sigmoid(h @ w))
    biased = scores + np.asarray(bias)
    top2 = np.sort(biased.reshape(-1, 4, 4), -1)[..., -2:].sum(-1)
    kept = np.argsort(-top2, -1)[:, :2]
    assert all(set(idx[t] // 4) <= set(kept[t]) for t in range(len(idx)))
    free, _ = rd.route(h, w, 4, bias, 2.5)
    assert not np.array_equal(np.sort(np.asarray(free), -1), np.sort(idx, -1))
    chosen = np.take_along_axis(scores, idx, -1)
    np.testing.assert_allclose(
        wts, chosen / chosen.sum(-1, keepdims=True) * 2.5, rtol=1e-6)
    unbiased, _ = rd.route(h, w, 4, jnp.zeros_like(bias), 2.5, 0.0, 4, 2)
    assert not np.array_equal(np.sort(np.asarray(unbiased), -1),
                              np.sort(idx, -1))


# ------------------------------------------- (f) the share adds up ------
def test_the_four_shares_of_a_layer_add_up_to_the_uncut_layer(reference):
    """The expert layer told it holds experts ``[first, first + 4)`` of 16,
    for the four blocks in turn, each with that block's weights: the four
    routed parts summed equal the dropless layer that holds all 16 — and,
    with the shared expert counted once, the uncut layer of the uncut
    reference (every expert applied and masked)."""
    ref, arch = reference
    rng = np.random.default_rng(9)
    t, d, f, e = 48, 64, 32, 16
    x = jnp.asarray(rng.normal(size=(t, d)), jnp.float32)
    w_gu = jnp.asarray(rng.normal(size=(e, d, 2 * f)) * 0.1, jnp.float32)
    w_d = jnp.asarray(rng.normal(size=(e, f, d)) * 0.1, jnp.float32)
    h, w, bias = _route_inputs(2, t, d, e)
    idx, wts = rd.route(x, w, 4, bias, 2.5, 0.0, 4, 2)
    whole, sizes = rd.routed_experts(x, idx, wts, w_gu, w_d,
                                     use_pallas=False, activation="silu")
    parts, computed = 0.0, 0
    for first in range(0, e, 4):
        part, rows = rd.routed_experts(
            x, idx, wts, w_gu[first:first + 4], w_d[first:first + 4],
            use_pallas=False, activation="silu", held=(first, 4))
        assert np.array_equal(rows, sizes[first:first + 4])
        parts, computed = parts + part, computed + int(rows.sum())
    assert computed == t * 4
    np.testing.assert_allclose(parts, whole, rtol=1e-5, atol=1e-6)
    full = ref.routing_weights(jax.nn.sigmoid(x @ w), bias, 4, 2.5, 4, 2)
    with jax.default_matmul_precision("highest"):
        uncut = ref._experts(x, full, w_gu, w_d, None)
    np.testing.assert_allclose(parts, uncut, rtol=1e-4, atol=1e-5)


def test_the_grouped_kernel_never_computes_an_absent_pair():
    """Through the Pallas grouped matmul (interpret mode): the rows behind
    the last held group are no group's, and what they hold does not reach
    the output."""
    rng = np.random.default_rng(10)
    x = jnp.asarray(rng.normal(size=(32, 128)), jnp.float32)
    w_gu = jnp.asarray(rng.normal(size=(4, 128, 256)) * 0.1, jnp.float32)
    w_d = jnp.asarray(rng.normal(size=(4, 128, 128)) * 0.1, jnp.float32)
    idx = jnp.asarray(rng.integers(0, 16, (32, 4)), jnp.int32)
    wts = jnp.asarray(rng.uniform(size=(32, 4)), jnp.float32)
    want, rows = rd.routed_experts(x, idx, wts, w_gu, w_d, use_pallas=False,
                                   activation="silu", held=(4, 4))
    got, rows_k = rd.routed_experts(x, idx, wts, w_gu, w_d,
                                    use_pallas="interpret",
                                    activation="silu", held=(4, 4))
    assert np.array_equal(rows, rows_k)
    assert int(rows.sum()) == int(((idx >= 4) & (idx < 8)).sum())
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


# --------------------------------------------- (g) plans and pages ------
def test_serving_memory_plan_counts_the_matrix_entry(toy):
    model, _ = toy
    plan = serving_memory_plan(model, num_slots=4, max_seq_len=160,
                               kv_page_size=PAGE, kv_pool_pages=41)
    per_layer = (4 * 8 * 8 + 3 * 3 * 32) * 4
    assert plan["state_bytes_per_page"] == N_LINEAR * per_layer
    assert plan["state_bytes_paged"] == 40 * N_LINEAR * per_layer
    # the one latent layer: a row of 24 + 8 values in 128 lanes, float32
    assert plan["per_token_kv_bytes"] == 128 * 4
    assert plan["kv_heads"] == 1 and plan["head_dim"] == 128


def test_the_cells_sizes_plan_7856128_bytes_of_state_a_page():
    """At ``ling-serve-longgen``'s own sizes (its configuration's build
    call and its engine: 32 heads of 128, 4 taps, seven linear layers,
    bfloat16, 385 pages of 1,024; shapes only): the filter inputs' leaf is
    ``[P, 16, 2304]``, whole bfloat16 tiles that pad nothing, and the
    plan and the gauge's source both read 7,856,128 B a page."""
    import json
    from dtf_tpu.serve.decode import state_bytes_per_page, trace_paged_init

    def load(*path):
        with open(os.path.join(ROOT, "benchmark", *path)) as f:
            return json.load(f)
    config = load("configs", "ling-3.0-flash-vl.json")
    eng = load("workloads", "ling-serve-longgen.json")["engine"]
    model, _ = build_model(
        config["build_model"]["name"], num_classes=config["num_classes"],
        dtype=jnp.bfloat16, **config["build_model"]["kwargs"])
    shapes = trace_paged_init(model, eng["kv_page_size"],
                              eng["kv_pool_pages"])[0]
    entries = [leaf for path, leaf in
               jax.tree_util.tree_leaves_with_path(shapes)
               if path[-1].key == "conv_state"]
    assert [e.shape for e in entries] == [(385, 16, 2304)] * 7
    assert all(e.dtype == jnp.bfloat16 for e in entries)
    assert state_bytes_per_page(shapes) == 7_856_128
    plan = serving_memory_plan(
        model, num_slots=eng["max_batch"], max_seq_len=eng["max_seq_len"],
        kv_page_size=eng["kv_page_size"],
        kv_pool_pages=eng["kv_pool_pages"])
    assert plan["state_bytes_per_page"] == 7_856_128
    assert plan["state_bytes_paged"] == 384 * 7_856_128


def test_every_cache_leaf_is_of_a_named_kind(decoders):
    dec = decoders[False]
    shapes = jax.eval_shape(dec.fresh_cache)
    state = cache_leaves(shapes, PAGE_STATE)
    assert len(state) == 2 * N_LINEAR
    # [P, 8, 288 / 8] filter inputs (whole float32 tiles) and [P, 4, 8, 8]
    assert sorted({p.shape[1:] for _, p in state}) == [(4, 8, 8), (8, 36)]
    assert len(cache_leaves(shapes)) == 2 * N_LINEAR + 1
    assert dec.carries_state and not dec.decode_all_heads


@pytest.mark.parametrize("s,index", [(1, [2100, 1024, 5, 0]),
                                     (1024, [0, 1024, 2048, 0])],
                         ids=["step", "chunk"])
def test_latent_kernel_equals_oracle_at_pages_of_1024(s, index):
    """The latent paged kernel in interpret mode against the gather oracle
    where ONE page is 1,024 tokens — more than the kernel's block of
    tokens, so the rows a grid point holds give way."""
    rng = np.random.default_rng(11)
    page, m, hq, w, r = 1024, 4, 4, 256, 128
    pool = jnp.asarray(rng.normal(size=(13, page, w)) * 0.3, jnp.float32)
    tables = jnp.asarray(1 + np.arange(12).reshape(4, 3) % 12, jnp.int32)
    tables = jnp.pad(tables, ((0, 0), (0, m - 3)))
    q = jnp.asarray(rng.normal(size=(4, s, hq, w)) * 0.3, jnp.float32)
    idx = jnp.asarray(index, jnp.int32)
    want = pa.latent_paged_attention(q, pool, tables, idx, value_lanes=r,
                                     scale=0.1)
    got = pa.paged_flash_decode(q, pool, None, tables, idx, scale=0.1,
                                interpret=True, value_lanes=r)
    assert np.abs(np.asarray(got - want)).max() <= 2e-5


def test_the_cli_reaches_the_linear_kinds():
    """``--model routed_decoder_linear``: the registry's small size of
    these layer kinds, for ``cli/serve_main.py``."""
    model, _ = build_model("routed_decoder_linear", num_classes=256)
    assert model.carries_state and model.experts_held == (0, 8)
    assert "linear_delta" in model.layer_mixers()
    assert model.stats_names[-2:] == rd.LINEAR_STATS


def test_a_call_that_crosses_pages_unaligned_is_refused(toy):
    model, params = toy
    dec = Decoder(model.clone(use_pallas=False), params, num_slots=1,
                  max_seq_len=64, kv_page_size=PAGE, kv_pool_pages=9)
    with pytest.raises(ValueError, match="neither one token nor whole"):
        dec.model.apply(
            {"params": params, "cache": dec.fresh_cache()},
            jnp.zeros((1, PAGE + 1), jnp.int32),
            cache_index=jnp.zeros((1,), jnp.int32),
            block_table=jnp.ones((1, dec.pages_per_slot), jnp.int32),
            last_pos=jnp.zeros((1,), jnp.int32), mutable=["cache"])
