"""The layers of the routed decoder's GATED forms against their own oracles
(``tests/test_gated_decoder.py`` holds the whole model to the plain
reference): ``LinearDelta`` with a decay a head under fewer key heads and
the ``silu(z)`` gate, ``GroupedQueryAttention`` at the published head
geometry with a partial rotary and the output gate, ``RoutedBlock``'s
gated shared expert and the four shares of its experts; and, through
``ServeEngine``, a prefix hit over pages that carry K, V AND state.
float32 throughout."""

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
from dtf_tpu.ops import linear_state  # noqa: E402
from dtf_tpu.serve.engine import ServeEngine  # noqa: E402

from test_gated_decoder import (  # noqa: E402,F401
    CHUNK, F32, PAGE, VOCAB, _noisy, toy)


def _delta_inputs(seed=0, b=2, s=24, hv=4, hk=2, dh=8):
    rng = np.random.default_rng(seed)
    q, k = (rng.standard_normal((b, s, hk, dh)).astype(np.float32)
            for _ in range(2))
    v = rng.standard_normal((b, s, hv, dh)).astype(np.float32)
    g = -np.exp(rng.uniform(-6, 1, (b, s, hv))).astype(np.float32)
    beta = rng.uniform(0, 1, (b, s, hv)).astype(np.float32)
    return q, k, v, g, beta


def test_the_scalar_decay_under_fewer_key_heads_is_the_state_forms_own():
    """A decay a HEAD and 2 key heads under 4 value heads: the literal
    recurrence written for exactly that (one scalar, a key row a pair of
    value heads) is ``linear_state.recurrent`` fed the broadcast decay and
    the repeated keys, and the blocked form of a chunk gives the same."""
    q, k, v, g, beta = _delta_inputs()
    b, s, hv, dh = v.shape
    state = np.zeros((b, hv, dh, dh), np.float64)       # [key, value]
    want = np.zeros((b, s, hv, dh))
    for t in range(s):
        for h in range(hv):
            kt, qt = k[:, t, h // 2], q[:, t, h // 2]
            st = np.exp(g[:, t, h])[:, None, None] * state[:, h]
            u = (v[:, t, h] - np.einsum("bk,bkv->bv", kt, st)) \
                * beta[:, t, h, None]
            state[:, h] = st + kt[:, :, None] * u[:, None, :]
            want[:, t, h] = np.einsum("bk,bkv->bv", qt, state[:, h])
    a = np.broadcast_to(g[..., None], (b, s, hv, dh))
    qr, kr = (np.repeat(x, 2, axis=2) for x in (q, k))
    got, last = linear_state.recurrent(qr, kr, v, a, beta)
    tol = dict(rtol=1e-4, atol=1e-5)        # float32 against float64
    np.testing.assert_allclose(got, want, **tol)
    np.testing.assert_allclose(np.swapaxes(last, -1, -2), state, **tol)
    blocked, _ = linear_state.chunked(qr, kr, v, a, beta, block=8)
    np.testing.assert_allclose(blocked, want, **tol)


def _linear_layer(**kw):
    return rd.LinearDelta(4, 8, 4, -5.0, 1e-6, F32, F32, key_heads=2,
                          decay="head", gate="silu", **kw)


def test_the_gated_delta_layer_against_the_recurrence_by_hand():
    """``LinearDelta`` with a decay a head, 2 key heads under 4 value heads
    and the ``silu(z)`` gate, from its own parameters by hand: the filter,
    the norms, ``linear_state.recurrent`` fed the broadcast decay and the
    repeated keys, the plain-weight norm."""
    layer = _linear_layer()
    h = jnp.asarray(np.random.default_rng(1).standard_normal((2, 20, 32)),
                    F32)
    params = _noisy(jax.jit(layer.init)(jax.random.key(1), h)["params"])

    @jax.jit
    def by_hand(params, h):
        pre = h @ params["qkvz"]
        pre, z = pre[..., :64], pre[..., 64:]
        padded = jnp.pad(pre, ((0, 0), (3, 0), (0, 0)))
        mixed = jax.nn.silu(sum(params["taps"][:, j] * padded[:, j:j + 20]
                                for j in range(4)))
        q, k = (mixed[..., i * 16:(i + 1) * 16].reshape(2, 20, 2, 8)
                for i in range(2))
        v = mixed[..., 32:].reshape(2, 20, 4, 8)
        q, k = (x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)
                for x in (q, k))
        ba = h @ params["ba"]
        g = -jnp.exp(params["a_log"]) * jax.nn.softplus(
            ba[..., 4:] + params["dt_bias"])
        o, _ = linear_state.recurrent(
            jnp.repeat(q * 8 ** -0.5, 2, 2), jnp.repeat(k, 2, 2), v,
            jnp.broadcast_to(g[..., None], v.shape),
            jax.nn.sigmoid(ba[..., :4]))
        o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + 1e-6) \
            * params["out_norm"]
        return (o.reshape(2, 20, 32) * jax.nn.silu(z)) @ params["out"]
    with jax.default_matmul_precision("highest"):
        got, _ = jax.jit(layer.apply)({"params": params}, h)
        want = by_hand(params, h)
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_its_paged_step_is_its_oracles():
    """One token a row through the pool: the kernel (interpreted) against
    ``paged_step``, after a chunk of two pages wrote the rows' entries —
    outputs and both state leaves, rows on a page's last token, on its
    first and an idle one among them."""
    h = jnp.asarray(np.random.default_rng(2).standard_normal((3, 2 * PAGE,
                                                              32)), F32)
    table = jnp.asarray([[1, 2, 3], [4, 5, 6], [0, 0, 0]], jnp.int32)
    out = {}
    for use_pallas in (False, "interpret"):
        layer = _linear_layer(decode=True, kv_page_size=PAGE,
                              kv_pool_pages=8, use_pallas=use_pallas)
        zeros = jnp.zeros((3,), jnp.int32)
        both = jax.jit(layer.init)(jax.random.key(1), h, zeros, table)
        params, cache = _noisy(both["params"]), both["cache"]
        last = jnp.asarray([2 * PAGE - 1, PAGE, 0], jnp.int32)
        call = jax.jit(lambda cache, x, index, last: layer.apply(
            {"params": params, "cache": cache}, x, index, table, last,
            mutable=["cache"]))
        with jax.default_matmul_precision("highest"):
            _, mut = call(cache, h, zeros, last)
            (y, advanced), mut = call(
                mut["cache"], h[:, :1],
                jnp.asarray([2 * PAGE, PAGE + 1, 0], jnp.int32), None)
        assert int(advanced) == 2               # the idle row: scratch page
        out[use_pallas] = (y, mut["cache"]["linear_state"],
                           mut["cache"]["conv_state"])
    for got, want in zip(out["interpret"], out[False]):
        # page 0 is every idle row's scratch: nobody reads it
        np.testing.assert_allclose(got[1:] if got.ndim > 3 else got,
                                   want[1:] if want.ndim > 3 else want,
                                   atol=1e-5)


def _plain_gated_attention(params, h, hq, hkv, dh, rotary, theta, eps):
    """Softmax attention written out, numpy float64."""
    p = {k: np.asarray(v, np.float64) for k, v in params.items()}
    b, s, _ = h.shape
    qkv = np.asarray(h, np.float64) @ p["qkv"]
    q = qkv[..., :hq * dh].reshape(b, s, hq, dh)
    k = qkv[..., hq * dh:(hq + hkv) * dh].reshape(b, s, hkv, dh)
    v = qkv[..., (hq + hkv) * dh:(hq + 2 * hkv) * dh].reshape(b, s, hkv, dh)
    gate = qkv[..., (hq + 2 * hkv) * dh:]

    def norm(x, w):
        return x / np.sqrt(np.mean(x * x, -1, keepdims=True) + eps) * (1 + w)
    q, k = norm(q, p["q_norm"]), norm(k, p["k_norm"])
    angle = np.arange(s)[:, None] * theta ** (
        -np.arange(0, rotary, 2) / rotary)              # [S, rotary / 2]
    cos, sin = np.cos(angle)[None, :, None], np.sin(angle)[None, :, None]

    def turn(x):
        x1, x2 = x[..., :rotary // 2], x[..., rotary // 2:rotary]
        return np.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                               x[..., rotary:]], -1)
    q, k = turn(q), turn(k)
    o = np.zeros((b, s, hq, dh))
    for i in range(hq):
        kv = i // (hq // hkv)
        sc = np.einsum("bqd,bkd->bqk", q[:, :, i], k[:, :, kv]) / np.sqrt(dh)
        sc = np.where(np.tril(np.ones((s, s), bool)), sc, -np.inf)
        w = np.exp(sc - sc.max(-1, keepdims=True))
        o[:, :, i] = (w / w.sum(-1, keepdims=True)) @ v[:, :, kv]
    o = o.reshape(b, s, hq * dh) / (1 + np.exp(-gate))
    return o @ p["out"]


@pytest.mark.parametrize("paged", [False, "interpret"],
                         ids=["whole_sequence", "paged_kernels"])
def test_heads_of_256_with_a_partial_rotary_and_the_gate_are_plain_softmax(
        paged):
    """The published head geometry — 8 query heads a KV head of 256 lanes,
    the first 64 turning, zero-centred norms of q and k, the output times
    the sigmoid of the gate that rides the query projection — against
    softmax attention written out; then through the pools, a first chunk
    (the flash forward), a continuation chunk and a decode step (the paged
    kernel), interpreted."""
    hq, hkv, dh, s, page = 8, 1, 256, 48, 16
    attn = rd.GroupedQueryAttention(
        hq, hkv, dh, None, 1e7, F32, F32, qk_norm_eps=1e-6, rotary_dim=64,
        output_gate=True, qk_norm_unit_offset=True, use_pallas=paged,
        decode=bool(paged), kv_page_size=page if paged else None,
        kv_pool_pages=5 if paged else None)
    rng = np.random.default_rng(4)
    h = jnp.asarray(rng.standard_normal((1, s, 32)), F32)
    positions = jnp.arange(s, dtype=jnp.int32)[None]
    table = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
    zero = jnp.zeros((1,), jnp.int32)
    both = jax.jit(attn.init)(jax.random.key(2), h[:, :page],
                              positions[:, :page], zero, table)
    params = _noisy(both["params"])
    assert params["qkv"].shape == (32, (2 * hq + 2 * hkv) * dh)
    want = _plain_gated_attention(params, h, hq, hkv, dh, 64, 1e7, 1e-6)
    with jax.default_matmul_precision("highest"):
        if not paged:
            got = jax.jit(attn.apply)({"params": params}, h, positions)
        else:
            call = jax.jit(
                lambda cache, x, pos, at, first: attn.apply(
                    {"params": params, "cache": cache}, x, pos, at, table,
                    first, mutable=["cache"]), static_argnums=4)
            cache, got = both["cache"], []
            for start, n, first in ((0, 16, True), (16, 16, False),
                                    (32, 15, None), (47, 1, None)):
                for at in ([start] if first is not None
                           else range(start, start + n)):
                    m = n if first is not None else 1
                    y, mut = call(cache, h[:, at:at + m],
                                  positions[:, at:at + m],
                                  jnp.asarray([at], jnp.int32), bool(first))
                    cache = mut["cache"]
                    got.append(y)
            got = jnp.concatenate(got, 1)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_the_rotary_part_and_the_gate_change_the_layer():
    """Not vacuous: the same parameters under a rotary over the whole head,
    or without the gate's sigmoid, give another output."""
    kw = dict(qk_norm_eps=1e-6, output_gate=True, qk_norm_unit_offset=True)
    h = jnp.asarray(np.random.default_rng(5).standard_normal((1, 12, 32)),
                    F32)
    positions = jnp.arange(12, dtype=jnp.int32)[None]
    part = rd.GroupedQueryAttention(4, 2, 16, None, 1e4, F32, F32,
                                    rotary_dim=4, **kw)
    whole = rd.GroupedQueryAttention(4, 2, 16, None, 1e4, F32, F32, **kw)
    params = jax.jit(part.init)(jax.random.key(0), h, positions)["params"]
    # ... and a gain is an initialiser: 2 is w = 1 under 1 + w
    gained = rd.GroupedQueryAttention(4, 2, 16, None, 1e4, F32, F32,
                                      qk_norm_gain=2.0, **kw)
    started = jax.jit(gained.init)(jax.random.key(0), h, positions)["params"]
    assert float(params["q_norm"][0]) == 0.0
    assert float(started["q_norm"][0]) == float(started["k_norm"][0]) == 1.0
    a = jax.jit(part.apply)({"params": params}, h, positions)
    b = jax.jit(whole.apply)({"params": params}, h, positions)
    assert float(jnp.abs(a - b).max()) > 1e-3 * float(jnp.abs(a).max())
    # position 0 turns by nothing: there the two are one
    np.testing.assert_allclose(a[:, 0], b[:, 0], atol=1e-6)


def _block(held=None, gate=True):
    """One layer of a model of such layers: what the layer is comes from
    the model's ``layer_specs()``, as the model's own loop takes it."""
    model = rd.RoutedDecoderLM(
        vocab_size=8, num_layers=1, num_heads=4, num_kv_heads=2, head_dim=16,
        num_experts=16, experts_per_token=4, expert_width=32,
        layer_window=(False,), layer_rope=(True,), rope_theta=1e7,
        shared_expert_width=32, activation="silu",
        router_input="post_attention", qk_norm=True, norm_unit_offset=True,
        rotary_dim=4, attention_output_gate=True, shared_expert_gate=gate,
        experts_held=held)
    return rd.RoutedBlock(model.layer_specs()[0], 1e-6, F32, F32,
                          norm_unit_offset=True)


def test_the_four_shares_and_the_shared_expert_once_make_the_uncut_layer():
    """THE SHARES ADD UP: a layer whose device holds experts 4r .. 4r + 3
    (r = 0 .. 3) computes its part of the routed sum plus the gated shared
    expert; the four parts, the shared expert and the residual stream
    counted once, are the layer that holds all 16."""
    rng = np.random.default_rng(6)
    x = jnp.asarray(rng.standard_normal((1, 24, 64)), F32)
    positions = jnp.arange(24, dtype=jnp.int32)[None]
    whole = _block()
    params = _noisy(jax.jit(whole.init)(jax.random.key(4), x,
                                        positions)["params"])
    with jax.default_matmul_precision("highest"):
        want, sizes, *_ = jax.jit(whole.apply)({"params": params}, x,
                                               positions)
        # what every share computes alike: the mixer's residual and the
        # gated shared expert, from a share that holds an expert nobody
        # chose... there is none: take it from the parts instead
        parts, rows = [], []
        for r in range(4):
            share = dict(params, gate_up=params["gate_up"][4 * r:4 * r + 4],
                         down=params["down"][4 * r:4 * r + 4])
            y, held_rows, *_ = jax.jit(_block((4 * r, 4)).apply)(
                {"params": share}, x, positions)
            parts.append(y)
            rows.append(held_rows)
        # without any routed expert: x + mixer + gated shared expert
        none = dict(params, gate_up=0 * params["gate_up"],
                    down=0 * params["down"])
        common, *_ = jax.jit(whole.apply)({"params": none}, x, positions)
    np.testing.assert_allclose(sum(parts) - 3 * common, want, atol=1e-5)
    np.testing.assert_array_equal(np.concatenate(rows), sizes)
    assert int(sizes.sum()) == 24 * 4           # every pair computed once


def test_the_shared_experts_gate_is_one_scalar_a_token():
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.standard_normal((1, 6, 64)), F32)
    positions = jnp.arange(6, dtype=jnp.int32)[None]
    gated = _block()
    params = _noisy(jax.jit(gated.init)(jax.random.key(5), x,
                                        positions)["params"])
    plain = {k: v for k, v in params.items() if k != "shared_gate"}
    none = dict(plain, shared_gate_up=0 * params["shared_gate_up"])
    with jax.default_matmul_precision("highest"):
        a, *_ = jax.jit(gated.apply)({"params": params}, x, positions)
        ungated = jax.jit(_block(gate=False).apply)
        b, *_ = ungated({"params": plain}, x, positions)
        c, *_ = ungated({"params": none}, x, positions)
    # a - c is the gated shared expert, b - c the ungated one: their
    # quotient is one number a token, sigmoid(h2 . w)
    gated_y, plain_y = np.asarray(a - c)[0], np.asarray(b - c)[0]
    ratio = (gated_y * plain_y).sum(-1) / (plain_y * plain_y).sum(-1)
    np.testing.assert_allclose(gated_y, ratio[:, None] * plain_y, atol=1e-6)
    assert ((ratio > 0) & (ratio < 1)).all() and ratio.std() > 1e-3


@pytest.fixture(scope="module")
def engines(toy):
    """(an engine that shares nothing, one with prefix sharing on: the
    default)."""
    model, params = toy
    kw = dict(max_batch=2, max_seq_len=128, max_delay_s=0.0,
              kv_page_size=PAGE, kv_pool_pages=33, prefill_chunk=CHUNK,
              seed=3)
    plain = ServeEngine(model, params, prefix_sharing=False, **kw)
    shared = ServeEngine(model, params, **kw)
    yield plain, shared
    plain.stop()
    shared.stop()


@pytest.mark.parametrize("plen", [3 * PAGE, 3 * PAGE + 5],
                         ids=["whole_prompt_registered", "prefix_registered"])
def test_a_prefix_hit_resumes_from_the_pages_k_v_and_state(engines, plen):
    """Through ``ServeEngine`` with prefix sharing on (the default; no
    cell's traffic reaches it): a second request whose leading whole pages
    are registered takes them — K and V AND the state entries they carry —
    and serves the tokens of an engine that shares nothing.  A cache that
    carries state cannot replay a token on a copied page, so the engine
    prefills the last page again from the carry of the page before it and
    copies nothing."""
    plain, shared = engines
    prompt = np.random.default_rng(plen).integers(1, VOCAB, plen,
                                                  dtype=np.int32)
    longer = np.concatenate([prompt[:2 * PAGE],
                             (prompt[2 * PAGE:] + 1) % VOCAB])
    want = plain.generate(prompt, max_new_tokens=PAGE + 2).tokens
    other = plain.generate(longer, max_new_tokens=PAGE + 2).tokens
    assert shared.generate(prompt, max_new_tokens=PAGE + 2).tokens == want
    hits = shared.metrics.get("serve_prefix_hit_pages_total")
    before = hits.value
    assert shared.generate(prompt, max_new_tokens=PAGE + 2).tokens == want
    assert hits.value - before == (plen - 1) // PAGE
    # another request that shares the two leading pages and no more
    before = hits.value
    assert shared.generate(longer, max_new_tokens=PAGE + 2).tokens == other
    assert hits.value - before == 2
    assert shared.metrics.get("serve_prefix_cow_total").value == 0
    assert shared.metrics.get("serve_state_bytes_per_page").value \
        == 3 * (4 * 8 * 8 + 3 * 64) * 4
    assert shared.metrics.get("serve_kv_bytes_per_token").value \
        == 2 * 2 * 16 * 4
