"""The grouped expert product's tile rule (``routed_decoder.gmm_tile``) at
the shapes the four routed configurations bring, and the expert layer
through the Pallas kernel (interpret mode) in every class of tile the rule
can return, against the dense oracle."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from dtf_tpu.models import routed_decoder as rd  # noqa: E402

# configuration: (experts held, k, width, expert width)
CONFIGS = {
    "lfm2": (32, 4, 2048, 1792),
    "smallthinker": (64, 6, 2560, 768),
    "joyai": (256, 8, 2048, 768),
    "ling": (128, 8, 2560, 768),
}
# (configuration, tokens of the call): the cells' decode steps and the
# chunks their engines cut -> the gate/up and the down product's tile.
# Under 128 rows an expert a call streams weights and keeps the tile every
# call had before the rule; from 128 on (LFM2's chunks of 1,024 tokens and
# more): the widest column tile that fits, beside row tiles of 64 where
# those of 128 leave no room for it.
WANT = {
    ("lfm2", 96): ((128, 2048, 512), (128, 1792, 512)),
    ("lfm2", 256): ((128, 2048, 512), (128, 1792, 512)),
    ("lfm2", 512): ((128, 2048, 512), (128, 1792, 512)),
    ("lfm2", 1024): ((64, 2048, 1792), (128, 1792, 1024)),
    ("lfm2", 1536): ((64, 2048, 1792), (128, 1792, 1024)),
    ("lfm2", 2048): ((64, 2048, 1792), (128, 1792, 1024)),
    ("smallthinker", 16): ((128, 2560, 768), (128, 768, 1280)),
    ("smallthinker", 512): ((128, 2560, 768), (128, 768, 1280)),
    ("smallthinker", 1024): ((128, 2560, 768), (128, 768, 1280)),
    ("joyai", 24): ((128, 2048, 768), (128, 768, 512)),
    ("joyai", 2048): ((128, 2048, 768), (128, 768, 512)),
    ("ling", 96): ((128, 2560, 768), (128, 768, 1280)),
    ("ling", 1024): ((128, 2560, 768), (128, 768, 1280)),
}


# GLM-5.2's share (PR 49): 16 held experts of 2,048 behind a width of 6,144,
# top 8 — the first contraction of 6,144.  A decode step of 16 rows streams
# weights (the tile of before); a chunk of 2,048 tokens and a prompt's last
# chunk of 256 bring 128 rows an expert and more, and beside that
# contraction the guard of ``gmm_tile`` leaves row tiles of 64
GLM = (16, 8, 6144, 2048)
WANT_GLM = {
    16: ((128, 6144, 512), (128, 2048, 768)),
    256: ((64, 6144, 512), (128, 2048, 1536)),
    2048: ((64, 6144, 512), (128, 2048, 1536)),
}


def _tile_pr42(pairs, groups, k, n):
    """``gmm_tile`` as it stood from PR 42 to PR 48: no guard beside a long
    contraction."""
    if pairs < rd._GMM_ARITHMETIC_FROM * groups:
        return _tile_before(k, n)
    columns = ([c for c in range(n, 0, -128) if n % c == 0]
               if n % 128 == 0 else [n])

    def widest(tm, tk):
        return next((c for c in columns
                     if rd.gmm_blocks_bytes(tm, tk, c) <= rd._GMM_VMEM), 0)
    tk = k
    while not widest(64, tk) and tk % 256 == 0:
        tk //= 2
    tm = 64 if widest(64, tk) > widest(128, tk) else 128
    return tm, tk, widest(tm, tk) or columns[-1]


def _tile_before(k, n):
    """What ``routed_experts`` handed the kernel in every call up to PR 41."""
    return 128, k, next(c for c in (1280, 768, 512, 256, 128, n)
                        if n % c == 0)


@pytest.mark.parametrize("product", [0, 1], ids=["gate_up", "down"])
@pytest.mark.parametrize("config,tokens", list(WANT))
def test_the_rule_returns_a_tile_the_kernel_and_the_chip_can_take(
        config, tokens, product):
    groups, k, d, f = CONFIGS[config]
    kk, n = ((d, 2 * f), (f, d))[product]
    tm, tk, tn = rd.gmm_tile(tokens * k, groups, kk, n)
    assert n % tn == 0 and tn % 128 == 0
    assert tk == kk                     # a group reads its weights once
    assert tm % 8 == 0 and 128 % tm == 0    # the rows are padded to 128
    assert rd.gmm_blocks_bytes(tm, tk, tn) <= rd._GMM_VMEM < 16 * 2 ** 20
    assert (tm, tk, tn) == WANT[config, tokens][product]
    if tokens * k < rd._GMM_ARITHMETIC_FROM * groups:
        assert (tm, tk, tn) == _tile_before(kk, n)
    else:
        assert tn >= 1024


@pytest.mark.parametrize("product", [0, 1], ids=["gate_up", "down"])
@pytest.mark.parametrize("config,tokens", list(WANT))
def test_the_guard_beside_a_long_contraction_moves_no_call_there_was(
        config, tokens, product):
    """PR 49's clause in ``gmm_tile`` (half a byte a (row, contraction)
    element beside the blocks) was written for a contraction of 6,144: every
    call of the four configurations the benchmark had gets the tile it got
    from PR 42 to PR 48, so their kernels are the parent's."""
    groups, k, d, f = CONFIGS[config]
    kk, n = ((d, 2 * f), (f, d))[product]
    assert rd.gmm_tile(tokens * k, groups, kk, n) \
        == _tile_pr42(tokens * k, groups, kk, n)


@pytest.mark.parametrize("product", [0, 1], ids=["gate_up", "down"])
@pytest.mark.parametrize("tokens", list(WANT_GLM))
def test_a_contraction_of_6144_takes_row_tiles_of_64(tokens, product):
    """GLM-5.2's calls.  Compiled for the v5e, the kernel alone at (128,
    6144, 512) is refused for 16.95 MiB: 15.50 of the pipeline's
    double-buffered blocks — which is what the compiler reports, to the
    0.01 MiB, wherever those alone pass 16 (19.00 at (256, 6144, 512),
    28.00 at (128, 6144, 1024), 16.75 at (128, 2048, 1792)) — and 1.45 of
    accumulator and of the body's own temporaries, where
    ``gmm_blocks_bytes`` counts 0.31 for them.  The guard is fitted to that
    refusal, on the safe side of it; what holds it to the compiler is
    ``tests/test_tpu_lowering.py``'s compile of both GLM bodies."""
    groups, k, d, f = GLM
    kk, n = ((d, 2 * f), (f, d))[product]
    tile = rd.gmm_tile(tokens * k, groups, kk, n)
    assert tile == WANT_GLM[tokens][product]
    arithmetic = tokens * k >= rd._GMM_ARITHMETIC_FROM * groups
    assert arithmetic == (tokens >= 256)
    if arithmetic and product == 0:
        assert _tile_pr42(tokens * k, groups, kk, n) == (128, 6144, 512)
        assert tile[0] == 64
    elif arithmetic:
        assert tile == _tile_pr42(tokens * k, groups, kk, n)
    assert n % tile[2] == 0 and tile[1] == kk
    assert rd.gmm_blocks_bytes(*tile) <= rd._GMM_VMEM


# the longest chunk each of the three cells' engines cuts: 1,024, 2,048
# and 1,024 tokens
def _chunk(cell):
    with open(os.path.join(ROOT, "benchmark", "workloads",
                           cell + ".json")) as f:
        return json.load(f)["engine"]["prefill_chunk"]


CHUNK = {"smallthinker": _chunk("smallthinker-serve-mixedctx"),
         "joyai": _chunk("joyai-serve-longctx"),
         "ling": _chunk("ling-serve-longgen")}


@pytest.mark.parametrize("config,tokens", [
    (c, t) for c, longest in CHUNK.items()
    for t in (1, 16, 96, 256, 512, 1024, 2048) if t <= longest])
def test_the_three_streaming_configurations_keep_their_kernels(config,
                                                               tokens):
    """No call of SmallThinker's, JoyAI's or Ling's cell brings 128 rows
    an expert: both products run the tile they ran before the rule, so
    their kernels are the parent's."""
    groups, k, d, f = CONFIGS[config]
    for kk, n in ((d, 2 * f), (f, d)):
        assert rd.gmm_tile(tokens * k, groups, kk, n) == _tile_before(kk, n)


@pytest.mark.parametrize("tm,tk,tn,fits", [
    (64, 2048, 1792, True),         # compiled for the v5e: 15.92 MiB, passes
    (320, 896, 2048, False),        # refused there: 16.21 of 16 MiB
    (512, 2048, 896, False),        # refused there
    (128, 2048, 1792, False),       # refused there
    (128, 1792, 1024, True),
])
def test_the_budget_is_the_compilers(tm, tk, tn, fits):
    assert (rd.gmm_blocks_bytes(tm, tk, tn) <= rd._GMM_VMEM) == fits


def test_the_refused_tile_counts_what_the_compiler_counted():
    """"Scoped allocation with size 16.21M and limit 16.00M": the message
    of the compile for the v5e of (320, 896, 2048)."""
    assert int(rd.gmm_blocks_bytes(320, 896, 2048) / 2 ** 20 * 100) == 1621


def test_a_contraction_no_tile_fits_beside_is_halved_until_one_does():
    tm, tk, tn = rd.gmm_tile(1024, 8, 32768, 1024)
    assert (tm, tk, tn) == (64, 16384, 128)
    assert rd.gmm_blocks_bytes(tm, tk, tn) <= rd._GMM_VMEM


@pytest.mark.parametrize("pairs", [15, 1024], ids=["stream", "arithmetic"])
@pytest.mark.parametrize("k,n", [(128, 64), (256, 192)])
def test_a_width_of_no_whole_lane_tiles_is_its_own_column_tile(pairs, k, n):
    """... and costs the contraction nothing: it stays whole."""
    assert rd.gmm_tile(pairs, 8, k, n) == (128, k, n)


# the classes of tile the rule can return, each at a toy shape that lands
# in it: (tokens, width, expert width, VMEM budget the test sets, the
# gate/up product's tile it then expects), 4 experts top-2
CLASSES = {
    # a streaming call: the tile of before, all 256 columns in one tile
    "stream": (40, 128, 128, None, (128, 128, 256)),
    # 128 rows an expert: the widest column tile, where before took 512
    "arithmetic": (256, 256, 512, None, (128, 256, 1024)),
    # the same under a budget that fits it only beside row tiles of 64
    "rows_of_64": (256, 256, 512, 2_000_000, (64, 256, 1024)),
    # the same under a budget nothing fits beside the whole contraction
    "half_k": (256, 256, 512, 250_000, (64, 128, 128)),
}


def _routing(case, t, e, k, rng):
    logits = rng.normal(size=(t, e))
    if case == "all_to_one":            # every token's first choice: expert 2
        logits[:, 2] += 100.0           # (a group of several row tiles)
    elif case == "one_empty":           # nobody takes expert 1
        logits[:, 1] -= 100.0
    vals, idx = jax.lax.top_k(jnp.asarray(logits, jnp.float32), k)
    return idx, jax.nn.softmax(vals, -1)


@pytest.mark.parametrize("held", [False, True], ids=["all", "held"])
@pytest.mark.parametrize("case", ["uneven", "all_to_one", "one_empty"])
@pytest.mark.parametrize("tile_class", list(CLASSES))
def test_every_tile_class_equals_the_dense_oracle(monkeypatch, tile_class,
                                                  case, held):
    """Through the Pallas grouped matmul (interpret mode), uneven, empty
    and many-tile groups, all experts here and a held share of twice as
    many: what the sorted, grouped path gives is what every expert on
    every token, masked by the routing weights, gives — and with a share
    held, what ``ragged_dot`` gives for the same share, pair counts
    included."""
    t, d, f, vmem, want = CLASSES[tile_class]
    if vmem is not None:
        monkeypatch.setattr(rd, "_GMM_VMEM", vmem)
    e, k = 4, 2
    assert rd.gmm_tile(t * k, e, d, 2 * f) == want
    rng = np.random.default_rng(len(tile_class) + len(case))
    total = 2 * e if held else e
    x = jnp.asarray(rng.normal(size=(t, d)), jnp.float32)
    wgu = jnp.asarray(rng.normal(size=(total, d, 2 * f)) * 0.1, jnp.float32)
    wd = jnp.asarray(rng.normal(size=(total, f, d)) * 0.1, jnp.float32)
    if not held:
        idx, w = _routing(case, t, e, k, rng)
        got, sizes = rd.routed_experts(x, idx, w, wgu, wd,
                                       use_pallas="interpret",
                                       activation="silu")
        want_y = rd.routed_experts_dense(x, idx, w, wgu, wd,
                                         activation="silu")
        assert int(sizes.sum()) == t * k
        np.testing.assert_allclose(np.asarray(got), np.asarray(want_y),
                                   rtol=1e-5, atol=1e-5)
        return
    # the router chooses among 2e experts, this device holds e of them
    # starting at `first`; the case's experts 1 and 2 are among the held
    first = 2
    idx, w = _routing(case, t, total, k, rng)
    idx = (idx + first) % total
    share = slice(first, first + e)
    got, rows_k = rd.routed_experts(x, idx, w, wgu[share], wd[share],
                                    use_pallas="interpret",
                                    activation="silu", held=(first, e))
    want_y, rows = rd.routed_experts(x, idx, w, wgu[share], wd[share],
                                     use_pallas=False, activation="silu",
                                     held=(first, e))
    assert np.array_equal(rows, rows_k)
    assert int(rows.sum()) == int(((idx >= first) & (idx < first + e)).sum())
    np.testing.assert_allclose(got, want_y, rtol=1e-4, atol=1e-5)
