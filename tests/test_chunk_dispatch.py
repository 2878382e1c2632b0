"""The HOST side of ``Decoder.prefill_chunk``: what a chunk needs besides
its body reaches the device as ONE host array through ONE cached program
(``_chunk_operands``: it splits the array into the body's small operands
and makes the sampling key).

Held here: that program's key is the eager ``fold_in(key(seed), position)``
bit for bit; a chunk's token and logits are those of the body called the
way the parent called it (a ``jnp.asarray`` an argument, the eager key);
no VALUE of seed, start, ``sample_pos`` or temperature compiles anything
once a chunk shape is warm; and a chunk is two programs on the device.
Toy sizes, CPU."""

import collections
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib.runtime import CompileWatch
from dtf_tpu.models import build_model
from dtf_tpu.serve import Decoder
from dtf_tpu.serve import decode as sd
from test_held_params import gpt2_toy
from test_routed_state import TOY as STATE_TOY

PAGE, CHUNK, SEQ = 8, 16, 64
SEEDS = [0, 1, 2**31, 2**32 - 1]
POSITIONS = [0, 1, 2047, 32767]
# the event the benchmark counts: a run with one inside its window is not
# ``correct``
COMPILE_EVENT = CompileWatch.EVENT


def _eager_key(seed, position):
    """The parent's key: eager primitives, one small program each."""
    return jax.random.fold_in(
        jax.random.key(jnp.asarray(seed, jnp.uint32)),
        jnp.asarray(position, jnp.int32))


def _state_toy():
    """The routed decoder whose cache carries state a page (short
    convolutions beside attention); float32."""
    model, _ = build_model("routed_decoder", num_classes=128,
                           dtype=jnp.float32, **STATE_TOY)
    return model, model.init(jax.random.key(3),
                             jnp.zeros((1, PAGE), jnp.int32))["params"]


TOYS = {"gpt2": lambda: gpt2_toy(jnp.bfloat16), "routed_state": _state_toy}


# -- (a) the key, and the operands beside it ------------------------------
TEMPERATURES = [0.0, 0.8, 1e-6, 3.0e38]


@pytest.mark.parametrize("position", POSITIONS)
@pytest.mark.parametrize("seed", SEEDS)
def test_the_one_program_gives_the_eager_key_and_the_host_values(seed,
                                                                 position):
    want = np.asarray(jax.random.key_data(_eager_key(seed, position)))
    tokens = np.arange(3, 3 + CHUNK, dtype=np.int32)
    row = np.arange(1, 9, dtype=np.int32)[::-1].copy()
    start, sample_pos = position // CHUNK * CHUNK, position % CHUNK
    temperature = TEMPERATURES[SEEDS.index(seed)]
    got = sd._chunk_operands(
        sd._pack_chunk_operands(tokens, row, sample_pos, start, seed,
                                temperature), row.size)
    assert got[4].dtype == _eager_key(0, 0).dtype and got[4].shape == ()
    np.testing.assert_array_equal(
        np.asarray(jax.random.key_data(got[4])), want)
    # the other five: the host's values at the body's avals, strong-typed
    for x, value in zip(got[:4] + got[5:],
                        (tokens[None], row[None], np.int32(sample_pos),
                         np.float32(temperature), np.int32(start))):
        assert (x.shape, x.dtype, x.weak_type) == (
            value.shape, value.dtype, False)
        np.testing.assert_array_equal(np.asarray(x), value)
    # the traceable function itself, as the lowering checks call it
    np.testing.assert_array_equal(
        np.asarray(jax.random.key_data(sd.position_key(seed, position))),
        want)


def test_the_steps_row_keys_are_the_same_keys():
    grid = [(s, p) for s in SEEDS for p in POSITIONS]
    rows = sd._seed_row_keys(
        jnp.asarray([s for s, _ in grid], jnp.uint32),
        jnp.asarray([p for _, p in grid], jnp.int32))
    want = np.stack([np.asarray(jax.random.key_data(_eager_key(s, p)))
                     for s, p in grid])
    np.testing.assert_array_equal(np.asarray(jax.random.key_data(rows)),
                                  want)


# -- (b) the same chunk --------------------------------------------------
CHUNKS = ["first", "continuation", "tail_padded_final"]


def _plan(prompt):
    """(name, start, tokens [CHUNK], sample_pos) of a prompt of two whole
    chunks and a padded tail."""
    out = []
    for name, start in zip(CHUNKS, range(0, len(prompt), CHUNK)):
        real = prompt[start:start + CHUNK]
        tokens = np.zeros((CHUNK,), np.int32)
        tokens[:len(real)] = real
        out.append((name, start, tokens, len(real) - 1))
    return out


def _parents_call(dec, cache, tokens, row, start, sample_pos, temperature,
                  seed):
    """``prefill_chunk`` as the parent made the call: every small argument
    a ``jnp.asarray`` of its own and the key from eager primitives, into
    the jitted body."""
    window = (None if dec._kernel_attn
              else (start + tokens.size) // dec.page_size)
    tok, cache, last, _ = dec._chunk(
        dec.params, cache, jnp.asarray(tokens.reshape(1, -1)),
        jnp.asarray(row.reshape(1, -1)), jnp.asarray(sample_pos, jnp.int32),
        jnp.asarray(temperature, jnp.float32),
        _eager_key(int(seed), int(start) + int(sample_pos)),
        jnp.asarray(int(start), jnp.int32), window, start == 0)
    return tok, cache, last


@pytest.fixture(scope="module", params=list(TOYS))
def decoder(request):
    model, params = TOYS[request.param]()
    return Decoder(model, params, num_slots=2, max_seq_len=SEQ,
                   kv_page_size=PAGE)


@pytest.fixture(scope="module", params=[0.0, 0.8])
def both_ways(request, decoder):
    """{chunk: ((token, logits) of ``prefill_chunk``, of the parent's
    call)}, each way on a cache of its own."""
    dec, temperature = decoder, request.param
    vocab = dec.model.vocab_size
    prompt = np.random.default_rng(11).integers(
        0, vocab, (2 * CHUNK + PAGE + 3,)).astype(np.int32)
    row = 1 + np.arange(dec.pages_per_slot, dtype=np.int32)
    seed = 2**32 - 5
    mine, theirs, out = dec.fresh_cache(), dec.fresh_cache(), {}
    for name, start, tokens, sample_pos in _plan(prompt):
        tok, mine, last = dec.prefill_chunk(
            mine, tokens, row, start, sample_pos, temperature, seed=seed)
        ptok, theirs, plast = _parents_call(
            dec, theirs, tokens, row, start, sample_pos, temperature, seed)
        out[name] = ((int(tok), np.asarray(last)),
                     (int(ptok), np.asarray(plast)))
    return out


@pytest.mark.parametrize("chunk", CHUNKS)
def test_token_and_logits_bit_equal_to_the_parents_call(both_ways, chunk):
    (tok, last), (ptok, plast) = both_ways[chunk]
    assert np.isfinite(plast).all() and np.ptp(plast) > 0
    np.testing.assert_array_equal(last, plast)
    assert tok == ptok


# -- (c) no value compiles -----------------------------------------------
class _Compiles:
    """Counts what asks the backend for a program while it is open: the
    event ``CompileWatch`` counts (raised only where the persistent cache
    is on, which tier-1 turns off) and the backend compile's own duration
    event, raised at the same call whatever the cache."""

    def __init__(self):
        self.requests = self.backend = 0

    def _on_event(self, event, **_):
        self.requests += event == COMPILE_EVENT

    def _on_duration(self, event, duration, **_):
        self.backend += event == "/jax/core/compile/backend_compile_duration"

    def __enter__(self):
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        return self

    def __exit__(self, *exc):
        jax._src.monitoring.unregister_event_listener(self._on_event)
        jax._src.monitoring.unregister_event_duration_listener(
            self._on_duration)


def test_no_value_of_the_small_arguments_compiles():
    """A kernel-path decoder (one executable a chunk shape, ``start``
    traced): after a warm call of the first-chunk and the continuation
    body, 50 chunks that differ in seed, start, ``sample_pos`` and
    temperature ask for no compilation."""
    model, params = gpt2_toy(jnp.bfloat16)
    dec = Decoder(model.clone(use_pallas="interpret"), params, num_slots=2,
                  max_seq_len=SEQ, kv_page_size=PAGE)
    assert dec._kernel_attn
    row = 1 + np.arange(dec.pages_per_slot, dtype=np.int32)
    tokens = np.arange(CHUNK, dtype=np.int32)
    cache = dec.fresh_cache()
    sd._chunk_operands.clear_cache()        # another test may have warmed it
    with _Compiles() as warm:
        for start in (0, CHUNK):
            _, cache, _ = dec.prefill_chunk(cache, tokens, row, start,
                                            CHUNK - 1, 0.0, seed=0)
    # the counter is live: the two bodies and the operands' program
    assert warm.backend >= 3 and dec.compiled_count == 2
    starts = range(0, SEQ, PAGE)            # page-aligned, chunk fits
    with _Compiles() as window:
        for i in range(50):
            start = starts[i % (len(starts) - 1)]
            tok, cache, _ = dec.prefill_chunk(
                cache, tokens, row, start, i % CHUNK, 0.01 * i,
                seed=(2**32 - 1) - 7919 * i)
        int(tok)
    assert (window.requests, window.backend) == (0, 0)
    assert dec.compiled_count == 2


# -- (d) two programs a chunk --------------------------------------------
def _programs_run(tmp_path, fn, name):
    """How many programs ``fn`` ran on the CPU backend: its executable's
    ``Execute`` events on the profiler's host plane (an eager primitive is
    a small jitted program and counts like any other)."""
    jax.profiler.start_trace(str(tmp_path / name))
    try:
        jax.block_until_ready(fn())
    finally:
        jax.profiler.stop_trace()
    [path] = glob.glob(str(tmp_path / name / "plugins/profile/*/*.xplane.pb"))
    counts = collections.Counter(
        event.name for plane in jax.profiler.ProfileData.from_file(path).planes
        for line in plane.lines for event in line.events)
    return sum(n for event, n in counts.items()
               if event.endswith("Executable::Execute"))


def test_a_chunk_is_two_programs(tmp_path, decoder):
    dec = decoder
    row = 1 + np.arange(dec.pages_per_slot, dtype=np.int32)
    tokens = np.arange(CHUNK, dtype=np.int32)
    box = [dec.fresh_cache()]

    def chunks(n=4):
        for i in range(n):
            tok, box[0], _ = dec.prefill_chunk(
                box[0], tokens, row, 0, CHUNK - 1 - i, 0.5 + i, seed=3 + i)
        return tok

    chunks(1)                               # warm
    _eager_key(0, 0)
    # the counter sees small programs: the eager key alone is several
    assert _programs_run(tmp_path, lambda: _eager_key(5, 7), "eager") > 2
    assert _programs_run(tmp_path, chunks, "chunks") == 2 * 4
