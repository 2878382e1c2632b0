"""The one generator of request traffic.  A traffic mix is a data file of
parameters under ``benchmark/traffic/``; the program sees only the
requests made here.

The schedule — when each request falls due, how long its prompt is and
how many tokens it asks for — belongs to the mix: it is drawn from the
mix's own ``base_seed``, phase by phase, and is the same for every run.
The run's ``--seed`` draws the token ids (and, in the driver, the
weights).  In a queueing system the order of arrivals IS the work: with
the same multiset of sizes and gaps in another order per seed, the steady
cell's p90 time to first token spread 29 % over six seeds and its
tokens/s 12 % (my chip runs, PR 23), which no bound of at most 10 % can
hold.  So a difference between seeds is noise of the system, not of the
draw; another schedule is another mix (a data file with another
``base_seed``).
"""

from __future__ import annotations

import dataclasses
from typing import List

import numpy as np


@dataclasses.dataclass(frozen=True)
class Request:
    due_s: float            # offset from the start of the load (open loop)
    prompt: np.ndarray      # int32 token ids
    max_new_tokens: int


def _lognormal_clipped(rng, n: int, median: float, sigma: float,
                       lo: int, hi: int) -> np.ndarray:
    x = rng.lognormal(mean=np.log(median), sigma=sigma, size=n)
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def _snap(values: np.ndarray, grid: List[int]) -> np.ndarray:
    """Each value to the nearest grid point (ties to the lower)."""
    grid_a = np.asarray(sorted(grid), np.int64)
    idx = np.abs(values[:, None] - grid_a[None, :]).argmin(axis=1)
    return grid_a[idx]


def request_sizes(mix: dict, n: int, rng) -> np.ndarray:
    """[n, 2] (prompt length, output budget)."""
    p, o = mix["prompt_len"], mix["output_len"]
    prompts = _lognormal_clipped(rng, n, p["median"], p["sigma"],
                                 p["min"], p["max"])
    if p.get("snap_to"):
        prompts = _snap(prompts, p["snap_to"])
    outputs = _lognormal_clipped(rng, n, o["median"], o["sigma"],
                                 o["min"], o["max"])
    return np.stack([prompts, outputs], axis=1)


def _prepared_blocks(mix: dict, length_s: float) -> List[int]:
    """How many requests a closed-loop phase of this length prepares, as
    the sizes of the blocks they are drawn in.  ``prepare_per_s`` bounds
    how many requests the phase can use up.  Where the file names a
    ``prepare_block_per_s``, the requests are drawn block by block at that
    rate from the phase's one sequential stream: raising ``prepare_per_s``
    then appends blocks and leaves every earlier request as it was (the
    first block is what the file prepared when its rate was the block's)."""
    n = int(np.ceil(float(mix["prepare_per_s"]) * length_s))
    block = int(np.ceil(float(mix.get("prepare_block_per_s",
                                      mix["prepare_per_s"])) * length_s))
    return [min(block, n - done) for done in range(0, n, block)]


def phase_draw(mix: dict, phase: int, length_s: float):
    """(sizes [n, 2], gaps [n]) of one phase of the load (ramp, window,
    tail), from the mix's base seed and the phase's number alone — never
    from the run's seed.  Open loop: exponential gaps at ``rate_per_s``
    (Poisson arrivals) until the phase is full, so every seed offers the
    same number of requests in the phase.  Closed loop: no schedule; the
    sizes of ``_prepared_blocks``, block after block."""
    rng = np.random.default_rng([int(mix["base_seed"]), phase])
    if mix["arrivals"] == "closed":
        sizes = np.concatenate([request_sizes(mix, n, rng)
                                for n in _prepared_blocks(mix, length_s)])
        return sizes, np.zeros((len(sizes),), np.float64)
    if mix["arrivals"] != "poisson":
        raise ValueError(f"unknown arrivals {mix['arrivals']!r}")
    draw = rng.exponential(1.0 / float(mix["rate_per_s"]),
                           size=int(4 * mix["rate_per_s"] * length_s) + 16)
    gaps = draw[:int(np.searchsorted(np.cumsum(draw), length_s))]
    return request_sizes(mix, len(gaps), rng), gaps


def make_requests(mix: dict, seed: int, phases_s: List[float],
                  vocab_size: int) -> List[Request]:
    """The requests of a run whose load has phases of these lengths (ramp,
    window, tail): the mix's schedule, with token ids from the run's
    seed, in the order the load thread sends them.  Open loop: by due
    time.  Closed loop: the clients take the list in order, whatever the
    clock says, so every phase's first block comes before any phase's
    further blocks — a system no faster than ``prepare_block_per_s``
    meets the requests it met before the further blocks were added."""
    rng = np.random.default_rng(int(seed))
    first, further, t0 = [], [], 0.0
    for k, length_s in enumerate(phases_s):
        sizes, gaps = phase_draw(mix, k, float(length_s))
        rows = [(float(t), int(plen), int(budget))
                for (plen, budget), t in zip(sizes, t0 + np.cumsum(gaps))]
        cut = (_prepared_blocks(mix, float(length_s))[0]
               if mix["arrivals"] == "closed" else len(rows))
        first += rows[:cut]
        further += rows[cut:]
        t0 += float(length_s)
    return [Request(t, rng.integers(0, vocab_size, size=plen,
                                    dtype=np.int32), budget)
            for t, plen, budget in first + further]


def prefill_bodies(mix: dict, prefill_chunk: int, page_size: int) -> list:
    """The distinct (chunk length, is first chunk) pairs the engine's
    chunk plan makes of this mix's prompt lengths — one compiled prefill
    body each on the kernel path (copy of the arithmetic of
    ``dtf_tpu.serve.engine.chunk_plan``)."""
    p = mix["prompt_len"]
    lengths = p.get("snap_to") or range(p["min"], p["max"] + 1)
    bodies = set()
    for plen in lengths:
        start = 0
        while plen - start > prefill_chunk:
            bodies.add((prefill_chunk, start == 0))
            start += prefill_chunk
        rem = plen - start
        bodies.add((-(-rem // page_size) * page_size, start == 0))
    return sorted(bodies)
