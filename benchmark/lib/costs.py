"""Operations and bytes the kernels that belong to no family need,
computed from shapes: the numerators of the roofline shares.  What belongs
to a model family (FLOPs per training sample, how many calls of which
shape a step makes) is in the family's file, ``benchmark/families/``.

They count what the mathematics requires: causal attention once (the
masked half is not work), recomputation (the flash backward's second look
at the scores aside — see ``flash_bwd``) not at all.  XLA's own cost
analysis is not used: it does not see inside Mosaic kernels.
"""

from __future__ import annotations


def flash_fwd(batch: int, heads: int, seq: int, head_dim: int,
              itemsize: int = 2) -> tuple:
    """(FLOPs, bytes) of one causal flash-attention forward call: the
    scores and the weighted values over the unmasked half; q, k, v read
    and o written once."""
    flops = 0.5 * 2 * 2.0 * batch * heads * seq * seq * head_dim
    nbytes = 4.0 * batch * heads * seq * head_dim * itemsize
    return flops, nbytes


def flash_bwd(batch: int, heads: int, seq: int, head_dim: int,
              itemsize: int = 2) -> tuple:
    """(FLOPs, bytes) of the backward of that call: five matmuls (scores
    again — flash attention keeps no score matrix, so this one is the
    algorithm, not remat — dP, dV, dQ, dK) over the unmasked half; q, k,
    v, o, do read and dq, dk, dv written once."""
    flops = 0.5 * 5 * 2.0 * batch * heads * seq * seq * head_dim
    nbytes = 8.0 * batch * heads * seq * head_dim * itemsize
    return flops, nbytes


def paged_decode(context_tokens: int, heads: int, head_dim: int,
                 itemsize: int = 2) -> tuple:
    """(FLOPs, bytes) of one layer's paged decode attention over
    ``context_tokens`` cached positions in total over the batch's rows:
    every cached K and V row is read once and meets one query row."""
    flops = 2 * 2.0 * context_tokens * heads * head_dim
    nbytes = 2.0 * context_tokens * heads * head_dim * itemsize
    return flops, nbytes


def step_keys(call: dict, counters: tuple, layers: int) -> int:
    """Cached positions the decoding rows' queries of one decode step must
    see, summed over ``layers`` layers: what the program counted on the
    span (``counters``, summed over ALL the step's rows and those layers)
    less the one position it counts a layer for each idle or prefilling
    row (``slots - rows``).  No count on the span: 0, attention reads
    low."""
    if counters[0] not in call:
        return 0
    idle = call["slots"] - call["rows"]
    return max(sum(call[c] for c in counters) - layers * idle, 0)


def causal_keys(start: int, tokens: int, window: int = None) -> int:
    """Keys the ``tokens`` queries of a chunk at positions ``start ...``
    MUST see between them under the causal rule: the query at position p
    sees p + 1 keys, itself among them, and in a window layer at most
    ``window``."""
    if window is None:
        return tokens * start + tokens * (tokens + 1) // 2
    return sum(min(p + 1, window) for p in range(start, start + tokens))
