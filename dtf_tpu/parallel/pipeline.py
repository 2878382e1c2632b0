"""GPipe-style pipeline parallelism over a mesh axis.

No reference counterpart (SURVEY.md §2.2 lists PP as an explicit
absence); built TPU-first: the schedule is a single ``lax.scan`` whose
body computes one stage tick and rotates activations to the clockwise
neighbor with ``lax.ppermute`` — an ICI neighbor exchange XLA overlaps
with the next tick's compute.  Running inside ``shard_map`` keeps the
whole pipeline one SPMD program: reverse-mode AD of the scan+ppermute
program *is* the backward pipeline schedule, so no hand-written
backward pass exists.

Semantics: classic GPipe.  ``num_microbatches`` activations flow
through ``pp`` stages in ``num_microbatches + pp - 1`` ticks; the
pipeline bubble is the usual (pp-1)/(M+pp-1) fraction, amortized by
choosing M ≥ pp.  Bubble ticks still execute the stage computation on
placeholder data (XLA needs static control flow — SURVEY's "no
data-dependent Python control flow under jit" rule); their results are
masked out of the output buffer and receive zero cotangents.

The runner auto-scales M to 4·pp when --num_microbatches is unset
(halving to divide the per-shard batch).  At pp=4, M=4 → M=16 takes the
bubble+placeholder-compute fraction from (7-4)/7 = 43% of ticks to
(19-16)/19 = 16% (a count of ticks; step times are not measured on this
installation).  `pipeline_spmd_interleaved` (below) instead halves the
bubble TIME at equal M.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax


def pipeline_spmd(stage_fn, x_microbatches, axis_name: str):
    """Run ``stage_fn`` as one pipeline stage per shard of ``axis_name``.

    Must be called inside shard_map with ``axis_name`` bound.

    stage_fn: activation -> activation, shape-preserving (this shard's
      stack of layers).
    x_microbatches: [M, microbatch, ...] — the microbatched input,
      replicated over ``axis_name`` (only stage 0 reads it).

    Returns [M, microbatch, ...] outputs — valid on the LAST stage,
    zeros elsewhere; mask-psum over ``axis_name`` to broadcast.
    """
    pp = lax.psum(1, axis_name)          # static axis size
    idx = lax.axis_index(axis_name)
    m = x_microbatches.shape[0]
    perm = [(j, (j + 1) % pp) for j in range(pp)]

    def tick(carry, t):
        recv, outputs = carry
        # stage 0 injects microbatch t (clamped reads during drain ticks
        # are discarded downstream); later stages consume the neighbor's
        # activation from the previous tick
        mb = lax.dynamic_index_in_dim(
            x_microbatches, jnp.clip(t, 0, m - 1), keepdims=False)
        out = stage_fn(jnp.where(idx == 0, mb, recv))
        # the last stage finishes microbatch t-(pp-1) at tick t
        w = jnp.clip(t - (pp - 1), 0, m - 1)
        valid = jnp.logical_and(idx == pp - 1, t >= pp - 1)
        cur = lax.dynamic_index_in_dim(outputs, w, keepdims=False)
        outputs = lax.dynamic_update_index_in_dim(
            outputs, jnp.where(valid, out, cur), w, axis=0)
        # NETWORK BOUNDARY: activation handoff to the next stage
        recv = lax.ppermute(out, axis_name, perm)
        return (recv, outputs), None

    carry0 = (jnp.zeros_like(x_microbatches[0]),
              jnp.zeros_like(x_microbatches))
    (_, outputs), _ = lax.scan(tick, carry0, jnp.arange(m + pp - 1))
    return outputs


def pipeline_spmd_interleaved(stage_fn, x_microbatches, axis_name: str):
    """Two-virtual-stages-per-device (interleaved) GPipe schedule.

    Device j runs virtual stages j and j+pp: a microbatch circles the
    ring twice, using the device's first layer chunk on lap 0 and its
    second on lap 1.  Each tick runs HALF a stage's layers, and the
    schedule takes 2·pp·ceil(M/pp) + pp - 1 ticks (= 2M + pp - 1 when
    pp | M) — so the fill/drain bubble costs (pp-1) half-ticks instead
    of GPipe's (pp-1) full ticks: bubble time halves at equal M
    (Megatron-LM interleaving, arXiv:2104.04473 §2.2, expressed in the
    same scan+ppermute SPMD formulation as `pipeline_spmd`).

    The static injection pattern alternates pp-tick blocks: device 0
    injects microbatches m = b·pp + r at tick i = 2·pp·b + r, and the
    lap-1 activation of that microbatch returns to device 0 exactly pp
    ticks later, in the non-injection block.  Chunk selection at
    (device j, tick t) is the parity of (t - j) // pp — fully static,
    no data-dependent control flow.

    stage_fn: (activation, chunk_index) -> activation, chunk_index in
      {0, 1} selecting the device-local layer chunk.
    Returns [M, microbatch, ...] outputs, valid on the LAST device
    (which hosts the final virtual stage 2pp-1); zeros elsewhere.
    """
    pp = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    m = x_microbatches.shape[0]
    perm = [(j, (j + 1) % pp) for j in range(pp)]
    nblocks = -(-m // pp)
    ticks = 2 * pp * nblocks + pp - 1

    def tick(carry, t):
        recv, outputs = carry
        tj = t - idx                      # ticks since this activation
        lap = jnp.where(tj >= 0, (tj // pp) % 2, 0)
        inj = tj - lap * pp               # its injection tick at dev 0
        mb_idx = inj - (inj // (2 * pp)) * pp
        mb = lax.dynamic_index_in_dim(
            x_microbatches, jnp.clip(mb_idx, 0, m - 1), keepdims=False)
        inject = jnp.logical_and(idx == 0, lap == 0)
        out = stage_fn(jnp.where(inject, mb, recv), lap)
        w = jnp.clip(mb_idx, 0, m - 1)
        valid = ((idx == pp - 1) & (lap == 1) & (tj >= 0)
                 & (mb_idx >= 0) & (mb_idx < m))
        cur = lax.dynamic_index_in_dim(outputs, w, keepdims=False)
        outputs = lax.dynamic_update_index_in_dim(
            outputs, jnp.where(valid, out, cur), w, axis=0)
        recv = lax.ppermute(out, axis_name, perm)
        return (recv, outputs), None

    carry0 = (jnp.zeros_like(x_microbatches[0]),
              jnp.zeros_like(x_microbatches))
    (_, outputs), _ = lax.scan(tick, carry0, jnp.arange(ticks))
    return outputs


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def last_stage_broadcast(x, axis_name: str):
    """Broadcast the last stage's value to every stage (mask + psum).

    custom_vjp: the cotangent returns to the last stage alone, at unit
    scale.  A raw psum's transpose is psum under shard_map AD, which
    would hand the pipeline ``pp×`` the true cotangent (one copy per
    stage's identical loss replica)."""
    return _mask_psum(x, axis_name)


def _mask_psum(x, axis_name):
    pp = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    return lax.psum(jnp.where(idx == pp - 1, x, jnp.zeros_like(x)),
                    axis_name)


def _lsb_fwd(x, axis_name):
    return _mask_psum(x, axis_name), None


def _lsb_bwd(axis_name, _, g):
    pp = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    return (jnp.where(idx == pp - 1, g, jnp.zeros_like(g)),)


last_stage_broadcast.defvjp(_lsb_fwd, _lsb_bwd)
