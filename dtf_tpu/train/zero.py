"""ZeRO weight-update sharding — the shared per-leaf layout + collective
helpers behind stages 1-3 (PAPERS.md: Xu et al. 2020, arXiv 2004.13336).

One layout contract, used by the train step (train/loop.py), the
stage-3 parameter store, and the canonical-checkpoint conversions:

  - a leaf already sharded over 'data' (MoE experts riding the batch
    axis) keeps its full LOCAL shape — each data shard holds distinct
    experts, there is nothing left to slice;
  - every other leaf's ZeRO slice is a block of COLUMNS of a 2-D view
    of the LOCAL (TP/PP) shard (``slice_view``): the leaf's own last
    dimension where it splits into whole (8, 128) tiles a data shard,
    else the flattened leaf zero-padded at the tail and viewed
    ``[-1, nd·128]`` — PartitionSpec (None, 'data'), composed with
    'model' when the param itself shards there (each (data, model)
    coordinate owns one column block of its model shard).  Columns,
    not a contiguous 1/nd of the flat leaf, because the TPU compiler
    keeps a ``psum_scatter`` along a minor dimension of whole tiles as
    ONE reduce-scatter, and decomposes one along the major-most
    dimension (a flat vector has no other) into an all-reduce of the
    whole leaf and a slice (tests/test_tpu_lowering.py pins both).

Everything here is a pure function of (PartitionSpec, leaf) and runs
either inside ``shard_map`` (the collective forms) or as host-side
shape math.  The padding elements are zeros at init and STAY zero under
every supported optimizer (zero grads in, zero updates out — see
optimizer.ZEROS_INIT_OPTIMIZERS), which is what makes dropping and
re-creating them across a checkpoint round-trip exact.

``comm_off=True`` variants replace each cross-'data' collective with a
local op of the same output shape (values are garbage).  They exist
for ONE purpose: the ``--zero_probe`` timing twin — a compiled step
whose wall time is the step minus its data-axis collectives, so the
EXPOSED (non-overlapped) communication time is a measured number
rather than a model claim.  Never use a comm_off result as state.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from dtf_tpu.models.partition import spec_axes
from dtf_tpu.runtime.mesh import DATA_AXIS, MODEL_AXIS, SEQ_AXIS


class Replicated:
    """Canonical-spec sentinel for leaves that are genuinely replicated
    in BOTH layouts (the optimizer step count): distinguishes them from
    replicated *params*, whose ZeRO slice is a (None, 'data') column
    block."""


REP = Replicated()


def is_spec(x) -> bool:
    return isinstance(x, (P, Replicated))


def zero_leaf_spec(spec):
    """ZeRO-slice PartitionSpec for one param-shaped leaf (the layout
    the optimizer state — and stage-3 params — live in)."""
    if isinstance(spec, Replicated):
        return P()
    axes = spec_axes(spec)
    if DATA_AXIS in axes:
        return spec
    if MODEL_AXIS in axes:
        return P(None, (DATA_AXIS, MODEL_AXIS))
    return P(None, DATA_AXIS)


# one f32 vreg tile: a data shard's column block is whole tiles of it
SUBLANES, LANES = 8, 128


def whole_rows(rows: int) -> int:
    """``rows`` rounded up to q·2^k with q <= 128 and 2^k >= 8 (under
    1.6 % more): row counts in eights, with no large prime factor — the
    TPU compiler gives up on a reduce-scatter whose row count has one
    (8·509 rows: an all-reduce and a slice again; 8·251: kept)."""
    unit = SUBLANES
    while unit * 128 < rows:
        unit *= 2
    return -(-rows // unit) * unit


def slice_view(shape, nd: int) -> tuple:
    """(rows, cols) of the 2-D view a local leaf of ``shape`` is sliced
    in; a data shard owns ``cols // nd`` columns of every row.  The
    leaf's own last dimension where ``nd·128`` divides it and the rows
    above it are ``whole_rows`` (no padding, and the gather rebuilds
    the leaf without a relayout); else the flat leaf padded to
    ``whole_rows`` of ``nd·128``."""
    size = math.prod(shape)
    tile = nd * LANES
    if len(shape) >= 2 and shape[-1] % tile == 0:
        rows = size // shape[-1]
        if rows == whole_rows(rows):
            return rows, shape[-1]
    return whole_rows(-(-size // tile)), tile


def slice_shape(shape, nd: int) -> tuple:
    """Shape of one data shard's ZeRO slice of a local leaf."""
    rows, cols = slice_view(shape, nd)
    return rows, cols // nd


def as_view(p, nd: int):
    """A local leaf in its ``slice_view``; the zero padding, where the
    view needs any, lives at the flat tail and is trimmed off after
    gather."""
    rows, cols = slice_view(p.shape, nd)
    pad = rows * cols - p.size
    if pad:
        p = jnp.concatenate([p.reshape(-1), jnp.zeros((pad,), p.dtype)])
    return p.reshape(rows, cols)


def own_columns(view, nd: int, idx):
    """Data shard ``idx``'s column block of a leaf's view — the
    elements ``psum_scatter`` along dim 1 delivers there."""
    k = view.shape[1] // nd
    return lax.dynamic_slice_in_dim(view, idx * k, k, axis=1)


def local_shape(spec, shape, mesh_shape) -> tuple:
    """The shard_map-local shape of a leaf sharded by ``spec`` on a
    mesh of ``mesh_shape`` (dims divided by their axis sizes)."""
    if isinstance(spec, Replicated) or spec is None:
        return tuple(shape)
    out = list(shape)
    for d, part in enumerate(spec):
        if part is None:
            continue
        for a in (part if isinstance(part, (tuple, list)) else (part,)):
            out[d] //= mesh_shape[a]
    return tuple(out)


# ---------------------------------------------------------------------------
# shard_map-local leaf ops (spec = the leaf's MODEL partition spec)
# ---------------------------------------------------------------------------

def slice_leaf(spec, p, nd: int, idx):
    """This data shard's ZeRO slice of a local param leaf."""
    if isinstance(spec, Replicated):
        return p
    if DATA_AXIS in spec_axes(spec):
        return p
    return own_columns(as_view(p, nd), nd, idx)


def gather_leaf(spec, s, shape, dtype, nd: int, comm_off: bool = False):
    """Rebuild the full LOCAL leaf (``shape``/``dtype``) from its ZeRO
    slice — the stage-3 per-leaf parameter all-gather (and the
    canonical-checkpoint re-gather)."""
    if isinstance(spec, Replicated):
        return s
    if DATA_AXIS in spec_axes(spec):
        return s.astype(dtype)
    if comm_off:
        full = jnp.tile(s, (1, nd))   # shape-right stand-in, no wire
    else:
        full = lax.all_gather(s, DATA_AXIS, axis=1, tiled=True)
    size = math.prod(shape)
    if full.size != size:
        full = full.reshape(-1)[:size]
    return full.reshape(shape).astype(dtype)


def scatter_leaf(spec, g, nd: int, reduce_axes, mesh_shape,
                 comm_off: bool = False, idx=None, wire=jnp.float32):
    """Reduce-scatter one local grad leaf into this shard's f32 slice
    (mean over the batch-splitting axes).  Leaves sharded over 'data'
    (experts) keep their local shape: reverse-mode all_to_all already
    summed their true grads, so they divide to the global-mean
    convention instead of psum-ing.

    ``wire`` is the reduce-scatter WIRE dtype (``--zero_wire``): bf16
    halves the stage-2/3 scatter volume — the collective then also
    SUMS in bf16, which is the documented trade (the same one
    ``--ps_wire bf16`` ships on the async-PS path).  The returned
    slice is always f32, so the cross-microbatch accumulation carry
    (``slice_zeros``) and the optimizer update math keep full
    precision whatever crosses the wire.  Expert leaves are exempt:
    their true grads were already summed exactly by the all_to_all
    transpose — there is no wire volume left to trade."""
    sharded = spec_axes(spec) if not isinstance(spec, Replicated) else set()
    if DATA_AXIS in sharded:
        axes = tuple(a for a in reduce_axes if a not in sharded)
        if axes and not comm_off:
            g = lax.pmean(g, axes)
        denom = 1
        for a in reduce_axes:
            if a in sharded:
                denom *= mesh_shape[a]
        return (g / denom).astype(jnp.float32)
    view = as_view(g.astype(wire), nd)
    if comm_off:
        return own_columns(view, nd, idx).astype(jnp.float32) / nd
    s = lax.psum_scatter(view, DATA_AXIS, scatter_dimension=1,
                         tiled=True).astype(jnp.float32) / nd
    return lax.pmean(s, SEQ_AXIS)


def slice_zeros(spec, p, nd: int):
    """f32 zeros shaped like ``scatter_leaf``'s output for a local leaf
    ``p`` — the stage-2 sharded grad-accumulation carry."""
    if not isinstance(spec, Replicated) and DATA_AXIS in spec_axes(spec):
        return jnp.zeros(p.shape, jnp.float32)
    return jnp.zeros(slice_shape(p.shape, nd), jnp.float32)


def tree_map_specs(fn, specs, *trees):
    """tree_map with PartitionSpec/Replicated leaves treated as leaves
    of the spec tree."""
    return jax.tree_util.tree_map(fn, specs, *trees, is_leaf=is_spec)


def concrete_specs(specs):
    """Replace Replicated sentinels with P() — the form shard_map's
    in/out_specs and NamedSharding accept."""
    return tree_map_specs(
        lambda s: P() if isinstance(s, Replicated) else s, specs)
