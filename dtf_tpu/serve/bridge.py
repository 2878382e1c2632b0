"""Checkpoint → serving bridge.

Train-side state comes in two on-disk shapes (train/checkpoint.py):

  <model_dir>/checkpoints/<step>/  — the full TrainState (params,
      batch_stats, optimizer state, step) written by the per-epoch
      CheckpointCallback via orbax CheckpointManager
  <export_dir>/model/              — inference variables only
      (params + batch_stats), the --export_dir SavedModel equivalent

Serving needs neither optimizer state nor the step counter.  Params
come out of orbax as host-global arrays regardless of how the run was
sharded — a ZeRO run (--optimizer_sharding) slices only its *optimizer*
state across 'data', and a TP/EP/PP run's params are saved as global
arrays with per-leaf shardings — so placement is one decision per
serving deployment:

  model_parallelism == 1 — device_put the restored tree with the
      replicated sharding of a fresh 1-chip serving mesh (the original
      restore-then-rebroadcast contract).
  model_parallelism N — build an N-chip serving mesh ('model' axis =
      N) and device_put each leaf DIRECTLY into the Megatron layout
      (``param_partition_specs``: heads/ff column-parallel, out/fc2
      row-parallel, everything else replicated).  The host-global
      restore goes straight to its shards — no replicated on-device
      intermediate, so a model that trains sharded loads for serving
      without ever needing to fit on one chip.
"""

from __future__ import annotations

import logging
import os
from typing import Optional

import jax

log = logging.getLogger("dtf_tpu")


def serving_mesh(model_parallelism: int = 1, devices=None):
    """A serving mesh: ``model_parallelism`` devices on the 'model'
    axis (data = seq = 1 — serving data parallelism is replica
    processes, not a mesh axis)."""
    from dtf_tpu.runtime.mesh import make_mesh

    mp = max(int(model_parallelism), 1)
    devices = list(devices if devices is not None else jax.devices())
    if len(devices) < mp:
        raise ValueError(
            f"serving model_parallelism {mp} needs {mp} devices, "
            f"{len(devices)} attached")
    return make_mesh(devices[:mp], data=1, seq=1, model=mp)


def load_inference_variables(model_dir: str = "", export_dir: str = "",
                             step: Optional[int] = None) -> dict:
    """Load {"params": ..., "batch_stats": ...} from a train checkpoint
    (``model_dir``) or an exported model (``export_dir``).

    ``export_dir`` wins when both are given (it is the purpose-built
    inference artifact).  ``step`` selects a specific train checkpoint;
    None = latest.  Raises FileNotFoundError when neither location has
    a restorable checkpoint — serving random init would silently answer
    garbage, which is strictly worse than failing."""
    if export_dir and os.path.isdir(os.path.join(
            os.path.abspath(export_dir), "model")):
        from dtf_tpu.train.checkpoint import load_exported_model
        payload = load_exported_model(export_dir)
        log.info("serve bridge: loaded exported model from %s", export_dir)
        return {"params": payload["params"],
                "batch_stats": payload.get("batch_stats", {})}
    if model_dir:
        from dtf_tpu.train.checkpoint import load_train_checkpoint
        payload = load_train_checkpoint(model_dir, step=step)
        if payload is not None:
            return payload
    raise FileNotFoundError(
        f"no checkpoint to serve: export_dir={export_dir!r} has no "
        f"model/, model_dir={model_dir!r} has no checkpoints/")


def tp_param_shardings(params, mesh):
    """(PartitionSpec tree, NamedSharding tree) of the Megatron serving
    layout for a full param pytree — THE single definition both the
    bridge's placement and the Decoder's shard_map in_specs consume, so
    a layout change cannot silently diverge between them."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dtf_tpu.models.transformer import param_partition_specs
    from dtf_tpu.runtime.mesh import MODEL_AXIS

    specs = param_partition_specs(params, MODEL_AXIS)
    shardings = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, P))
    return specs, shardings


def place_for_serving(variables, devices=None, mesh=None,
                      model_parallelism: int = 1):
    """Place the (host-global) inference variables on the serving mesh.

    Replicated at ``model_parallelism`` 1 (the original contract);
    otherwise each params leaf goes DIRECTLY to its tensor-parallel
    shard per ``param_partition_specs`` — train/export/ZeRO
    checkpoints restore into the sharded layout with no replicated
    intermediate.  ``mesh`` overrides the mesh construction (the
    engine and the bridge must agree on one)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dtf_tpu.runtime.mesh import MODEL_AXIS, make_mesh

    if mesh is None:
        if model_parallelism > 1:
            mesh = serving_mesh(model_parallelism, devices)
        else:
            devices = list(devices if devices is not None
                           else jax.devices()[:1])
            mesh = make_mesh(devices, data=1, seq=1, model=1)
    mp = int(mesh.shape[MODEL_AXIS])
    if mp <= 1:
        return jax.device_put(variables, NamedSharding(mesh, P()))
    replicated = NamedSharding(mesh, P())
    shardings = {k: (tp_param_shardings(v, mesh)[1] if k == "params"
                     else jax.tree_util.tree_map(lambda _: replicated, v))
                 for k, v in variables.items()}
    return jax.device_put(variables, shardings)


def load_for_serving(model_dir: str = "", export_dir: str = "",
                     step: Optional[int] = None, devices=None, mesh=None,
                     model_parallelism: int = 1) -> dict:
    """One-call bridge: restore + place (replicated or TP-sharded)."""
    return place_for_serving(
        load_inference_variables(model_dir, export_dir, step=step),
        devices=devices, mesh=mesh, model_parallelism=model_parallelism)


def serving_memory_plan(model, *, num_slots: int, max_seq_len: int,
                        kv_page_size: int,
                        kv_pool_pages: int = 0,
                        model_parallelism: int = 1, params=None) -> dict:
    """Byte accounting for a serving deployment: params + KV cache.

    The cache's geometry is what the model's own paged init STORES
    (``serve.decode.trace_paged_init``: one abstract trace, nothing
    materialised): ``per_token_kv_bytes`` sums, over every pool of every
    layer, one token's row — K and V of ``kv_heads x head_dim`` for whole
    or grouped-query heads, one latent row (``kv_heads`` 1, ``head_dim``
    its stored lanes) for latent attention, one row of ``[k | v]`` a KV
    head (``head_dim`` twice the head's) where K and V share a pool.  A
    ``PAGE_STATE`` leaf (``serve.decode.CACHE_LEAF_KINDS`` names every
    leaf's kind) is STATE A PAGE (a
    short-convolution layer's running entry): ``state_bytes_per_page``
    sums those, and ``state_bytes_paged`` is what they hold beside
    ``kv_bytes_paged``.  ``param_bytes`` is what the
    parameter tree holds in the dtype it is held in (``params``: the
    tree or its shapes; None = the shapes of the model's own init).

    The KV page pool holds ``(kv_pool_pages − 1) × kv_page_size``
    tokens TOTAL — sized to the expected tokens in flight, not the
    worst case.  ``kv_pool_pages`` of 0 = one full ``max_seq_len``
    reservation per slot (plus the scratch page): ``pages_per_slot``,
    which under a model that keeps one summary a chunk of its closed
    windows (``summary_window``) is its own count and does not grow as
    ``max_seq_len / kv_page_size``.  Returns dict with
    ``kv_bytes_paged``, ``kv_tokens_capacity`` and the layer geometry —
    serve_main logs it so pool sizing is a visible decision, not a
    guess."""
    import numpy as np

    from dtf_tpu.ops import window_summary
    from dtf_tpu.serve.decode import (KV_POOL, LATENT_POOL, cache_leaves,
                                      kv_bytes_per_token,
                                      state_bytes_per_page, trace_paged_init)

    shapes = trace_paged_init(model, kv_page_size, 2)[0]
    kinds, pools = zip(*cache_leaves(shapes, KV_POOL, LATENT_POOL))
    per_token = kv_bytes_per_token(shapes, kv_page_size)
    per_page_state = state_bytes_per_page(shapes)
    # [P, page, H, Dh] a K or V pool; [P, page, W] a pool of latent rows:
    # the geometry reported is the widest pool's (the first of them; an
    # indexer's keys lie beside wider latent rows)
    kind, widest = max(zip(kinds, pools),
                       key=lambda kp: int(np.prod(kp[1].shape[1:])))
    kv_heads = widest.shape[2] if kind == KV_POOL else 1
    head_dim = widest.shape[-1]
    if params is None:
        params = jax.eval_shape(
            model.init, jax.random.key(0),
            jax.ShapeDtypeStruct((1, kv_page_size), "int32")
        )["params"]
    param_bytes = sum(int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
                      for leaf in jax.tree_util.tree_leaves(params))
    # what a full-length row holds: every page of its length, or the
    # model's own count where closed windows shrink to their summaries
    pages_per_slot = window_summary.row_pages(model, max_seq_len,
                                              kv_page_size)
    pool_pages = int(kv_pool_pages) or 1 + num_slots * pages_per_slot
    paged_tokens = (pool_pages - 1) * kv_page_size
    mp = max(int(model_parallelism), 1)
    plan = {
        "kv_heads": kv_heads,
        "head_dim": head_dim,
        "param_bytes": param_bytes,
        "param_bytes_per_device": param_bytes // mp,
        "per_token_kv_bytes": per_token,
        "kv_bytes_paged": paged_tokens * per_token,
        "state_bytes_per_page": per_page_state,
        "state_bytes_paged": (pool_pages - 1) * per_page_state,
        "kv_tokens_capacity": paged_tokens,
        "pages_per_slot": pages_per_slot,
        "pool_pages": pool_pages,
        # TP shards the pool's HEAD dim: each of the mp chips holds
        # 1/mp of every page (and of the params) — the lever that
        # makes a too-big-for-one-chip model servable at all
        "model_parallelism": mp,
        "kv_bytes_per_device": (paged_tokens * per_token) // mp,
    }
    log.info(
        "serving memory plan: %d slots x %d tokens; weights %.1f MB; "
        "%d pools of %d x %d a token, %d B/token; KV page pool %.1f MB "
        "(%d pages x %d tokens); state %d B/page%s", num_slots, max_seq_len,
        param_bytes / 2**20, len(pools), kv_heads, head_dim, per_token,
        plan["kv_bytes_paged"] / 2**20, pool_pages, kv_page_size,
        per_page_state,
        (f", TP={mp}: {plan['kv_bytes_per_device'] / 2**20:.1f} "
         f"MB KV/device" if mp > 1 else ""))
    return plan
