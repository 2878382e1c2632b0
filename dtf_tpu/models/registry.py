"""Model registry + the L2-as-loss-term rule.

The reference applies L2 through Keras kernel_regularizers, which fold
into the loss (SURVEY §7 hard-part 5; resnet_model.py:37-43).  Here the
same behavior is a pure function over the param pytree: every 'kernel'
leaf is penalized, plus the final classifier's bias (the reference sets
bias_regularizer only on fc1000/fc10 — resnet_model.py:378-380,
resnet_cifar_model.py:250-251).  BatchNorm scale/bias are never
penalized, matching Keras.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

import functools

from dtf_tpu.models import (moe, pipeline_lm, resnet, resnet_cifar,
                            routed_decoder, transformer, trivial)

# reference weight-decay constants
L2_IMAGENET = 1e-4  # resnet_model.py:37
L2_CIFAR = 2e-4     # resnet_cifar_model.py:36

_REGISTRY = {
    "resnet50": (resnet.ResNet50, 1001, L2_IMAGENET),
    "resnet20": (resnet_cifar.resnet20, 10, L2_CIFAR),
    "resnet32": (resnet_cifar.resnet32, 10, L2_CIFAR),
    "resnet56": (resnet_cifar.resnet56, 10, L2_CIFAR),
    "resnet110": (resnet_cifar.resnet110, 10, L2_CIFAR),
    "resnet662": (resnet_cifar.resnet662, 10, L2_CIFAR),
    "trivial": (trivial.TrivialModel, 1001, 0.0),
    # LM family (no L2: the reference's weight-decay rule is ResNet-only)
    "transformer": (transformer.TransformerLM, 32_768, 0.0),
    "transformer_small": (
        functools.partial(transformer.TransformerLM, num_layers=4,
                          d_model=256, num_heads=4, d_ff=1024),
        32_768, 0.0),
    # GPT-2-small-sized flagship with the TPU-native head layout:
    # 6 heads × d_head 128 instead of GPT-2's 12 × 64 — identical
    # parameter shapes and count (768 = 12·64 = 6·128).  The 12×64
    # penalty is intrinsic MXU geometry, not a kernel gap: matmuls
    # bill output_tiles × ceil(d/128) full passes (a 64-deep matmul
    # measures 0.7-1.3× the wall time of the 128-deep one at half the
    # FLOPs), so head-packing constructions cancel exactly, and 12
    # heads compute 2× the softmax score elements.  Its size end to
    # end is not measured on this installation.
    "transformer_tpu": (
        functools.partial(transformer.TransformerLM, num_layers=12,
                          d_model=768, num_heads=6, d_ff=3072),
        32_768, 0.0),
    # routed-expert LM family (expert parallelism over 'data')
    "moe_transformer": (moe.MoETransformerLM, 32_768, 0.0),
    "moe_transformer_small": (
        functools.partial(moe.MoETransformerLM, num_layers=4, d_model=256,
                          num_heads=4, d_ff=1024, num_experts=4),
        32_768, 0.0),
    # served-only decoder: per-layer window/full x rope/nope pattern,
    # grouped-query heads, dropless routed experts, bf16 parameters
    "routed_decoder": (routed_decoder.RoutedDecoderLM, 32_768, 0.0),
    # the same module's other layer kinds at a small size: latent
    # attention (one cached row a token), a leading dense layer, a shared
    # expert beside sigmoid-routed experts
    "routed_decoder_latent": (
        functools.partial(
            routed_decoder.RoutedDecoderLM, num_layers=4, d_model=512,
            num_heads=8, q_lora_rank=192, kv_lora_rank=128,
            qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32,
            rope_interleave=True, num_dense_layers=1, dense_width=1024,
            num_experts=16, experts_per_token=4, expert_width=128,
            shared_expert_width=128, routing="sigmoid_bias",
            routed_scale=2.5, router_bias_stddev=0.05, activation="silu",
            router_input="post_attention"),
        32_768, 0.0),
    # and its mixer kinds at a small size: short-convolution layers whose
    # running state rides the page table beside grouped-query layers with
    # per-head norms and [k | v] pool rows, two dense layers, a tied head
    "routed_decoder_state": (
        functools.partial(
            routed_decoder.RoutedDecoderLM, num_layers=8, d_model=512,
            num_heads=8, num_kv_heads=2, head_dim=64,
            layer_mixer=("short_conv", "short_conv", "attention",
                         "short_conv"), qk_norm=True,
            tie_head=True, layer_window=(False,), layer_rope=(True,),
            num_dense_layers=2, dense_width=1024, num_experts=16,
            experts_per_token=4, expert_width=128, routing="sigmoid_bias",
            routing_sum_eps=1e-6, router_bias_stddev=0.05,
            activation="silu", router_input="post_attention"),
        32_768, 0.0),
    # and delta-rule linear-attention layers (a matrix of state a head on
    # the page table) beside one latent layer in six with a direct query
    # projection, a head norm and a head gate; group-limited routing over
    # 16 experts of which this device holds 8
    "routed_decoder_linear": (
        functools.partial(
            routed_decoder.RoutedDecoderLM, num_layers=8, d_model=512,
            num_heads=8, layer_mixer=("linear_delta",) * 5 + ("attention",),
            linear_heads=8, linear_head_dim=64, kv_lora_rank=128,
            qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32,
            q_head_norm=True, attention_head_gate=True,
            num_dense_layers=2, dense_width=1024, num_experts=16,
            experts_per_token=4, expert_width=128, shared_expert_width=128,
            routing="sigmoid_bias", routed_scale=2.5,
            router_bias_stddev=0.05, route_groups=4, route_groups_kept=2,
            experts_held=(0, 8), activation="silu",
            router_input="post_attention"),
        32_768, 0.0),
    # and an all-dense stack whose attention layers read a window of exact
    # keys beside one learned summary a chunk of every closed window, in the
    # one K/V page pool under a compact table; unit-offset norms
    "routed_decoder_summary": (
        functools.partial(
            routed_decoder.RoutedDecoderLM, num_layers=4, d_model=512,
            num_heads=4, num_kv_heads=4, head_dim=128,
            layer_window=(False,), layer_rope=(True,), rope_theta=1e5,
            summary_window=64, summary_chunk=4, norm_unit_offset=True,
            num_dense_layers=4, dense_width=1024, activation="silu"),
        32_768, 0.0),
    # and block-sparse attention that chooses blocks through pooled keys
    # beside lightning linear-attention layers (a constant decay a head, no
    # write gate), dense MLPs, muP scalars: blocks of 8 tokens, pooled keys
    # over 4 at stride 2, 4 chosen beside 1 + 4 forced, dense up to 128
    "routed_decoder_sparse": (
        functools.partial(
            routed_decoder.RoutedDecoderLM, num_layers=4, d_model=512,
            num_heads=8, num_kv_heads=2, head_dim=64,
            layer_mixer=("sparse_block", "lightning", "lightning",
                         "sparse_block"),
            sparse=(8, 4, 2, 4, 32, 1, 128, 3.0), lightning=(8, 64, 0, 4),
            mup=(12.0, 1.4, 4, 2.0), num_dense_layers=4, dense_width=1024,
            activation="silu"),
        32_768, 0.0),
    # and learned sparse attention over the latent cache: a lightning
    # indexer with parameters of its own (4 heads of 32, rotary on 16)
    # chooses the 64 rows a query attends in the full layers, and the
    # shared ones attend over the choice of the full layer below; a leading
    # dense layer, then a held quarter of 16 sigmoid-routed experts
    "routed_decoder_indexed": (
        functools.partial(
            routed_decoder.RoutedDecoderLM, num_layers=5, d_model=512,
            num_heads=8, q_lora_rank=192, kv_lora_rank=128,
            qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32,
            rope_interleave=True, indexer=(4, 32, 64, 16),
            layer_indexer=("full", "shared", "shared", "shared", "full"),
            num_dense_layers=1, dense_width=1024, num_experts=16,
            experts_per_token=4, expert_width=128, shared_expert_width=128,
            routing="sigmoid_bias", routed_scale=2.5,
            router_bias_stddev=0.05, activation="silu",
            router_input="post_attention", experts_held=(4, 4)),
        32_768, 0.0),
    # and the GATED forms: the delta rule with ONE decay a head and 4 key
    # heads under 8 value heads behind a silu(z) gate, three layers in four,
    # beside gated grouped-query attention (a partial rotary, zero-centred
    # norms of q and k, an output gate on the query projection); every
    # layer top-4 of 16 softmax-routed experts, of which this device holds
    # 4, beside a shared expert under a scalar gate
    "routed_decoder_gated": (
        functools.partial(
            routed_decoder.RoutedDecoderLM, num_layers=8, d_model=512,
            num_heads=8, num_kv_heads=2, head_dim=64, rotary_dim=16,
            layer_mixer=("linear_delta",) * 3 + ("attention",),
            layer_window=(False,), layer_rope=(True,), rope_theta=1e7,
            qk_norm=True, attention_output_gate=True, norm_unit_offset=True,
            linear_heads=8, linear_key_heads=4, linear_head_dim=64,
            linear_decay="head", linear_gate="silu", num_experts=16,
            experts_per_token=4, expert_width=128, shared_expert_width=128,
            shared_expert_gate=True, experts_held=(0, 4), activation="silu",
            router_input="post_attention"),
        32_768, 0.0),
    # pipeline-stacked LM family (pipeline stages over 'model')
    "pipeline_transformer": (pipeline_lm.PipelinedTransformerLM,
                             32_768, 0.0),
    "pipeline_transformer_small": (
        functools.partial(pipeline_lm.PipelinedTransformerLM, num_layers=4,
                          d_model=256, num_heads=4, d_ff=1024),
        32_768, 0.0),
}


def build_model(name: str, num_classes: int | None = None,
                dtype: Any = jnp.float32, bn_axis: str | None = None,
                seq_axis: str | None = None, model_axis: str | None = None,
                expert_axis: str | None = None, pipe_axis: str | None = None,
                **model_kw):
    """Returns (module, l2_weight).

    `bn_axis` names the mesh axis for cross-replica (sync) BatchNorm;
    None = per-replica statistics, the reference's implicit
    MirroredStrategy behavior (SURVEY §7.4).  `seq_axis` names the mesh
    axis the sequence dimension is sharded over (transformer family
    only) — it switches attention to the ring implementation.
    `model_axis` enables Megatron-style tensor parallelism (transformer
    family only): heads/ff sharded; pair with
    transformer.param_partition_specs.  `expert_axis` shards MoE
    experts (moe_transformer family; pair with
    moe.moe_param_partition_specs); `pipe_axis` makes the axis shards
    pipeline stages (pipeline_transformer family; pair with
    pipeline_lm.pipeline_param_partition_specs)."""
    if name not in _REGISTRY:
        raise ValueError(f"unknown model {name!r}; have {sorted(_REGISTRY)}")
    ctor, default_classes, l2 = _REGISTRY[name]
    if name.startswith("moe_transformer"):
        kw = dict(vocab_size=num_classes or default_classes, dtype=dtype,
                  seq_axis=seq_axis, expert_axis=expert_axis, **model_kw)
    elif name.startswith("pipeline_transformer"):
        kw = dict(vocab_size=num_classes or default_classes, dtype=dtype,
                  pipe_axis=pipe_axis, **model_kw)
    elif name.startswith("transformer"):
        kw = dict(vocab_size=num_classes or default_classes, dtype=dtype,
                  seq_axis=seq_axis, model_axis=model_axis, **model_kw)
    elif name.startswith("routed_decoder"):
        # the layer pattern may come from a JSON file: lists to tuples
        # (module fields are hashed)
        kw = dict(vocab_size=num_classes or default_classes, dtype=dtype,
                  model_axis=model_axis,
                  **{k: tuple(v) if isinstance(v, list) else v
                     for k, v in model_kw.items()})
    else:
        kw = dict(num_classes=num_classes or default_classes, dtype=dtype,
                  **model_kw)
        if name != "trivial":
            kw["bn_axis"] = bn_axis
    module = ctor(**kw)
    return module, l2


def l2_weight_penalty(params, l2_weight: float, param_specs=None
                      ) -> jax.Array:
    """Keras-parity L2 term: l2 * sum(w²) over conv/dense kernels and the
    classifier bias.  Note Keras `regularizers.l2(l)` is `l * sum(w²)`
    (no 0.5 factor).

    With ``param_specs`` (a PartitionSpec tree matching ``params``, for
    model-sharded runs inside shard_map), each sharded leaf's local
    sum-of-squares is summed over its sharding axes with `tp_psum` (sum
    forward, identity backward), so the penalty — and its gradient on
    each local shard — matches the unsharded model exactly.  Without it,
    a TP/EP/PP-sharded kernel would be silently under-counted.

    Penalized leaves sharded over a BATCH axis ('data'/'seq') are
    rejected: the trainer's gradient reduction divides such leaves'
    grads by the axis size (the all_to_all-transpose convention), which
    would scale the tp_psum L2 gradient down by the same factor.  No
    model family hits this (expert weights are named w1/w2, outside the
    penalize rule), so it is a guard, not a capability."""
    if not l2_weight:
        return jnp.zeros((), jnp.float32)
    spec_leaves = None
    if param_specs is not None:
        from jax.sharding import PartitionSpec
        spec_leaves = [
            s for _, s in jax.tree_util.tree_leaves_with_path(
                param_specs,
                is_leaf=lambda x: isinstance(x, PartitionSpec))]
    total = jnp.zeros((), jnp.float32)
    for i, (path, leaf) in enumerate(
            jax.tree_util.tree_leaves_with_path(params)):
        keys = [getattr(p, "key", getattr(p, "name", "")) for p in path]
        last = keys[-1] if keys else ""
        penalized = last == "kernel" or (last == "bias" and "fc" in keys)
        if not penalized:
            continue
        ss = jnp.sum(jnp.square(leaf.astype(jnp.float32)))
        if spec_leaves is not None:
            from dtf_tpu.models.partition import spec_axes
            axes = spec_axes(spec_leaves[i])
            batch_sharded = axes & {"data", "seq"}
            if batch_sharded:
                raise ValueError(
                    f"L2-penalized leaf {'/'.join(keys)} is sharded over "
                    f"batch axes {sorted(batch_sharded)}; the L2 gradient "
                    f"would be divided by the axis size in gradient "
                    f"reduction — unsupported")
            if axes:
                from dtf_tpu.parallel.collectives import tp_psum
                for ax in sorted(axes):
                    ss = tp_psum(ss, ax)
        total = total + ss
    return l2_weight * total
