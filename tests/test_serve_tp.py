"""Tensor-parallel serving: sharded decode must be token-exact vs the
single-device path, from every checkpoint format the bridge restores.

The serving mesh carves its 'model' axis out of the 8 virtual CPU
devices (conftest); TP decode runs the whole prefill/decode pipeline
inside shard_map with params in the Megatron layout and every layer's
KV page pool sharded on its head dim (serve/decode.py).  Greedy decode
is deterministic, so exactness is asserted on TOKENS, end to end —
the strongest available pin that sharding changed the execution, not
the function.
"""

import dataclasses
import functools
import os
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dtf_tpu.models.transformer import TransformerLM, param_partition_specs
from dtf_tpu.serve import (Decoder, ServeEngine, load_for_serving,
                           place_for_serving, serving_mesh)

VOCAB, SEQ, PS = 64, 64, 8


def tiny_model(**kw):
    kw.setdefault("vocab_size", VOCAB)
    kw.setdefault("num_layers", 2)
    kw.setdefault("d_model", 32)
    kw.setdefault("num_heads", 4)   # divisible by TP 2 and 4
    kw.setdefault("d_ff", 64)
    kw.setdefault("max_seq_len", SEQ)
    return TransformerLM(**kw)


@pytest.fixture(scope="module")
def model_and_params():
    model = tiny_model()
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, SEQ), jnp.int32))["params"]
    return model, params


def _prompts(batch, rng_seed=3):
    rng = np.random.default_rng(rng_seed)
    # varied lengths incl. one crossing a page boundary and one > 3 pages
    lens = [3, PS, PS + 5, 3 * PS + 2, 5, 9, 2, 17][:batch]
    return [rng.integers(0, VOCAB, (n,)).astype(np.int32) for n in lens]


def _generate_all(model, params, prompts, *, mesh=None, n_new=6):
    eng = ServeEngine(model, params, max_batch=max(len(prompts), 1),
                      max_seq_len=SEQ, kv_page_size=PS, max_delay_s=0.0,
                      mesh=mesh)
    try:
        handles = [eng.submit(p, max_new_tokens=n_new) for p in prompts]
        return [h.result(timeout=300).tokens for h in handles]
    finally:
        eng.stop(drain=False)


def _assert_exact_at_batches(model, tp_params, ref_params, mesh,
                             n_new=6):
    """TP vs single-device token equality at request-batch 1/4/8
    through ONE engine pair (the engines serve all three bursts)."""
    engines = [
        ServeEngine(model, ref_params, max_batch=8, max_seq_len=SEQ,
                    kv_page_size=PS, max_delay_s=0.0),
        ServeEngine(model, tp_params, max_batch=8, max_seq_len=SEQ,
                    kv_page_size=PS, max_delay_s=0.0, mesh=mesh),
    ]
    try:
        for batch in (1, 4, 8):
            prompts = _prompts(batch, rng_seed=batch)
            ref, got = (
                [h.result(timeout=300).tokens for h in
                 [eng.submit(p, max_new_tokens=n_new) for p in prompts]]
                for eng in engines)
            assert got == ref, f"batch {batch} diverged"
    finally:
        for eng in engines:
            eng.stop(drain=False)


# ---------------------------------------------------------------------------
# TP decode ≡ single-device decode
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("batch", [1, 4, 8])
def test_tp2_token_exact_vs_single_device(model_and_params, eight_devices,
                                          batch):
    """TP=2 engine decode reproduces the TP=1 token stream exactly at
    batch 1/4/8 — prefill chunks, paged attention, sampling and all."""
    model, params = model_and_params
    prompts = _prompts(batch)
    ref = _generate_all(model, params, prompts)
    mesh = serving_mesh(2)
    tp_params = place_for_serving({"params": params}, mesh=mesh,
                                  model_parallelism=2)["params"]
    got = _generate_all(model, tp_params, prompts, mesh=mesh)
    assert got == ref


@pytest.mark.slow  # scale twin of the tier-1 tp2 token-exact parametrization
def test_tp4_token_exact_vs_single_device(model_and_params, eight_devices):
    """The axis generalizes: TP=4 (every head on its own shard pair)
    is exact too."""
    model, params = model_and_params
    prompts = _prompts(4)
    ref = _generate_all(model, params, prompts)
    mesh = serving_mesh(4)
    tp_params = place_for_serving({"params": params}, mesh=mesh,
                                  model_parallelism=4)["params"]
    got = _generate_all(model, tp_params, prompts, mesh=mesh)
    assert got == ref


def test_tp_params_are_actually_sharded(model_and_params, eight_devices):
    """place_for_serving at TP=2 puts qkv/fc1 on the model axis — the
    restore lands DIRECTLY sharded, not replicated-then-resliced."""
    model, params = model_and_params
    mesh = serving_mesh(2)
    tp_params = place_for_serving({"params": params}, mesh=mesh,
                                  model_parallelism=2)["params"]
    qkv = tp_params["block0"]["attn"]["qkv"]["kernel"]
    assert "model" in tuple(qkv.sharding.spec)  # head dim sharded
    # each device holds half the heads' slice, not the full tensor
    shard_shape = qkv.addressable_shards[0].data.shape
    assert shard_shape[2] == qkv.shape[2] // 2
    fc2 = tp_params["block0"]["fc2"]["kernel"]
    assert fc2.addressable_shards[0].data.shape[0] == fc2.shape[0] // 2
    # replicated leaves stay whole everywhere
    emb = tp_params["embed"]["embedding"]
    assert emb.addressable_shards[0].data.shape == emb.shape


def test_partition_specs_cover_every_leaf(model_and_params):
    """Every param leaf gets a spec (a missing rule would silently
    replicate a tensor the layout says is sharded)."""
    model, params = model_and_params
    specs = param_partition_specs(params, "model")
    assert (len(jax.tree_util.tree_leaves(params))
            == len(jax.tree_util.tree_leaves(
                specs, is_leaf=lambda x: isinstance(
                    x, jax.sharding.PartitionSpec))))


def test_tp_rejects_indivisible_heads(eight_devices):
    model = tiny_model(num_heads=2, d_model=16, d_ff=32)
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, SEQ), jnp.int32))["params"]
    with pytest.raises(ValueError, match="divisible"):
        Decoder(model, params, num_slots=2, max_seq_len=SEQ,
                kv_page_size=PS, mesh=serving_mesh(4))


# ---------------------------------------------------------------------------
# bridge: checkpoint formats restore DIRECTLY into the sharded layout
# ---------------------------------------------------------------------------

def test_tp_restore_train_checkpoint_token_exact(tmp_path,
                                                 model_and_params,
                                                 eight_devices):
    """A train-format checkpoint (full TrainState) restores straight
    into the TP=2 layout and serves the exact single-device tokens."""
    optax = pytest.importorskip("optax")
    from dtf_tpu.train.checkpoint import Checkpointer
    from dtf_tpu.train.loop import TrainState

    model, params = model_and_params
    tx = optax.sgd(0.1)
    state = TrainState(step=jnp.asarray(3, jnp.int32), params=params,
                       batch_stats={}, opt_state=tx.init(params))
    ck = Checkpointer(str(tmp_path))
    ck.save(state, step=3)
    ck.wait()
    ck.close()

    mesh = serving_mesh(2)
    variables = load_for_serving(model_dir=str(tmp_path), mesh=mesh,
                                 model_parallelism=2)
    qkv = variables["params"]["block0"]["attn"]["qkv"]["kernel"]
    assert qkv.addressable_shards[0].data.shape[2] == qkv.shape[2] // 2
    _assert_exact_at_batches(model, variables["params"], params, mesh)


@pytest.mark.slow  # restore coverage stays tier-1 via the train-checkpoint twin
def test_tp_restore_export_format_token_exact(tmp_path, model_and_params,
                                              eight_devices):
    """The --export_dir inference artifact restores sharded too."""
    import types

    from dtf_tpu.train.checkpoint import export_model

    model, params = model_and_params
    export_model(str(tmp_path), types.SimpleNamespace(
        params=params, batch_stats={}))
    mesh = serving_mesh(2)
    variables = load_for_serving(export_dir=str(tmp_path), mesh=mesh,
                                 model_parallelism=2)
    _assert_exact_at_batches(model, variables["params"], params, mesh)


@pytest.mark.slow
def test_tp_restore_zero_run_checkpoint_token_exact(tmp_path,
                                                    eight_devices):
    """e2e: a real ZeRO (--optimizer_sharding) + TP training run's
    checkpoint — optimizer state saved ('data','model')-sliced —
    restores into the TP=2 serving layout and decodes token-exact vs
    the TP=1 restore of the SAME checkpoint."""
    import dtf_tpu.data.base as db
    from dtf_tpu.cli.runner import run
    from dtf_tpu.config import Config
    from dtf_tpu.models import registry

    lm_tiny = dataclasses.replace(db.LM, num_classes=VOCAB, seq_len=16,
                                  num_train=32, num_eval=16)
    factory = functools.partial(TransformerLM, num_layers=2, d_model=32,
                                num_heads=4, d_ff=64, max_seq_len=SEQ)
    with mock.patch.dict(db._SPECS, {"lm": lm_tiny}), \
         mock.patch.dict(registry._REGISTRY,
                         {"transformer": (factory, VOCAB, 0.0)}):
        run(Config(model="transformer", dataset="lm", batch_size=8,
                   train_steps=2, use_synthetic_data=True, skip_eval=True,
                   model_dir=str(tmp_path), log_steps=1,
                   optimizer="adamw", model_parallelism=2, num_devices=4,
                   optimizer_sharding=True))
    assert os.path.isdir(tmp_path / "checkpoints")
    model = tiny_model()
    mesh = serving_mesh(2)
    tp_vars = load_for_serving(model_dir=str(tmp_path), mesh=mesh,
                               model_parallelism=2)
    ref_vars = load_for_serving(model_dir=str(tmp_path))
    _assert_exact_at_batches(model, tp_vars["params"],
                             ref_vars["params"], mesh)
