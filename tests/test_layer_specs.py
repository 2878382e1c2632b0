"""The seam of the routed decoder: a layer is described ONCE.

``RoutedDecoderLM.layer_specs()`` is the one reader of the model's mixer and
MLP fields; the block, the counts' names and arithmetic (``COUNTS``) and the
form a call's shape chooses all go by its result.  Held here, on the CPU and
without compiling anything:

  - the counts' names of every family's toy and every registry sibling are
    the literals PR 58's tree gave (before the table existed);
  - the predicate and the path agree: where the model says ``n`` layers WALK
    (or attend EXPANDED), ``n`` layers of a traced chunk go through the walk
    (or the expanded form), and where it says none, none does;
  - a configuration that describes no layer is refused by ``layer_specs()``
    itself, before ``init`` or ``apply``, with the message it always had.
"""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from dtf_tpu.models import build_model
from dtf_tpu.models import routed_decoder as rd
from dtf_tpu.models import transformer
from test_chunk_head import FAMILIES, toy_decoder

_E = "assignments experts_touched expert_load_max "
_HEADS = _E + "kv_tokens_read_global kv_tokens_read_window"
_CONV = _HEADS + " conv_tokens state_rows_advanced"
_LINEAR = _HEADS + " linear_tokens state_rows_advanced"
_LATENT = (_E + "latent_tokens_read", _E + "latent_tokens_expanded")
_LATENT_LINEAR = tuple(n + " linear_tokens state_rows_advanced"
                       for n in _LATENT)
_SUMMARY = _E + "kv_exact_rows_read kv_summary_rows_read"
_SPARSE = (_E + "kv_blocks_visible kv_blocks_read pooled_keys_scored "
           "rows_dense_path linear_tokens state_rows_advanced "
           "kv_blocks_streamed")
_INDEXED = _E + ("index_keys_scored latent_rows_visible latent_rows_selected "
                 "rows_dense_path")
_INDEXED = (_INDEXED, _INDEXED + " latent_tokens_expanded")
# model -> (stats_names, which a decode step's call_stats_names(1) are too;
# call_stats_names(its prefill chunk — a sibling's: 2,048)), as recorded
# from PR 58's tree; a family's toy in decode mode, a sibling cloned into it
NAMES = {
    "gpt2": (None, None),                   # TransformerLM counts nothing
    "smallthinker": (_HEADS, _HEADS), "joyai": _LATENT,
    "lfm2": (_CONV, _CONV), "ling": _LATENT_LINEAR,
    "evabyte": (_SUMMARY, _SUMMARY), "minicpm_sala": (_SPARSE, _SPARSE),
    "glm_dsa": _INDEXED, "qwen3_next": (_LINEAR, _LINEAR),
    "routed_decoder": (_HEADS, _HEADS),
    "routed_decoder_latent": _LATENT, "routed_decoder_state": (_CONV, _CONV),
    "routed_decoder_linear": _LATENT_LINEAR,
    "routed_decoder_summary": (_SUMMARY, _SUMMARY),
    "routed_decoder_sparse": (_SPARSE, _SPARSE),
    "routed_decoder_indexed": _INDEXED,
    "routed_decoder_gated": (_LINEAR, _LINEAR),
}
SIBLINGS = [n for n in NAMES if n.startswith("routed_decoder")]


def _decode_model(name):
    """(the model in decode mode, its prefill chunk)."""
    if name in FAMILIES:
        dec, chunk = toy_decoder(name, "float32")
        return dec.model, chunk
    model, _ = build_model(name, num_classes=256)
    return model.clone(decode=True, kv_page_size=16, kv_pool_pages=9), 2048


def test_the_table_holds_every_family_and_every_sibling():
    from dtf_tpu.models.registry import _REGISTRY
    assert set(FAMILIES) | {n for n in _REGISTRY
                            if n.startswith("routed_decoder")} == set(NAMES)
    assert len(SIBLINGS) == 1 + 7


@pytest.mark.parametrize("name", list(NAMES))
def test_the_counts_names_are_the_recorded_ones(name):
    model, chunk = _decode_model(name)
    stats, at_chunk = NAMES[name]
    if stats is None:
        assert not hasattr(model, "stats_names")
        return
    assert model.stats_names == tuple(stats.split())
    assert model.call_stats_names(1) == tuple(stats.split())
    assert model.call_stats_names(chunk) == tuple(at_chunk.split())
    # outside decode mode no call's shape chooses a form
    assert model.clone(decode=False).call_stats_names(chunk) \
        == tuple(stats.split())


@pytest.mark.parametrize("name", SIBLINGS)
def test_a_layers_description_names_its_class_arguments(name):
    """A spec is a small hashable value whose mixer arguments are fields of
    the mixer's class, none of them the common ones the block adds; the
    block itself declares the spec and the common fields alone."""
    model, _ = _decode_model(name)
    specs = model.layer_specs()
    assert len(specs) == model.num_layers and hash(specs) == hash(
        model.clone().layer_specs())
    common = {k for k, _ in rd._common(model)}
    for spec in specs:
        fields = set(spec.mixer.cls.__dataclass_fields__)
        assert set(dict(spec.mixer.args)) <= fields - common
        assert (spec.mlp.dense_width is None) != (spec.mlp.routed is None)
        assert spec.mixer.module(rd._common(model), parent=None).decode
    assert [s.mixer.kind if s.mixer.kind in rd.MIXERS else "attention"
            for s in specs] == model.layer_mixers()
    declared = [f.name for f in dataclasses.fields(rd.RoutedBlock)
                if f.name not in ("parent", "name")]
    assert declared[0] == "spec" and len(declared) <= 12
    assert set(declared[1:]) >= common


def _traced_calls(model, s, monkeypatch, *names):
    """How often a CONTINUATION chunk of ``s`` tokens, traced abstractly,
    calls each of ``names`` (functions the attention modules look up in
    their own modules' namespaces)."""
    calls = dict.fromkeys(names, 0)
    for module in (transformer, rd):
        for name in names:
            if hasattr(module, name):
                def counted(*a, _f=getattr(module, name), _n=name, **kw):
                    calls[_n] += 1
                    return _f(*a, **kw)
                monkeypatch.setattr(module, name, counted)
    i32 = jnp.int32
    at = dict(cache_index=jnp.zeros((1,), i32),
              block_table=jnp.zeros((1, 2 * s // model.kv_page_size), i32))
    tokens = jnp.zeros((1, s), i32)
    variables = jax.eval_shape(
        lambda: model.init(jax.random.key(0), tokens, **at))
    for name in names:
        calls[name] = 0                     # init's own trace
    jax.eval_shape(lambda v: model.apply(
        {"params": v["params"], "cache": v["cache"]}, tokens,
        mutable=["cache", "stats"], **at), variables)
    return [calls[n] for n in names]


@pytest.mark.parametrize("head_dim,layer_window,walking", [
    (16, (False, True, False), 2),      # two pools: the global layers walk
    (64, (False, True, False), 0),      # [k | v] rows: one pool, no walk
    (16, (True,), 0),                   # under a window nothing walks
])
def test_the_layers_that_walk_are_the_layers_the_model_counts(
        monkeypatch, head_dim, layer_window, walking):
    """8 query heads over 1 KV head: 128 queries are the 1,024 rows a KV
    head of a tile (``ops.paged_attention.chunk_walks``)."""
    model = rd.RoutedDecoderLM(
        vocab_size=64, num_layers=3, d_model=32, num_heads=8, num_kv_heads=1,
        head_dim=head_dim, window=64, layer_window=layer_window,
        layer_rope=(True,), num_experts=4, experts_per_token=2,
        expert_width=16, use_pallas=False, decode=True, kv_page_size=16,
        kv_pool_pages=33)
    assert model.layers_walking(128) == walking
    assert model.layers_walking(64) == model.layers_walking(1) == 0
    assert ("kv_tokens_walked" in model.call_stats_names(128)) == bool(walking)
    assert _traced_calls(model, 128, monkeypatch,
                         "paged_chunk_attention") == [walking]
    assert _traced_calls(model, 64, monkeypatch,
                         "paged_chunk_attention") == [0]


@pytest.mark.parametrize("indexer", [None, (2, 16, 8, 8)])
def test_the_layers_that_expand_are_the_layers_the_model_counts(
        monkeypatch, indexer):
    """2 heads of nope / rope / v 16 / 16 / 16 over rank 32, rows of 128
    lanes: 16 queries repay the expansion, 8 do not
    (``ops.paged_attention.latent_expands``); an indexer's layers go by the
    same rule."""
    model = rd.RoutedDecoderLM(
        vocab_size=64, num_layers=2, d_model=32, num_heads=2, q_lora_rank=24,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=16,
        v_head_dim=16, indexer=indexer, layer_indexer=(
            ("full", "shared") if indexer else ()), num_experts=4,
        experts_per_token=2, expert_width=16, use_pallas=False, decode=True,
        kv_page_size=8, kv_pool_pages=9)
    assert model.latent_expanded(16) and not model.latent_expanded(8)
    assert model.layers_walking(16) == 0
    assert "latent_tokens_expanded" in model.call_stats_names(16)
    assert "latent_tokens_expanded" not in model.call_stats_names(8)
    for s, expanded in ((16, 2), (8, 0)):       # a layer
        assert _traced_calls(model, s, monkeypatch,
                             "latent_chunk_attention") == [expanded]


@pytest.mark.parametrize("kwargs,message", [
    (dict(layer_mixer=("short_conv", "attention"), kv_lora_rank=32),
     "short_conv layers go with whole heads, not with the latent cache"),
    (dict(summary_window=64, layer_window=(False, True)),
     "summary_window goes with whole heads in every layer: no latent cache, "
     "no state layer, no sliding window"),
    (dict(kv_lora_rank=32, q_lora_rank=24, indexer=(2, 16, 8, 8),
          layer_indexer=("shared", "full", "full", "full")),
     "an indexer chooses rows of the latent cache: every layer latent "
     "attention, layer_indexer ('shared', 'full', 'full', 'full') one of "
     "full | shared a layer, the first of them full"),
    (dict(routing="top1"), "routing 'top1': softmax_topk or sigmoid_bias"),
    (dict(layer_mixer=("attention", "mamba")),
     "layer_mixer ('attention', 'mamba'): each one of " + repr(rd.MIXERS)),
], ids=["short_conv_with_latent", "summary_with_window", "indexer_shared_first",
        "routing", "mixer"])
def test_a_configuration_that_describes_no_layer_is_refused_untraced(
        kwargs, message):
    """``layer_specs()`` of the unbound model raises — nothing is
    initialised or applied — and everything that reads the layers raises
    with it."""
    model = rd.RoutedDecoderLM(vocab_size=64, **kwargs)
    with pytest.raises(ValueError) as err:
        model.layer_specs()
    assert str(err.value) == message
    with pytest.raises(ValueError):
        model.stats_names
