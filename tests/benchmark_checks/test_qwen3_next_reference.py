"""Checks of the plain reference the family ``qwen3_next`` brings
(``benchmark/families/reference_qwen3_next.py``): that it stands on its own
(nothing of ``dtf_tpu``, float32 at the highest matmul precision), that its
comparison is ``lib/agreement``'s, that each control changes one thing,
that the four injected faults are refused by the toy's own limit, and that
THE SHARES ADD UP — the four shares' expert parts plus the gated shared
expert counted once are the uncut layer, and the program's layer with one
share held is that share's part.  ``test_qwen3_next.py`` holds the files;
the toy's readings are in its docstring.  CPU only."""

import os
import re
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import families  # noqa: E402
from benchmark.lib import agreement  # noqa: E402
from benchmark.lib.runtime import load_benchmark, load_cell  # noqa: E402

CELL = "qwen3next-serve-hybriddoc"


@pytest.fixture(scope="module")
def cell():
    return load_cell(load_benchmark(), CELL)


def _toy_model(cell, dtype="float32", **changes):
    import jax.numpy as jnp
    from dtf_tpu.models import build_model
    toy = cell.family.TOY["serve"]
    kw = dict(cell.config["build_model"]["kwargs"], **toy["model_kwargs"])
    kw.update(param_dtype=dtype, **changes)
    model, _ = build_model("routed_decoder", num_classes=toy["vocab_size"],
                           dtype=jnp.dtype(dtype), **kw)
    return model


@pytest.fixture(scope="module")
def toy_sample(cell):
    """The toy's weights (a float32 tree, its vectors moved off their
    initial values), two prompts — one three chunks and a token long — and
    what the reference would serve for them."""
    import jax
    import jax.numpy as jnp
    reference = families.load_reference(cell.config, ROOT)
    reference.FAULT_CHUNK = cell.family.TOY["serve"]["engine"][
        "prefill_chunk"]
    params = jax.jit(_toy_model(cell).init)(
        jax.random.key(5), jnp.zeros((1, 16), jnp.int32))["params"]
    rng = np.random.default_rng(5)
    params = jax.tree_util.tree_map(
        lambda a: a + 0.1 * rng.standard_normal(a.shape).astype(a.dtype)
        if a.ndim == 1 else a, params)
    vocab = cell.family.TOY["serve"]["vocab_size"]
    prompts = [rng.integers(0, vocab, n, dtype=np.int32) for n in (40, 97)]
    served = reference.greedy_tokens(params, prompts, 4)
    return reference, params, prompts, served


def test_the_reference_stands_on_its_own():
    """It imports nothing from ``dtf_tpu`` — nor do the references it takes
    its shared helpers from — and every product of ``hidden`` runs under
    ``default_matmul_precision("highest")`` in float32."""
    here = os.path.join(ROOT, "benchmark", "families")
    seen, todo = set(), ["reference_qwen3_next"]
    while todo:
        name = todo.pop()
        if name in seen:
            continue
        seen.add(name)
        with open(os.path.join(here, name + ".py")) as f:
            text = f.read()
        assert not re.search(r"^\s*(from|import)\s+dtf_tpu", text, re.M), name
        todo += re.findall(r"from benchmark\.families\.(reference_\w+)",
                           text)
    assert {"reference_qwen3_next", "reference_smallthinker"} <= seen
    with open(os.path.join(here, "reference_qwen3_next.py")) as f:
        text = f.read()
    assert 'jax.default_matmul_precision("highest")' in text
    assert "pallas" not in text.replace("no kernel", "")


def test_the_references_own_comparison_is_lib_agreements(toy_sample):
    """``served_tokens_agree`` gathers the hidden rows before the head, a
    prompt at a time; ``lib/agreement.tokens_agree`` gathers them after, in
    one padded batch.  Same dictionary, same numbers."""
    reference, params, prompts, served = toy_sample
    rows = reference.rows_that_chose(params, prompts, served)
    rng = np.random.default_rng(0)
    program = [r + 0.05 * rng.normal(size=r.shape).astype(np.float32)
               for r in rows]
    ours = reference.served_tokens_agree(params, prompts, served, 0.01,
                                         program, 0.02)
    theirs = agreement.tokens_agree(reference.forward, params, prompts,
                                    served, 0.01, program, 0.02)
    assert set(ours) == set(theirs) and ours["ok"] is theirs["ok"] is False
    for key in ours:
        if isinstance(ours[key], float):
            assert ours[key] == pytest.approx(theirs[key], rel=1e-4,
                                              abs=2e-5), key
        else:
            assert ours[key] == theirs[key], key
    exact = reference.served_tokens_agree(params, prompts, served, 0.01,
                                          rows, 0.02)
    assert exact["ok"] and exact["logit_rms"] == 0.0
    assert exact["greedy_identical"] == exact["tokens_compared"] == 8


@pytest.mark.parametrize("control", [
    "w8", "router_bf16", "state_bf16", "other_share", "zero_state_carry",
    "zero_filter_carry", "ungated", "rope_all"])
def test_a_control_changes_one_thing_and_a_fault_is_refused(
        cell, toy_sample, control):
    """Each control of the reference changes one thing and reads a
    ``logit_rms`` above 0: 8-bit weights; the router's input alone in
    bfloat16 (flipped top-k choices); the matrices of state alone rounded
    to bfloat16 after every token (what the pool holds); ANOTHER block of
    the experts taken for the held one.  Each INJECTED FAULT — a state or a
    filter that starts from zeros at every chunk, an attention output
    without its gate, a rotary over the whole head — is refused by the
    toy's own limit, which the rounding controls pass."""
    import jax.numpy as jnp
    reference, params, prompts, served = toy_sample

    def bf16(a):
        return a.astype(jnp.bfloat16).astype(jnp.float32)
    kw = {"w8": {"weights": reference.rounded_to(8)},
          "router_bf16": {"router_input": bf16},
          "state_bf16": {"state": bf16},
          "other_share": {"held": (8, 4)}}.get(control, {"fault": control})
    limit = cell.family.TOY["serve"]["agreement"]["logit_rms_limit"]
    # the prompt that crosses chunks (one program a control, not two)
    prompts, served = prompts[1:], served[1:]
    said = reference.served_tokens_agree(
        params, prompts, served, 0.01,
        reference.rows_that_chose(params, prompts, served, **kw), limit)
    if control in reference.FAULTS or control in ("w8", "other_share"):
        assert said["logit_rms"] > limit and not said["ok"], said
    else:
        assert 0.0 < said["logit_rms"] < limit, said


def test_an_unknown_fault_is_refused(toy_sample):
    reference, params, prompts, _ = toy_sample
    with pytest.raises(ValueError):
        reference.hidden(params, prompts[0][None], fault="no_such_fault")


def test_the_four_shares_make_the_uncut_layer(cell, toy_sample):
    """THE SHARES ADD UP: an expert layer of the reference with ALL 16
    experts held is the sum, over the four shares of the host, of each
    share's expert part (its 4 held experts' weighted outputs alone, the
    weights renormalised over all the chosen) plus the GATED shared expert
    counted ONCE; and the program's ``routed_experts`` with one share held
    is that share's part."""
    import jax
    reference, params, _, _ = toy_sample
    p = params["layer1"]
    arch = dict(reference.arch_of(params))
    rng = np.random.default_rng(7)
    h2 = jax.numpy.asarray(rng.normal(size=(40, 64)), jax.numpy.float32)
    with jax.default_matmul_precision("highest"):
        # the toy's tree holds experts 0-3: four trees of other draws stand
        # for the four shares' weights
        shares = [jax.tree_util.tree_map(
            lambda a, r=r: a + 0.02 * np.random.default_rng(r).normal(
                size=a.shape).astype(np.float32),
            {"gate_up": p["gate_up"], "down": p["down"]}) for r in range(4)]
        every = {k: jax.numpy.concatenate([s[k] for s in shares])
                 for k in ("gate_up", "down")}
        whole = reference.expert_layer(h2, dict(p, **every), arch,
                                       held=(0, 16))
        parts = [reference.expert_layer(h2, dict(p, **shares[r]), arch,
                                        held=(4 * r, 4), shared=False)
                 for r in range(4)]
        none = reference.expert_layer(
            h2, dict(p, **jax.tree_util.tree_map(lambda a: 0 * a, shares[0])),
            arch, held=(0, 4))                  # the shared expert alone
    assert all(float(abs(part).max()) > 0 for part in parts)
    assert float(abs(none).max()) > 0
    np.testing.assert_allclose(np.asarray(sum(parts) + none),
                               np.asarray(whole), rtol=1e-5, atol=1e-6)
    from dtf_tpu.models.routed_decoder import route, routed_experts
    idx, w = route(h2, p["router"], arch["top_k"])
    y, sizes = routed_experts(h2, idx, w, shares[2]["gate_up"],
                              shares[2]["down"], use_pallas=False,
                              activation="silu", held=(8, 4))
    np.testing.assert_allclose(np.asarray(y), np.asarray(parts[2]),
                               rtol=2e-4, atol=2e-5)
    assert int(sizes.sum()) == int(((np.asarray(idx) >= 8)
                                    & (np.asarray(idx) < 12)).sum())
