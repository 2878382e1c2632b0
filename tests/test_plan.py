"""Parallelism-planner tests (dtf_tpu/plan).

Three contracts, in rising order of expense:

  1. the ANALYTIC layer is exact where it claims exactness — param
     counts match ``jax.eval_shape`` of the real ``model.init`` for
     every characterized family — and the cost/memory model moves the
     right direction under every lever (ZeRO cuts optimizer bytes at
     equal step time, remat trades activations for re-forward compute,
     TP divides params, pipelining pays a bubble);
  2. plan→config COMPILATION is lossless and unambiguous — a plan
     round-trips through the flags it compiles into, plan-owned flags
     that were hand-set are loud errors, infeasible plans are rejected
     at resolve time with exit 2 from the CLI;
  3. a `--plan` run is BIT-IDENTICAL to the same configuration set by
     hand, asserted on the three reference configs the acceptance
     criteria name (cifar resnet smoke, transformer_small DP,
     transformer_small + ZeRO/model-parallel) by comparing per-step
     loss trajectories from the structured trace (slow-marked: each is
     two real multi-device compiles).
"""

import dataclasses
import functools
import glob
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import dtf_tpu.data.base as data_base
from dtf_tpu.cli import run
from dtf_tpu.config import Config
from dtf_tpu.models import build_model
from dtf_tpu.obs import trace
from dtf_tpu.plan import (Plan, apply_plan, characterize, check_plan,
                          load_plan_file, plan_from_config, predict,
                          resolve_plan, search)
from dtf_tpu.plan.cost_model import OPTIMIZER_SLOTS
from dtf_tpu.plan.mesh_spec import GiB, PRESETS, MeshSpec, mesh_spec
from dtf_tpu.plan.search import best_plan, enumerate_plans, ranked_artifact

TINY_CIFAR = dataclasses.replace(data_base.CIFAR10, image_size=8,
                                 num_train=64, num_eval=16)


@pytest.fixture(autouse=True)
def clean(monkeypatch):
    monkeypatch.setitem(data_base._SPECS, "cifar10", TINY_CIFAR)
    yield
    trace.disable()


# ---------------------------------------------------------------------------
# 1. model characterization is exact
# ---------------------------------------------------------------------------

def _real_counts(name, example):
    """(trainable, non-trainable) element counts of the ACTUAL model,
    via shape-only evaluation — no arrays are materialized."""
    model, _ = build_model(name)
    shapes = jax.eval_shape(
        functools.partial(model.init, train=False),
        jax.random.key(0), example)
    count = lambda tree: sum(int(np.prod(s.shape))
                             for s in jax.tree_util.tree_leaves(tree))
    return count(shapes["params"]), count(shapes.get("batch_stats", {}))


@pytest.mark.parametrize("name,seq", [("transformer_small", 64),
                                      ("transformer_tpu", 128)])
def test_transformer_param_counts_exact(name, seq):
    stats = characterize(name, seq_len=seq)
    tokens = jax.ShapeDtypeStruct((1, seq), jnp.int32)
    params, state = _real_counts(name, tokens)
    assert stats.params == params
    assert stats.state == state == 0


@pytest.mark.parametrize("name,size", [("resnet20", 8), ("resnet56", 8),
                                       ("resnet50", 224)])
def test_resnet_param_counts_exact(name, size):
    stats = characterize(name)
    images = jax.ShapeDtypeStruct((1, size, size, 3), jnp.float32)
    params, state = _real_counts(name, images)
    assert stats.params == params
    assert stats.state == state


def test_characterize_rejects_unplannable():
    with pytest.raises(ValueError, match="MoE|by hand"):
        characterize("moe_transformer_small")
    with pytest.raises(ValueError, match="trivial"):
        characterize("trivial")
    with pytest.raises(ValueError, match="unknown model"):
        characterize("resnet9000")


def test_family_capabilities_mirror_runner():
    t = characterize("transformer_small", seq_len=64)
    assert t.supports_tp and t.supports_seq and t.supports_remat
    p = characterize("pipeline_transformer_small", seq_len=64)
    assert p.supports_pipeline and not p.supports_tp
    r = characterize("resnet20")
    assert not (r.supports_tp or r.supports_seq or r.supports_pipeline
                or r.supports_remat)
    assert characterize("resnet50").supports_remat


# ---------------------------------------------------------------------------
# 2. Plan lattice point + mesh descriptor
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kw", [
    dict(model=2, pipeline=2),   # both ride the 'model' mesh axis
    dict(zero=4),                # ZeRO stages end at 3
    dict(data=0),
    dict(microbatch=0),
])
def test_plan_rejects(kw):
    with pytest.raises(ValueError):
        Plan(**kw)


def test_plan_dict_roundtrip():
    p = Plan(data=2, model=4, zero=1, microbatch=2, remat=True)
    assert Plan.from_dict(p.to_dict()) == p
    assert p.num_devices == 8 and p.model_axis_size == 4
    assert p.describe() == "dp=2,tp=4,zero1,micro=2,remat"
    with pytest.raises(ValueError, match="unknown plan fields"):
        Plan.from_dict({"data": 2, "tensor": 4})


def test_mesh_spec_presets_and_descriptor():
    assert mesh_spec("4x4") is PRESETS["4x4"]
    m = mesh_spec("hosts=2,devices=4,hbm=16g,flops=10t,inter=5g")
    assert (m.num_hosts, m.devices_per_host) == (2, 4)
    # bytes take BINARY suffixes (hbm=16g ≡ 16 GiB, matching the
    # presets); rates stay decimal
    assert m.hbm_bytes == 16 * GiB and m.device_flops == 10e12
    assert m.intra_bw == PRESETS["cpu"].intra_bw  # unset keys inherit
    assert m.inter_bw == 5e9
    with pytest.raises(ValueError, match="unknown mesh preset"):
        mesh_spec("v9000")
    with pytest.raises(ValueError, match="unknown mesh descriptor key"):
        mesh_spec("hosts=2,chips=4")
    with pytest.raises(ValueError, match="positive"):
        mesh_spec("hbm=0")


def test_live_mesh_spec_describes_the_chip_it_finds(monkeypatch):
    """On a TPU the live descriptor takes HBM from the device and the
    rates from the preset for its device_kind; a kind with no preset is
    refused rather than described as some other chip."""
    from dtf_tpu.runtime import mesh as mesh_mod

    def topo(kind):
        return lambda: {"num_hosts": 1, "devices_per_host": 4,
                        "platform": "tpu", "device_kind": kind,
                        "hbm_bytes": 16_909_336_064}

    monkeypatch.setattr(mesh_mod, "topology", topo("TPU v5 lite"))
    m = mesh_spec("")
    assert (m.num_hosts, m.devices_per_host) == (1, 4)
    assert m.hbm_bytes == 16_909_336_064
    assert m.device_flops == PRESETS["v5e-4"].device_flops
    monkeypatch.setattr(mesh_mod, "topology", topo("TPU v9000"))
    with pytest.raises(ValueError, match="no rate preset"):
        mesh_spec("")


def test_axis_bandwidth_tiers():
    m = PRESETS["4x4"]  # 4 hosts × 4 devices
    assert m.axis_bandwidth(1, 4) == m.intra_bw    # span fits one host
    assert m.axis_bandwidth(1, 8) == m.inter_bw    # spans two hosts
    assert m.axis_bandwidth(4, 4) == m.inter_bw    # outer axis over DCN
    assert m.axis_bandwidth(1, 1) == m.intra_bw    # degenerate


# ---------------------------------------------------------------------------
# 3. hard constraints (check_plan) mirror the runner's rules
# ---------------------------------------------------------------------------

def test_check_plan_catches_each_violation():
    mesh = PRESETS["cpu"]  # 8 devices
    t = characterize("transformer_small", seq_len=64)  # heads=4, ff=1024
    ok = Plan(data=2, model=4)
    assert check_plan(ok, t, mesh, 8) == []
    assert any("devices" in v for v in check_plan(Plan(data=4), t, mesh, 8))
    bad_tp = Plan(data=1, model=8)  # heads 4 % 8
    assert any("num_heads" in v for v in check_plan(bad_tp, t, mesh, 8))
    assert check_plan(Plan(data=2, seq=4), t, mesh, 8) == []  # 64 % 4
    t60 = characterize("transformer_small", seq_len=60)       # 60 % 8
    assert any("seq_len" in v
               for v in check_plan(Plan(data=1, seq=8), t60, mesh, 8))
    assert any("batch" in v for v in check_plan(ok, t, mesh, 9))
    assert any("microbatch" in v
               for v in check_plan(Plan(data=2, model=4, microbatch=8),
                                   t, mesh, 8))
    r = characterize("resnet20")
    assert any("tensor parallelism" in v
               for v in check_plan(Plan(data=2, model=4), r, mesh, 8))
    assert any("pipeline" in v
               for v in check_plan(Plan(data=2, pipeline=4), t, mesh, 8))
    assert any("remat" in v
               for v in check_plan(Plan(data=8, remat=True), r, mesh, 8))
    p = characterize("pipeline_transformer_small", seq_len=64)  # 4 layers
    assert check_plan(Plan(data=2, pipeline=4, microbatch=2), p,
                      mesh, 8) == []
    assert any("num_layers" in v
               for v in check_plan(Plan(data=1, pipeline=8), p, mesh, 8))


# ---------------------------------------------------------------------------
# 4. cost model directionality
# ---------------------------------------------------------------------------

FLAGSHIP = characterize("transformer_tpu", seq_len=2048, dtype_bytes=2)
POD = PRESETS["4x4"]


def _cost(plan, batch=256, optimizer="adamw", mesh=POD, stats=FLAGSHIP):
    return predict(plan, stats, mesh, batch, optimizer=optimizer)


def test_zero1_cuts_memory_not_time():
    base = _cost(Plan(data=16))
    z = _cost(Plan(data=16, zero=1))
    assert z.peak_bytes < base.peak_bytes
    assert z.step_time_s == base.step_time_s  # same wire volume
    # the saving is exactly the sharded optimizer slots
    saved = base.breakdown["opt_bytes"] - z.breakdown["opt_bytes"]
    assert saved == pytest.approx(
        base.breakdown["opt_bytes"] * (1 - 1 / 16))


def test_zero2_shards_grads_and_zero3_shards_params():
    """Stage-aware memory terms: stage 2 cuts the gradient buffer by
    ~dp (sliced accumulator + one layer's transient — which needs the
    accumulation scan, so microbatch > 1), stage 3 additionally slices
    the persistent params (the gathered working copy is still counted
    in full — honest accounting)."""
    z1 = _cost(Plan(data=16, zero=1, microbatch=2))
    z2 = _cost(Plan(data=16, zero=2, microbatch=2))
    z3 = _cost(Plan(data=16, zero=3, microbatch=2))
    # stage 2's whole point: the 2× full-grad accumulation buffer goes
    assert z2.breakdown["grad_bytes"] < z1.breakdown["grad_bytes"] / 2
    assert z2.peak_bytes < z1.peak_bytes
    # stage 3 pays the gathered copy on top of its slices
    assert z3.breakdown["param_term_bytes"] > \
        z2.breakdown["param_term_bytes"]
    # but opt + grads stay sliced, so z3 still beats replicated
    assert z3.peak_bytes < _cost(Plan(data=16, microbatch=2)).peak_bytes


def test_overlap_term_credits_only_differing_schedules():
    """hidden = min(ov_share·comm, overlap_frac·compute), where only
    the collectives whose SCHEDULE differs from the monolithic sync
    earn credit: stage 2 at m=1 emits the SAME program as stage 1 and
    must be priced identically; stage 3's pre-compute gathers earn
    credit at any m; per-chunk scatters earn it only with m > 1."""
    z1 = _cost(Plan(data=16, zero=1))
    z2 = _cost(Plan(data=16, zero=2))
    z3 = _cost(Plan(data=16, zero=3))
    assert z1.breakdown["hidden_comm_s"] == 0.0
    # m=1: stage 2 ≡ stage 1, time AND peak — identical programs
    assert z2.breakdown["hidden_comm_s"] == 0.0
    assert z2.step_time_s == z1.step_time_s
    assert z2.peak_bytes == z1.peak_bytes
    # stage 3's param gather hides behind the forward even at m=1
    assert z3.breakdown["hidden_comm_s"] > 0.0
    assert z3.step_time_s < z1.step_time_s
    # with accumulation the per-chunk scatters earn credit too
    z2_m2 = _cost(Plan(data=16, zero=2, microbatch=2))
    assert z2_m2.breakdown["hidden_comm_s"] > 0.0
    # per-microbatch scatters UNhidden cost more wire than one sync
    z2_m4 = predict(Plan(data=16, zero=2, microbatch=4), FLAGSHIP, POD,
                    256, optimizer="adamw", overlap_frac=0.0)
    z1_m4 = predict(Plan(data=16, zero=1, microbatch=4), FLAGSHIP, POD,
                    256, optimizer="adamw", overlap_frac=0.0)
    assert z2_m4.breakdown["grad_sync_s"] > z1_m4.breakdown["grad_sync_s"]
    with pytest.raises(ValueError, match="overlap_frac"):
        predict(Plan(data=16, zero=2), FLAGSHIP, POD, 256,
                optimizer="adamw", overlap_frac=1.5)


def test_zero3_unlocks_a_config_replicated_cannot_fit():
    """The headline window: a mesh where zero ∈ {0,1} is memory-
    infeasible at ANY accumulation depth but zero=3 with a sharded
    grad accumulator (microbatch > 1) fits — params+grads+opt
    dominate, so slicing them over dp is the difference between
    refusing and training."""
    stats = characterize("transformer_tpu", seq_len=256, dtype_bytes=2)
    mesh = mesh_spec("hosts=1,devices=16,hbm=1g,flops=140t")
    for m in (1, 2):
        for z in (0, 1):
            c = predict(Plan(data=16, zero=z, remat=True, microbatch=m),
                        stats, mesh, 16, optimizer="adamw")
            assert not c.feasible, (z, m)
    c3 = predict(Plan(data=16, zero=3, remat=True, microbatch=2),
                 stats, mesh, 16, optimizer="adamw")
    assert c3.feasible


def test_remat_trades_activations_for_compute():
    base = _cost(Plan(data=16))
    r = _cost(Plan(data=16, remat=True))
    assert r.breakdown["act_bytes"] < base.breakdown["act_bytes"]
    assert r.compute_s > base.compute_s  # the re-forward is paid


def test_tp_divides_params_and_pp_pays_bubble():
    base = _cost(Plan(data=16))
    tp = _cost(Plan(data=4, model=4))
    # blocks shard /4; embed + head stay replicated
    assert tp.breakdown["param_bytes"] < base.breakdown["param_bytes"]
    assert tp.breakdown["tp_psum_s"] > 0
    pstats = characterize("pipeline_transformer_small", seq_len=64)
    pp = predict(Plan(data=2, pipeline=4, microbatch=4), pstats,
                 PRESETS["cpu"], 8)
    assert pp.breakdown["bubble_factor"] == pytest.approx((4 + 4 - 1) / 4)
    assert pp.breakdown["pipeline_xfer_s"] > 0


def test_microbatch_cuts_activations():
    base = _cost(Plan(data=16))
    m = _cost(Plan(data=16, microbatch=4))
    assert m.breakdown["act_bytes"] < base.breakdown["act_bytes"]
    # grad accumulation double-buffers the gradient
    assert m.breakdown["grad_bytes"] == 2 * base.breakdown["grad_bytes"]


def test_seq_parallelism_pays_ring_attention():
    sp = _cost(Plan(data=8, seq=2))
    assert sp.breakdown["seq_ring_s"] > 0


def test_infeasible_when_hbm_tiny():
    mesh = dataclasses.replace(POD, hbm_bytes=256 * 1024 ** 2)
    c = predict(Plan(data=16), FLAGSHIP, mesh, 256, optimizer="adamw")
    assert not c.feasible and c.peak_bytes > c.hbm_budget_bytes


def test_unknown_optimizer_is_loud():
    assert OPTIMIZER_SLOTS["adamw"] == 2
    with pytest.raises(ValueError, match="unknown optimizer"):
        _cost(Plan(data=16), optimizer="lion")


# ---------------------------------------------------------------------------
# 5. search / ranking
# ---------------------------------------------------------------------------

def test_search_ranks_feasible_first_fastest_first():
    t = characterize("transformer_small", seq_len=64)
    ranked = search(t, PRESETS["cpu"], 8, optimizer="adamw")
    assert ranked, "empty lattice"
    feas = [r.feasible for r in ranked]
    assert feas == sorted(feas, reverse=True)  # feasible block first
    times = [r.cost.step_time_s for r in ranked if r.feasible]
    assert times == sorted(times)
    # equal-speed ties break toward the fewest microbatches (unmodeled
    # per-chunk dispatch overhead), then toward the lower predicted peak
    for a, b in zip(ranked, ranked[1:]):
        if (a.feasible and b.feasible
                and a.cost.step_time_s == b.cost.step_time_s):
            assert (a.plan.microbatch, a.cost.peak_bytes) \
                <= (b.plan.microbatch, b.cost.peak_bytes)


def test_enumerate_respects_family_axis_roles():
    p = characterize("pipeline_transformer_small", seq_len=64)
    plans = list(enumerate_plans(p, PRESETS["cpu"], 8))
    assert plans
    # the 'model' mesh axis carries STAGES for the pipeline family
    assert all(pl.model == 1 for pl in plans)
    assert any(pl.pipeline > 1 for pl in plans)
    r = characterize("resnet20")
    rplans = list(enumerate_plans(r, PRESETS["cpu"], 8))
    assert rplans and all(pl.model_axis_size == 1 and pl.seq == 1
                          for pl in rplans)


def test_best_plan_loud_when_nothing_fits():
    t = characterize("transformer_small", seq_len=64)
    tiny = mesh_spec("hosts=1,devices=8,hbm=16m")
    with pytest.raises(ValueError, match="HBM budget"):
        best_plan(t, tiny, 8)


def test_ranked_artifact_is_json_clean(tmp_path):
    t = characterize("transformer_small", seq_len=64)
    ranked = search(t, PRESETS["cpu"], 8)
    art = ranked_artifact(t, PRESETS["cpu"], 8, ranked, top=5)
    text = json.dumps(art)  # must serialize without custom encoders
    back = json.loads(text)
    assert back["plan_count"] == len(ranked)
    assert back["feasible_count"] == sum(1 for r in ranked if r.feasible)
    assert len(back["plans"]) == 5
    assert back["plans"][0]["feasible"] is True


# ---------------------------------------------------------------------------
# 6. plan → config compilation
# ---------------------------------------------------------------------------

def _lm_cfg(**kw):
    kw.setdefault("model", "transformer_small")
    kw.setdefault("dataset", "lm")
    kw.setdefault("use_synthetic_data", True)
    kw.setdefault("seq_len", 64)
    kw.setdefault("batch_size", 8)
    kw.setdefault("train_steps", 3)
    kw.setdefault("log_steps", 1)
    kw.setdefault("skip_eval", True)
    kw.setdefault("skip_checkpoint", True)
    kw.setdefault("model_dir", "")
    return Config(**kw)


def test_apply_plan_compiles_to_exact_flags():
    cfg = _lm_cfg()
    out = apply_plan(cfg, Plan(data=2, model=4, zero=1, microbatch=2))
    assert out.plan == ""
    assert out.num_devices == 8
    assert out.model_parallelism == 4
    assert out.optimizer_sharding is True
    assert out.grad_accum_steps == 2 and out.num_microbatches is None
    pipe = apply_plan(_lm_cfg(model="pipeline_transformer_small"),
                      Plan(data=2, pipeline=4, microbatch=2))
    assert pipe.model_parallelism == 4      # stages ride the same axis
    assert pipe.num_microbatches == 2 and pipe.grad_accum_steps == 1


def test_apply_plan_rejects_handset_conflicts():
    with pytest.raises(ValueError, match="conflicts with hand-set"):
        apply_plan(_lm_cfg(model_parallelism=4), Plan(data=8))
    with pytest.raises(ValueError, match="contradicts"):
        apply_plan(_lm_cfg(num_devices=4), Plan(data=8))
    # matching --num_devices is fine
    assert apply_plan(_lm_cfg(num_devices=8), Plan(data=8)).num_devices == 8


@pytest.mark.parametrize("plan", [
    Plan(data=8),
    Plan(data=2, model=4, zero=1),
    Plan(data=4, seq=2, microbatch=2, remat=True),
])
def test_plan_config_roundtrip(plan):
    cfg = apply_plan(_lm_cfg(), plan)
    assert plan_from_config(cfg, plan.num_devices) == plan


def test_pipeline_plan_config_roundtrip():
    plan = Plan(data=2, pipeline=4, microbatch=2)
    cfg = apply_plan(_lm_cfg(model="pipeline_transformer_small"), plan)
    assert plan_from_config(cfg, 8) == plan


def test_load_plan_file_forms(tmp_path):
    bare = tmp_path / "bare.json"
    bare.write_text(json.dumps({"data": 2, "model": 4}))
    assert load_plan_file(str(bare)) == Plan(data=2, model=4)
    wrapped = tmp_path / "wrapped.json"
    wrapped.write_text(json.dumps({"plan": {"data": 8}}))
    assert load_plan_file(str(wrapped)) == Plan(data=8)
    art = tmp_path / "ranked.json"
    art.write_text(json.dumps({"plans": [
        {"plan": {"data": 4}, "feasible": False},
        {"plan": {"data": 8, "zero": 1}, "feasible": True},
    ]}))
    assert load_plan_file(str(art)) == Plan(data=8, zero=1)
    art.write_text(json.dumps({"plans": [
        {"plan": {"data": 4}, "feasible": False}]}))
    with pytest.raises(ValueError, match="no\\s+feasible"):
        load_plan_file(str(art))


def test_plan_auto_respects_num_devices():
    """--num_devices N + --plan auto plans a SUBSET of the attached
    chips (the live mesh is bounded by the flag) instead of dying on
    apply_plan's device-count contradiction."""
    out = resolve_plan(_lm_cfg(plan="auto", num_devices=4))
    assert out.plan == "" and out.num_devices == 4


def test_plan_from_config_pipeline_auto_microbatch():
    """A pipeline config with num_microbatches UNSET mirrors the
    runner's auto-pick (M = 4·pp halved until it divides the per-shard
    batch) — calibration must predict the schedule the run executes,
    not a 1-microbatch strawman."""
    cfg = _lm_cfg(model="pipeline_transformer_small",
                  model_parallelism=4, batch_size=32)
    plan = plan_from_config(cfg, 8)
    assert plan.pipeline == 4 and plan.microbatch == 16  # 4·pp, 16|16
    cfg_odd = _lm_cfg(model="pipeline_transformer_small",
                      model_parallelism=4, batch_size=4)
    # per-shard 2: 16 -> 8 -> 4 -> 2
    assert plan_from_config(cfg_odd, 8).microbatch == 2


def test_resolve_plan_rejects_oversized_mesh(tmp_path):
    """A plan for a larger simulated mesh must die loudly at resolve
    time — runtime/mesh.initialize would otherwise silently truncate
    the device list and run a DIFFERENT parallelization than planned."""
    f = tmp_path / "p.json"
    f.write_text(json.dumps({"data": 16}))
    cfg = _lm_cfg(plan=str(f), plan_mesh="hosts=2,devices=8",
                  batch_size=16)
    with pytest.raises(ValueError, match="attached"):
        resolve_plan(cfg)


def test_resolve_plan_rejects_multihost_num_devices():
    """--num_devices bounds the live SINGLE-host planning mesh; on a
    multi-host topology its meaning is strategy-dependent, so the
    combination is a loud error pointing at --plan_mesh."""
    with pytest.raises(ValueError, match="multi-host"):
        resolve_plan(_lm_cfg(plan="auto", num_devices=4),
                     mesh=PRESETS["4x4"])


def test_resolve_plan_noop_and_guards(tmp_path):
    cfg = _lm_cfg()
    assert resolve_plan(cfg) is cfg  # plan="" is a strict no-op
    bad = tmp_path / "p.json"
    bad.write_text(json.dumps({"data": 8}))
    with pytest.raises(ValueError, match="SPMD"):
        resolve_plan(_lm_cfg(plan=str(bad),
                             distribution_strategy="parameter_server"))


def test_resolve_plan_rejects_infeasible_file(tmp_path):
    f = tmp_path / "p.json"
    f.write_text(json.dumps({"data": 8}))
    tiny = mesh_spec("hosts=1,devices=8,hbm=16m")
    with pytest.raises(ValueError, match="INFEASIBLE"):
        resolve_plan(_lm_cfg(plan=str(f)), mesh=tiny)


def test_config_validates_plan_flags(tmp_path):
    with pytest.raises(ValueError, match="no such plan file"):
        Config(model="resnet20", dataset="cifar10",
               plan=str(tmp_path / "missing.json"))
    with pytest.raises(ValueError, match="unknown mesh preset"):
        Config(model="resnet20", dataset="cifar10", plan_mesh="v9000")
    Config(model="resnet20", dataset="cifar10", plan="auto",
           plan_mesh="4x4")  # valid combination constructs


# ---------------------------------------------------------------------------
# 7. `--plan` runs are bit-identical to the hand-flagged equivalent
# ---------------------------------------------------------------------------

def _loss_by_step(trace_dir):
    out = {}
    for path in glob.glob(os.path.join(trace_dir, "trace_rank*.jsonl")):
        for rec in trace.read_records(path):
            if rec.get("kind") == "event" and rec.get("name") == "train_loss":
                out.setdefault(int(rec["step"]), set()).add(rec["loss"])
    return out


def _assert_plan_run_bit_identical(tmp_path, cfg):
    """run(--plan …) vs run(the flags that plan compiles into): the
    per-step loss trajectories must be IDENTICAL — the planner owns no
    runtime, it only writes flags."""
    planned = dataclasses.replace(
        cfg, trace_dir=str(tmp_path / "planned_t"),
        model_dir=str(tmp_path / "planned_m"))
    run(planned)  # runner resolves cfg.plan internally
    trace.disable()
    hand = resolve_plan(cfg)  # the SAME resolution, done by hand
    assert hand.plan == ""    # ...is already in hand-flag form
    hand = dataclasses.replace(
        hand, trace_dir=str(tmp_path / "hand_t"),
        model_dir=str(tmp_path / "hand_m"))
    run(hand)
    trace.disable()
    a = _loss_by_step(str(tmp_path / "planned_t"))
    b = _loss_by_step(str(tmp_path / "hand_t"))
    assert a and set(a) == set(range(1, cfg.train_steps + 1))
    assert a == b, f"planned {a} != hand-flagged {b}"
    return hand


@pytest.mark.slow
def test_plan_auto_bit_identical_cifar_resnet(tmp_path):
    """Reference config 1: the cifar resnet smoke, planned on an
    explicit 2-device mesh descriptor (the resnet lattice is pure DP
    × zero × microbatch)."""
    cfg = Config(model="resnet20", dataset="cifar10",
                 use_synthetic_data=True, batch_size=8, train_steps=3,
                 log_steps=1, skip_eval=True, skip_checkpoint=True,
                 model_dir="", plan="auto", plan_mesh="hosts=1,devices=2")
    hand = _assert_plan_run_bit_identical(tmp_path, cfg)
    assert hand.num_devices == 2 and hand.model_parallelism == 1


@pytest.mark.slow
def test_plan_file_bit_identical_transformer_dp(tmp_path):
    """Reference config 2: transformer_small pure data parallelism,
    pinned by a plan FILE (the artifact path of plan→config)."""
    f = tmp_path / "dp.json"
    f.write_text(json.dumps({"plan": {"data": 8}}))
    cfg = _lm_cfg(plan=str(f))
    hand = _assert_plan_run_bit_identical(tmp_path, cfg)
    assert hand.num_devices == 8
    assert hand.model_parallelism == 1 and not hand.optimizer_sharding


@pytest.mark.slow
def test_plan_auto_bit_identical_transformer_zero_mp(tmp_path):
    """Reference config 3: transformer_small under `--plan auto` on the
    live 8-device mesh — the analytic winner at these shapes is now a
    ZeRO-2/3 plan (the overlap term hides the per-microbatch grad
    collectives behind compute, so the sharded stages outrank the
    monolithic-sync ones), exercising the --zero_stage compile path
    end to end through plan resolution."""
    cfg = _lm_cfg(plan="auto")
    hand = _assert_plan_run_bit_identical(tmp_path, cfg)
    assert hand.zero_stage_effective >= 2
    # and the historical TP × ZeRO-1 point stays bit-identical when
    # pinned explicitly via a plan file (the pre-overlap winner)
    import json as json_lib
    plan_file = tmp_path / "tp_zero1.json"
    plan_file.write_text(json_lib.dumps(
        {"plan": {"data": 4, "model": 2, "zero": 1}}))
    cfg2 = _lm_cfg(plan=str(plan_file))
    hand2 = _assert_plan_run_bit_identical(tmp_path / "pinned", cfg2)
    assert hand2.model_parallelism > 1
    assert hand2.optimizer_sharding is True


# ---------------------------------------------------------------------------
# 8. plan_main CLI (subprocess) + calibration contract
# ---------------------------------------------------------------------------

def _plan_main(*args, timeout=540, one_device=False):
    env = dict(os.environ)
    if one_device:
        # the pytest process exports the 8-virtual-device XLA_FLAGS
        # (conftest) and subprocesses inherit it; the calibration smoke
        # wants ONE device — eight virtual devices timesharing the same
        # physical cores would skew measured-vs-predicted by the
        # timesharing factor, which is a property of the test harness,
        # not of the cost model under test
        env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, "-m", "dtf_tpu.cli.plan_main", *args],
        capture_output=True, text=True, timeout=timeout, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def test_plan_main_ranks_and_writes_artifact(tmp_path):
    out = tmp_path / "plans.json"
    r = _plan_main("--model", "transformer_tpu", "--dataset", "lm",
                   "--seq_len", "2048", "--batch_size", "256",
                   "--dtype", "bf16", "--optimizer", "adamw",
                   "--plan_mesh", "4x4", "--top", "5", "--out", str(out))
    assert r.returncode == 0, r.stderr
    assert "plans feasible" in r.stdout
    art = json.loads(out.read_text())
    assert art["mesh"]["name"] == "4x4" and art["plans"]
    assert art["plans"][0]["feasible"] is True


def test_plan_cache_hit_reproduces_search_and_keys_strictly(tmp_path):
    """The sidecar memoizes the EXACT ranking (hit ≡ fresh search,
    object for object), keys on (workload, mesh, batch) strictly
    (different batch = miss), and degrades a corrupt file to a
    recompute instead of failing the resolve."""
    from dtf_tpu.plan.cache import cached_search
    from dtf_tpu.plan.compile import stats_for_config
    from dtf_tpu.plan.mesh_spec import mesh_spec

    cfg = Config(model="transformer_small", dataset="lm", batch_size=8,
                 seq_len=64)
    stats = stats_for_config(cfg)
    mesh = mesh_spec("cpu")
    path = str(tmp_path / "plan_cache.json")
    fresh, hit1 = cached_search(path, stats, mesh, 8)
    again, hit2 = cached_search(path, stats, mesh, 8)
    assert not hit1 and hit2
    assert ([r.to_dict() for r in again] == [r.to_dict() for r in fresh])
    _, hit3 = cached_search(path, stats, mesh, 16)
    assert not hit3                        # batch is part of the key
    _, hit4 = cached_search(path, stats, mesh_spec("4x4"), 8)
    assert not hit4                        # mesh descriptor too
    with open(path, "w") as f:
        f.write("{not json")
    recomputed, hit5 = cached_search(path, stats, mesh, 8)
    assert not hit5
    assert ([r.to_dict() for r in recomputed]
            == [r.to_dict() for r in fresh])
    _, hit6 = cached_search(path, stats, mesh, 8)   # rewritten after
    assert hit6


def test_plan_cache_stale_version_recomputes(tmp_path):
    """A cache entry written under an older CACHE_VERSION (a previous
    cost-model formula) must be RECOMPUTED, never served: the version
    is part of both the per-entry key and the file header, so a
    formula change cannot silently resurrect an old ranking."""
    import json as json_lib

    from dtf_tpu.plan import cache as cache_mod
    from dtf_tpu.plan.cache import cache_key, cached_search
    from dtf_tpu.plan.compile import stats_for_config
    from dtf_tpu.plan.mesh_spec import mesh_spec

    cfg = Config(model="transformer_small", dataset="lm", batch_size=8,
                 seq_len=64)
    stats = stats_for_config(cfg)
    mesh = mesh_spec("cpu")
    path = str(tmp_path / "plan_cache.json")
    fresh, hit = cached_search(path, stats, mesh, 8)
    assert not hit

    # forge the file a PREVIOUS version would have written: same
    # workload, keyed and stamped with CACHE_VERSION-1, carrying a
    # poisoned ranking that today's formula would never produce
    with open(path) as f:
        doc = json_lib.load(f)
    (cur_key, entry), = doc["entries"].items()
    poisoned = dict(entry)
    poisoned["ranked"] = entry["ranked"][:1]
    try:
        cache_mod.CACHE_VERSION -= 1
        old_key, _ = cache_key(stats, mesh, 8, "sgd")
    finally:
        cache_mod.CACHE_VERSION += 1
    assert old_key != cur_key       # the version IS part of the key
    stale = {"cache_version": cache_mod.CACHE_VERSION - 1,
             "entries": {old_key: poisoned}}
    with open(path, "w") as f:
        json_lib.dump(stale, f)

    recomputed, hit2 = cached_search(path, stats, mesh, 8)
    assert not hit2                 # stale version = miss, not serve
    assert len(recomputed) == len(fresh) > 1
    assert ([r.to_dict() for r in recomputed]
            == [r.to_dict() for r in fresh])
    # and the rewritten sidecar is current-version (stale entry gone)
    with open(path) as f:
        rewritten = json_lib.load(f)
    assert rewritten["cache_version"] == cache_mod.CACHE_VERSION
    assert old_key not in rewritten["entries"]


def test_plan_main_uses_cache_on_repeat(tmp_path):
    """Repeated --plan_cache rankings: first run misses and writes the
    sidecar, second hits and skips the search."""
    cache = str(tmp_path / "cache.json")
    args = ("--model", "transformer_tpu", "--dataset", "lm",
            "--seq_len", "2048", "--batch_size", "256",
            "--dtype", "bf16", "--optimizer", "adamw",
            "--plan_mesh", "4x4", "--top", "3", "--plan_cache", cache)
    r1 = _plan_main(*args)
    assert r1.returncode == 0, r1.stderr
    assert "plan cache: miss" in r1.stdout
    assert os.path.exists(cache)
    r2 = _plan_main(*args)
    assert r2.returncode == 0, r2.stderr
    assert "plan cache: HIT — search skipped" in r2.stdout
    # the ranking table is unchanged by the cache
    tbl = lambda s: [ln for ln in s.splitlines()
                     if ln.strip().startswith(("1 ", "2 ", "3 "))]
    assert tbl(r1.stdout) == tbl(r2.stdout)


def test_resolve_plan_auto_through_cache(tmp_path):
    """--plan auto resolution (the runner path) through the sidecar
    compiles the same flags as the uncached resolve."""
    from dtf_tpu.plan.compile import resolve_plan

    base = Config(model="transformer_small", dataset="lm", batch_size=8,
                  seq_len=64, plan="auto", plan_mesh="cpu")
    want = resolve_plan(base)
    cache = str(tmp_path / "cache.json")
    got1 = resolve_plan(base.replace(plan_cache=cache))
    got2 = resolve_plan(base.replace(plan_cache=cache))   # the hit
    for got in (got1, got2):
        assert (got.model_parallelism, got.seq_parallelism,
                got.optimizer_sharding, got.grad_accum_steps,
                got.remat) == (
            want.model_parallelism, want.seq_parallelism,
            want.optimizer_sharding, want.grad_accum_steps, want.remat)


def test_plan_main_auto_rejects_all_infeasible():
    """`--plan auto` on an all-infeasible lattice must exit 2, not
    rank-and-exit-0 (and --calibrate must never get the chance to run
    the least-over-budget plan)."""
    r = _plan_main("--model", "transformer_small", "--dataset", "lm",
                   "--seq_len", "64", "--batch_size", "8",
                   "--plan", "auto",
                   "--plan_mesh", "hosts=1,devices=8,hbm=16m")
    assert r.returncode == 2
    assert "plan auto REJECTED" in r.stderr


def test_calibrate_resets_plan_owned_flags(monkeypatch):
    """--calibrate on a HAND-FLAGGED config (plan_from_config's
    documented purpose): the derived plan re-writes the plan-owned
    flags, so they are reset to defaults first — apply_plan's
    hand-set-flag conflict guard must not fire on them."""
    import importlib

    import dtf_tpu.cli.runner as runner_mod
    from dtf_tpu.cli import plan_main

    # the package __init__ re-binds `mesh_spec` (the function) over the
    # submodule attribute, so `import dtf_tpu.plan.mesh_spec as m`
    # resolves to the function — go through importlib for the module
    mesh_spec_mod = importlib.import_module("dtf_tpu.plan.mesh_spec")
    from dtf_tpu.obs.registry import default_registry
    from dtf_tpu.plan.compile import stats_for_config

    default_registry().reset()
    cfg = _lm_cfg(grad_accum_steps=2, remat=True)
    seen = {}

    def fake_run(run_cfg):
        seen["cfg"] = run_cfg
        return {"avg_exp_per_second": 100.0}

    monkeypatch.setattr(runner_mod, "run", fake_run)
    monkeypatch.setattr(mesh_spec_mod, "calibrate_device_flops",
                        lambda: 1e10)
    mesh = mesh_spec("cpu")
    plan = plan_from_config(cfg, mesh.num_devices)
    assert plan.microbatch == 2 and plan.remat
    rc = plan_main._calibrate(cfg, stats_for_config(cfg), mesh, plan,
                              steps=2, tolerance=1e9, overlap_frac=0.5)
    assert rc == 0
    # the smoke ran with the SAME hand-set levers, via the plan
    assert seen["cfg"].grad_accum_steps == 2
    assert seen["cfg"].remat is True


def test_plan_main_rejects_infeasible_loudly(tmp_path):
    f = tmp_path / "p.json"
    f.write_text(json.dumps({"data": 8}))
    r = _plan_main("--model", "transformer_small", "--dataset", "lm",
                   "--seq_len", "64", "--batch_size", "8",
                   "--plan", str(f), "--plan_mesh",
                   "hosts=1,devices=8,hbm=16m")
    assert r.returncode == 2
    assert "REJECTED (memory-infeasible)" in r.stderr


@pytest.mark.slow
def test_plan_main_check_feasible_plans_compile():
    """The --check contract: every plan the model calls feasible must
    actually compile a smoke train step on the live devices."""
    r = _plan_main("--devices", "8", "--model", "transformer_small",
                   "--dataset", "lm", "--use_synthetic_data",
                   "--seq_len", "64", "--batch_size", "8",
                   "--check", "--check_top", "2", "--top", "3")
    assert r.returncode == 0, r.stdout + r.stderr
    assert r.stdout.count(": OK") == 2


# The documented memory-model factor: predicted peak counts transient
# activation/collective bytes the end-of-run `jax.live_arrays()` set no
# longer holds, so predicted/measured lands above 1; the fixed runtime
# overhead and conservative activation accounting bound it below 4× on
# the CPU smoke shapes.
MEM_FACTOR = 4.0


@pytest.mark.slow
def test_calibration_within_contract():
    """The acceptance bar: predicted step time within 2× of measured on
    the CPU smoke (plan_main exits nonzero otherwise), and predicted
    peak bytes within MEM_FACTOR of jax.live_arrays()-measured bytes."""
    r = _plan_main("--model", "transformer_small", "--dataset", "lm",
                   "--use_synthetic_data", "--seq_len", "64",
                   "--batch_size", "4", "--optimizer", "adamw",
                   "--calibrate", "--calibrate_tolerance", "2.0",
                   "--top", "0", one_device=True)
    assert r.returncode == 0, r.stdout + r.stderr
    m = re.search(r"ratio (\d+\.\d+)", r.stdout)
    assert m, r.stdout
    assert 0.5 <= float(m.group(1)) <= 2.0
    mem = re.search(r"predicted peak (\d+\.\d+) MiB, measured live "
                    r"(\d+\.\d+) MiB", r.stdout)
    assert mem, r.stdout
    factor = float(mem.group(1)) / float(mem.group(2))
    assert 1.0 <= factor <= MEM_FACTOR, (
        f"memory model off: predicted/live = {factor:.2f}")


def test_plan_main_out_writes_the_ranked_plan_artifact(tmp_path):
    """``plan_main --out`` (ci_check stage 6, the docs example's
    reproducible source) runs analytically — no accelerator work — its
    artifact loads as a plan file, and the property the worked example
    shows holds of its best plan: ZeRO-1 cuts predicted peak memory."""
    from dtf_tpu.cli import plan_main

    out = tmp_path / "PLAN.json"
    rc = plan_main.main(["--model", "transformer_small", "--dataset", "lm",
                         "--seq_len", "64", "--batch_size", "8",
                         "--plan_mesh", "cpu", "--plan", "auto",
                         "--out", str(out)])
    assert rc == 0
    plan = load_plan_file(str(out))
    assert plan.num_devices == PRESETS["cpu"].num_devices
    stats = characterize("transformer_small", seq_len=64)
    mesh = mesh_spec("cpu")
    c0, c1 = (predict(dataclasses.replace(plan, zero=z), stats, mesh, 8)
              for z in (0, 1))
    assert c1.peak_bytes < c0.peak_bytes


def test_plan_cache_calibration_feedback_loop(tmp_path):
    """The --calibrate feedback loop (ROADMAP FSDP follow-on #2): a
    measured plan_overlap_frac_implied persisted per (workload, mesh)
    is auto-applied by later cached searches — the ranking key carries
    the calibrated fraction, so a fresh calibration re-ranks instead
    of serving the default-fraction entry — while an EXPLICIT
    --overlap_frac always wins, and unknown workloads fall back to the
    default."""
    from dtf_tpu.plan.cache import (cached_search, load_calibration,
                                    store_calibration)
    from dtf_tpu.plan.compile import stats_for_config
    from dtf_tpu.plan.cost_model import DEFAULT_OVERLAP_FRAC
    from dtf_tpu.plan.mesh_spec import mesh_spec
    from dtf_tpu.plan.search import search

    cfg = Config(model="transformer_small", dataset="lm", batch_size=8,
                 seq_len=64)
    stats = stats_for_config(cfg)
    mesh = mesh_spec("cpu")
    path = str(tmp_path / "plan_cache.json")

    # no calibration yet: auto == default fraction
    assert load_calibration(path, stats, mesh) is None
    auto, hit = cached_search(path, stats, mesh, 8)
    assert not hit
    default_ranked = search(stats, mesh, 8,
                            overlap_frac=DEFAULT_OVERLAP_FRAC)
    assert ([r.to_dict() for r in auto]
            == [r.to_dict() for r in default_ranked])

    # persist a measured fraction; auto now uses it (a MISS — the
    # fraction is part of the ranking key) and matches a fresh search
    # at that fraction
    store_calibration(path, stats, mesh, 0.9)
    assert load_calibration(path, stats, mesh) == pytest.approx(0.9)
    cal, hit2 = cached_search(path, stats, mesh, 8)
    assert not hit2
    cal_ranked = search(stats, mesh, 8, overlap_frac=0.9)
    assert ([r.to_dict() for r in cal]
            == [r.to_dict() for r in cal_ranked])
    _, hit3 = cached_search(path, stats, mesh, 8)
    assert hit3                       # memoized under the new fraction

    # explicit fraction overrides the calibration
    exp, _ = cached_search(path, stats, mesh, 8, overlap_frac=0.1)
    exp_ranked = search(stats, mesh, 8, overlap_frac=0.1)
    assert ([r.to_dict() for r in exp]
            == [r.to_dict() for r in exp_ranked])

    # a different mesh is a different calibration point
    assert load_calibration(path, stats, mesh_spec("4x4")) is None
    # out-of-range persisted values degrade to the default, not error
    store_calibration(path, stats, mesh, 7.5)
    assert load_calibration(path, stats, mesh) is None


@pytest.mark.slow
def test_plan_main_calibrate_persists_overlap_to_cache(tmp_path):
    """`plan_main --calibrate` with --plan_cache closes the loop end to
    end: the measured implied fraction lands in the cache file and the
    next ranking announces it is using the MEASURED value."""
    cache_path = tmp_path / "plan_cache.json"
    r = _plan_main("--devices", "2", "--model", "transformer_small",
                   "--dataset", "lm", "--use_synthetic_data",
                   "--seq_len", "64", "--batch_size", "8",
                   "--optimizer", "adamw", "--zero_stage", "2",
                   "--calibrate", "--calibrate_steps", "4",
                   "--calibrate_tolerance", "1e9", "--top", "0",
                   "--plan_cache", str(cache_path), one_device=True)
    assert r.returncode == 0, r.stderr + r.stdout
    assert "persisted to" in r.stdout
    doc = json.loads(cache_path.read_text())
    (entry,) = doc["calibrations"].values()
    assert 0.0 <= entry["overlap_frac_implied"] <= 1.0
    assert entry["workload"]["model"] == "transformer_small"
    # a later ranking against the same cache announces the measurement
    r2 = _plan_main("--devices", "2", "--model", "transformer_small",
                    "--dataset", "lm", "--use_synthetic_data",
                    "--seq_len", "64", "--batch_size", "8",
                    "--optimizer", "adamw", "--top", "1",
                    "--plan_cache", str(cache_path), one_device=True)
    assert r2.returncode == 0, r2.stderr
    assert "MEASURED overlap_frac" in r2.stdout
