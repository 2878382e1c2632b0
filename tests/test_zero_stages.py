"""ZeRO stages 2/3 (--zero_stage): sharded gradients / sharded params
on the data axis, and the canonical-checkpoint contract that makes the
stages interchangeable.

Every stage is mathematically plain data parallelism, so the parity
tests demand the documented float tolerance (reassociation of the
reduce-scatter vs the all-reduce is the only difference).  Checkpoints
are always WRITTEN in the stage-0 layout (Trainer.canonical_state), so
the matrix here pins: save at stage A → restore at stage B continues
the exact stage-0 trajectory, for every interesting (A, B) — and a
stage-3 checkpoint loads into serving via the bridge's structure-free
restore with full-shaped params.
"""

import dataclasses

import flax.linen as nn
import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import dtf_tpu.data.base as data_base
from dtf_tpu.cli import run
from dtf_tpu.config import Config
from dtf_tpu.models import build_model
from dtf_tpu.runtime import initialize
from dtf_tpu.runtime.mesh import DATA_AXIS
from dtf_tpu.train import Trainer

TINY = dataclasses.replace(data_base.CIFAR10, image_size=8, num_train=64,
                           num_eval=16)


@pytest.fixture(autouse=True)
def tiny_specs(monkeypatch):
    monkeypatch.setitem(data_base._SPECS, "cifar10", TINY)


def _cfg(model_dir, stage, steps, **kw):
    kw.setdefault("checkpoint_steps", 2)
    return Config(model="resnet20", dataset="cifar10", batch_size=8,
                  train_steps=steps, use_synthetic_data=True,
                  skip_eval=True, model_dir=model_dir, log_steps=1,
                  distribution_strategy="mirrored", num_devices=4,
                  zero_stage=stage if stage != 1 else 0,
                  optimizer_sharding=stage == 1, **kw)


def test_zero_stage_flag_validation():
    with pytest.raises(ValueError, match="zero_stage"):
        Config(zero_stage=4)
    with pytest.raises(ValueError, match="optimizer_sharding"):
        Config(optimizer_sharding=True, zero_stage=2)
    with pytest.raises(ValueError, match="zero_probe"):
        Config(zero_probe=True)  # needs stage >= 2
    assert Config(zero_stage=2).zero_stage_effective == 2
    assert Config(optimizer_sharding=True).zero_stage_effective == 1
    assert Config().zero_stage_effective == 0


def _trainer(stage, num_devices=4):
    cfg = _cfg("", stage, 1, checkpoint_steps=0, skip_checkpoint=True)
    cfg = cfg.replace(num_devices=num_devices)
    rt = initialize(cfg)
    model, l2 = build_model("resnet20")
    trainer = Trainer(cfg, rt, model, l2, TINY, schedule=lambda s: 0.1)
    rng = np.random.default_rng(0)
    images = rng.normal(120, 50, (8, 8, 8, 3)).astype(np.float32)
    labels = rng.integers(0, 10, (8,)).astype(np.int32)
    state = trainer.init_state(jax.random.key(0), (images, labels))
    return trainer, rt, state, (images, labels)


def test_zero3_params_are_sliced_and_canonical_roundtrips(eight_devices):
    """The point of stage 3: params live as 1/nd column blocks of a
    2-D view over 'data'; the canonical conversion re-gathers full
    shapes and the staged inverse reproduces the slices BIT-identically
    (what makes the checkpoint matrix exact)."""
    trainer, rt, state, batch = _trainer(3)
    for leaf in jax.tree_util.tree_leaves(state.params):
        assert leaf.ndim == 2                       # [rows, nd·k] views
        assert leaf.sharding.spec == P(None, DATA_AXIS)
        assert leaf.shape[0] % 8 == 0               # whole (8, 128)
        assert leaf.shape[1] % (4 * 128) == 0       # tiles a data shard
    canon = trainer.canonical_state(state)
    # canonical params are the MODEL's shapes (conv kernels are 4-D)
    dims = {leaf.ndim
            for leaf in jax.tree_util.tree_leaves(canon.params)}
    assert 4 in dims
    staged = trainer.staged_state(jax.device_get(canon))
    for a, b in zip(jax.tree_util.tree_leaves(state),
                    jax.tree_util.tree_leaves(staged)):
        np.testing.assert_array_equal(np.asarray(jax.device_get(a)),
                                      np.asarray(jax.device_get(b)))
    # and the step runs on the sliced layout
    state, metrics = trainer.train_step(state, *rt.shard_batch(batch))
    assert np.isfinite(float(jax.device_get(metrics["loss"])))


@pytest.mark.slow
def test_stage23_match_plain_dp(eight_devices):
    """Per-step loss parity: stages 2 and 3 ≡ stage 0, with and
    without sharded grad accumulation."""
    def final_loss(stage, accum):
        cfg = _cfg("", stage, 2, checkpoint_steps=0,
                   skip_checkpoint=True).replace(grad_accum_steps=accum)
        rt = initialize(cfg)
        model, l2 = build_model("resnet20")
        trainer = Trainer(cfg, rt, model, l2, TINY,
                          schedule=lambda s: 0.1)
        rng = np.random.default_rng(1)
        images = rng.normal(120, 50, (8, 8, 8, 3)).astype(np.float32)
        labels = rng.integers(0, 10, (8,)).astype(np.int32)
        state = trainer.init_state(jax.random.key(0), (images, labels))
        batch = rt.shard_batch((images, labels))
        for _ in range(2):
            state, m = trainer.train_step(state, *batch)
        return float(jax.device_get(m["loss"]))

    for accum in (1, 2):
        ref = final_loss(0, accum)
        for stage in (2, 3):
            np.testing.assert_allclose(final_loss(stage, accum), ref,
                                       rtol=1e-5)


# save-stage → restore-stage pairs covering every conversion direction
# (full↔sliced params, full↔sliced opt state, same-stage identity)
MATRIX = [(0, 3), (3, 0), (2, 3), (3, 2), (1, 2), (3, 3)]


@pytest.mark.slow
@pytest.mark.parametrize("save_stage,restore_stage", MATRIX)
def test_checkpoint_matrix_cross_stage_trajectory_exact(
        tmp_path, eight_devices, save_stage, restore_stage):
    """Save at stage A (canonical layout on disk), restore at stage B,
    train on: the final loss equals the uninterrupted stage-0 run's —
    the stages are one training process with different layouts."""
    ref = run(_cfg(str(tmp_path / "ref"), 0, 4))
    run(_cfg(str(tmp_path / "x"), save_stage, 2))
    out = run(_cfg(str(tmp_path / "x"), restore_stage, 4,
                   resume=True))
    np.testing.assert_allclose(out["loss"], ref["loss"], rtol=1e-5)


@pytest.mark.slow
def test_zero3_checkpoint_serves_via_bridge(tmp_path, eight_devices):
    """A stage-3 run's checkpoint loads through the serve bridge's
    structure-free restore with FULL-shaped params (the canonical
    layout) — token-for-token equal to the same seed's stage-0
    checkpoint."""
    from dtf_tpu.train.checkpoint import load_train_checkpoint
    run(_cfg(str(tmp_path / "z3"), 3, 2))
    run(_cfg(str(tmp_path / "z0"), 0, 2))
    v3 = load_train_checkpoint(str(tmp_path / "z3"))
    v0 = load_train_checkpoint(str(tmp_path / "z0"))
    assert v3 is not None and v0 is not None
    l3 = dict(jax.tree_util.tree_leaves_with_path(v3["params"]))
    l0 = dict(jax.tree_util.tree_leaves_with_path(v0["params"]))
    assert set(l3) == set(l0)
    for path, a in l0.items():
        assert np.asarray(a).shape == np.asarray(l3[path]).shape
        np.testing.assert_allclose(np.asarray(a),
                                   np.asarray(l3[path]),
                                   atol=2e-6, rtol=1e-5,
                                   err_msg=jax.tree_util.keystr(path))


@pytest.mark.slow
def test_zero_resume_layout_mismatch_is_loud(tmp_path, eight_devices):
    """A checkpoint that VERIFIES (sha256-intact) but cannot restore
    into the canonical ZeRO template (layout mismatch — e.g. written
    by a different optimizer config, or a pre-canonical-format ZeRO
    run) must raise, not silently restart from step 0."""
    run(_cfg(str(tmp_path), 0, 2))  # sgd stage-0 checkpoint
    with pytest.raises(ValueError, match="canonical ZeRO checkpoint"):
        run(_cfg(str(tmp_path), 3, 4, resume=True)
            .replace(optimizer="adamw"))


@pytest.mark.slow
def test_zero3_killed_at_k_resumes_bit_identical(tmp_path):
    """The PR-4 chaos path under ZeRO-3: an injected crash@step:4 under
    the launch_local supervisor, resumed through the canonical-
    checkpoint restore, reproduces the uninterrupted run's per-step
    loss trajectory BIT-identically — sliced params/optimizer state
    round-trip through the stage-0 wire format without a single ulp."""
    import glob
    import json
    import subprocess
    import sys

    from dtf_tpu.cli.launch import launch_local

    def train_cmd(model_dir, trace_dir, extra=()):
        return [sys.executable, "-m", "dtf_tpu.cli.lm_main",
                "--use_synthetic_data", "--model", "transformer_small",
                "--seq_len", "64", "--batch_size", "4",
                "--train_steps", "6", "--log_steps", "1",
                "--skip_eval", "--verbose", "0",
                "--step_time_guard_factor", "0",
                "--num_devices", "4", "--zero_stage", "3",
                "--model_dir", model_dir, "--trace_dir", trace_dir,
                *extra]

    def loss_by_step(trace_dir):
        out = {}
        for path in glob.glob(str(trace_dir) + "/trace_rank*.jsonl"):
            with open(path) as f:
                for line in f:
                    try:
                        rec = json.loads(line)
                    except json.JSONDecodeError:
                        continue
                    if rec.get("kind") == "event" and \
                            rec.get("name") == "train_loss":
                        out.setdefault(int(rec["step"]),
                                       set()).add(rec["loss"])
        return out

    r = subprocess.run(train_cmd(str(tmp_path / "m0"),
                                 str(tmp_path / "t0")), timeout=900)
    assert r.returncode == 0
    baseline = loss_by_step(tmp_path / "t0")
    assert set(baseline) == set(range(1, 7))

    rc = launch_local(
        train_cmd(str(tmp_path / "m1"), str(tmp_path / "t1"),
                  extra=("--resume", "--checkpoint_steps", "2",
                         "--fault", "crash@step:4")),
        num_processes=1, coordinator="localhost:0",
        log_dir=str(tmp_path / "logs"), devices_per_process=None,
        max_restarts=2, restart_backoff_s=0.1)
    assert rc == 0
    got = loss_by_step(tmp_path / "t1")
    assert set(got) == set(baseline)
    for step in sorted(baseline):
        assert got[step] == baseline[step], (
            f"step {step}: {sorted(got[step])} != "
            f"{sorted(baseline[step])}")


@pytest.mark.slow
def test_zero_smoke_tool():
    """tools/zero_smoke.py — the ci_check stage-13 contract — as a
    slow-marked test so the suite exercises it too."""
    import subprocess
    import sys
    r = subprocess.run([sys.executable, "tools/zero_smoke.py",
                        "--fast"], capture_output=True, text=True,
                       timeout=1500)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"


# ---------------------------------------------------------------------------
# --zero_wire bf16: the grad reduce-scatter wire trade
# ---------------------------------------------------------------------------

def test_zero_wire_validation():
    with pytest.raises(ValueError, match="zero_wire"):
        Config(zero_wire="fp8")
    with pytest.raises(ValueError, match="zero_wire"):
        Config(zero_wire="bf16")              # needs stage >= 2
    with pytest.raises(ValueError, match="zero_wire"):
        Config(zero_wire="bf16", optimizer_sharding=True)  # stage 1
    assert Config(zero_wire="bf16", zero_stage=2).zero_wire == "bf16"
    assert Config(zero_stage=3).zero_wire == "fp32"


# documented loss tolerance of the bf16 scatter wire vs the f32 wire:
# the collective SUMS in bf16 (that is the halved-volume trade), so
# per-step losses agree to bf16 rounding of the gradient signal —
# orders above float-ulp, orders below any training signal
ZERO_WIRE_LOSS_RTOL = 5e-2


@pytest.mark.slow  # long tolerance run; bf16-wire validation units stay tier-1
def test_zero_wire_bf16_tracks_f32_within_tolerance(eight_devices):
    """--zero_wire bf16 halves the stage-2/3 scatter volume by casting
    the gradients' slice views to bf16 BEFORE psum_scatter (the slices and
    the cross-microbatch accumulation stay f32).  The trajectories must
    agree within the documented tolerance — and the wire dtype must
    actually reach the scatter (the trainer records it)."""
    def losses(wire):
        cfg = _cfg("", 2, 2, checkpoint_steps=0,
                   skip_checkpoint=True).replace(zero_wire=wire)
        rt = initialize(cfg)
        model, l2 = build_model("resnet20")
        trainer = Trainer(cfg, rt, model, l2, TINY,
                          schedule=lambda s: 0.1)
        import jax.numpy as jnp
        assert trainer.zero_wire == (jnp.bfloat16 if wire == "bf16"
                                     else jnp.float32)
        rng = np.random.default_rng(3)
        images = rng.normal(120, 50, (8, 8, 8, 3)).astype(np.float32)
        labels = rng.integers(0, 10, (8,)).astype(np.int32)
        state = trainer.init_state(jax.random.key(0), (images, labels))
        batch = rt.shard_batch((images, labels))
        out = []
        for _ in range(2):
            state, m = trainer.train_step(state, *batch)
            out.append(float(jax.device_get(m["loss"])))
        return out
    f32 = losses("fp32")
    bf16 = losses("bf16")
    np.testing.assert_allclose(bf16, f32, rtol=ZERO_WIRE_LOSS_RTOL)


# ---------------------------------------------------------------------------
# the slice layout itself (train/zero.py): a leaf is sliced by COLUMNS of a
# 2-D view of whole (8, 128) tiles a shard, so the scatter is one the TPU
# compiler keeps (tests/test_tpu_lowering.py pins that half)
# ---------------------------------------------------------------------------

# leaf-shaped at every nd here / at nd <= 2 only / never (tail-padded flat
# view): last dims that are and are not multiples of nd x 128, rows that do
# and do not come in eights, more than two dims, one dim, tiny
LEAF_SHAPES = [(16, 1024), (8, 256), (24, 128), (10, 7), (3, 3, 16, 512),
               (3, 512), (5,), (4099,), (2, 4096), (131, 4001), (1032, 512)]


def test_whole_rows_have_no_large_prime_factor():
    """What ``whole_rows`` is for: row counts in eights whose odd part
    is at most 128, at under 1.6 % (or 7 rows) of padding."""
    from dtf_tpu.train.zero import whole_rows
    for rows in (0, 1, 8, 9, 1024, 1025, 4072, 50257, 201028, 8 * 25129):
        got = whole_rows(rows)
        assert got >= rows and got % 8 == 0
        assert got - rows < max(8, rows / 64)
        odd = got
        while odd and odd % 2 == 0:
            odd //= 2
        assert odd <= 128, (rows, got)
        assert whole_rows(got) == got


def _data_mesh(devices, nd, seq=1):
    from dtf_tpu.runtime.mesh import make_mesh
    return make_mesh(devices[:nd * seq], data=nd, seq=seq)


@pytest.mark.parametrize("nd", [1, 2, 4])
@pytest.mark.parametrize("shape", LEAF_SHAPES, ids=str)
def test_slice_then_gather_is_the_identity(eight_devices, nd, shape):
    """``gather_leaf ∘ slice_leaf`` rebuilds the leaf bit for bit (the
    padding, where the view needs any, is trimmed), the nd slices
    together are the leaf's ``as_view``, and ``slice_zeros`` has the
    slice's shape."""
    import jax.numpy as jnp
    from jax import lax
    from dtf_tpu.train import zero as zero_lib
    mesh = _data_mesh(eight_devices, nd)
    p = np.random.default_rng(0).normal(size=shape).astype(np.float32)
    rows, cols = zero_lib.slice_view(shape, nd)
    assert rows == zero_lib.whole_rows(rows) and cols % (nd * 128) == 0
    assert rows * cols - p.size < 8 * cols + rows * cols // 64
    above = p.size // shape[-1]
    if len(shape) >= 2 and shape[-1] % (nd * 128) == 0 \
            and above == zero_lib.whole_rows(above):
        assert (rows, cols) == (above, shape[-1])           # leaf-shaped
    else:
        assert cols == nd * 128                             # flat, padded

    def local(p):
        s = zero_lib.slice_leaf(P(), p, nd, lax.axis_index(DATA_AXIS))
        assert s.shape == zero_lib.slice_shape(shape, nd)
        assert s.shape == zero_lib.slice_zeros(P(), p, nd).shape
        return s, zero_lib.gather_leaf(P(), s, shape, jnp.float32, nd)

    slices, back = jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=(P(),),
        out_specs=(zero_lib.zero_leaf_spec(P()), P()),
        check_vma=False))(p)
    np.testing.assert_array_equal(np.asarray(back), p)
    np.testing.assert_array_equal(np.asarray(slices),
                                  np.asarray(zero_lib.as_view(p, nd)))


@pytest.mark.parametrize("wire", ["fp32", "bf16"])
@pytest.mark.parametrize("nd", [1, 2, 4])
@pytest.mark.parametrize("shape", [(16, 1024), (10, 7), (4099,)], ids=str)
def test_scatter_is_the_mean_and_comm_off_cuts_the_same_elements(
        eight_devices, nd, shape, wire):
    """``scatter_leaf`` hands each data shard its column block of the
    MEAN of the shards' gradients, as f32 whatever the wire; the
    ``comm_off`` probe arm cuts the very same elements out of the
    shard's own gradient (times 1/nd)."""
    import jax.numpy as jnp
    from dtf_tpu.runtime.mesh import SEQ_AXIS
    from dtf_tpu.train import zero as zero_lib
    from jax import lax
    mesh = _data_mesh(eight_devices, nd)
    wire_dt = jnp.bfloat16 if wire == "bf16" else jnp.float32
    g = np.random.default_rng(1).normal(size=(nd,) + shape).astype(np.float32)

    def local(g, comm_off):
        return zero_lib.scatter_leaf(
            P(), g[0], nd, (DATA_AXIS, SEQ_AXIS), dict(mesh.shape),
            comm_off, lax.axis_index(DATA_AXIS), wire=wire_dt)

    def scattered(g, comm_off):
        out = jax.jit(jax.shard_map(
            lambda g: local(g, comm_off), mesh=mesh,
            in_specs=(P(DATA_AXIS),),
            out_specs=zero_lib.zero_leaf_spec(P()), check_vma=False))(g)
        assert out.dtype == jnp.float32
        return np.asarray(out)

    want = np.asarray(zero_lib.as_view(g.mean(0), nd))
    tol = dict(rtol=2e-2, atol=2e-2) if wire == "bf16" else dict(
        rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(scattered(g, False), want, **tol)
    # every shard holding the SAME gradient: the mean is that gradient,
    # and the stub's elements are exactly the scatter's, over nd
    same = np.broadcast_to(g[:1], g.shape)
    np.testing.assert_allclose(scattered(same, True) * nd,
                               scattered(same, False), rtol=1e-6, atol=1e-6)


class _MLP(nn.Module):
    """Leaves of every kind the layout knows: [192, 512] leaf-shaped at
    nd <= 4, [512, 24] flat and tail-padded, [24, 10] and the biases
    tiny.  At ``width`` 2048 the first kernel (1.5 MiB) rides the
    scatter's ring; everything else stays under the threshold."""
    width: int = 512

    @nn.compact
    def __call__(self, x, train=False):
        x = x.reshape((x.shape[0], -1))
        x = nn.relu(nn.Dense(self.width)(x))
        x = nn.relu(nn.Dense(24)(x))
        return nn.Dense(10)(x)


def _mlp_steps(stage, nd, accum, wire, steps=3, width=512, lowered=None):
    """``lowered``: a list that receives the step's lowered text."""
    cfg = _cfg("", stage, steps, checkpoint_steps=0, skip_checkpoint=True)
    cfg = cfg.replace(num_devices=nd, grad_accum_steps=accum,
                      zero_wire=wire if stage >= 2 else "fp32")
    rt = initialize(cfg)
    trainer = Trainer(cfg, rt, _MLP(width), 1e-4, TINY,
                      schedule=lambda s: 0.05)
    rng = np.random.default_rng(2)
    images = rng.normal(0, 1, (8, 8, 8, 3)).astype(np.float32)
    labels = rng.integers(0, 10, (8,)).astype(np.int32)
    state = trainer.init_state(jax.random.key(0), (images, labels))
    batch = rt.shard_batch((images, labels))
    if lowered is not None:
        lowered.append(trainer.train_step.lower(state, *batch).as_text())
    losses = []
    for _ in range(steps):
        state, m = trainer.train_step(state, *batch)
        losses.append(float(jax.device_get(m["loss"])))
    params = jax.device_get(trainer.canonical_state(state).params)
    return losses, dict(jax.tree_util.tree_leaves_with_path(params))


@pytest.mark.parametrize("nd,accum,wire", [
    (1, 1, "fp32"), (2, 2, "fp32"), (4, 1, "fp32"), (4, 2, "fp32"),
    (2, 1, "bf16"), (4, 2, "bf16")])
def test_stages_match_plain_dp_on_every_leaf_kind(eight_devices, nd, accum,
                                                  wire):
    """Stage 0 ≡ 1 ≡ 2 ≡ 3 after three steps — losses and every
    parameter — over leaves that are and are not multiples of nd x 128
    (tail padding trimmed after the gather), tiny ones, with and
    without the sliced accumulation carry; the bf16 wire (stages 2, 3)
    inside its documented tolerance."""
    ref_losses, ref = _mlp_steps(0, nd, accum, "fp32")
    assert ref_losses[-1] < ref_losses[0]
    for stage in ((2, 3) if wire == "bf16" else (1, 2, 3)):
        losses, params = _mlp_steps(stage, nd, accum, wire)
        if wire == "bf16":
            np.testing.assert_allclose(losses, ref_losses,
                                       rtol=ZERO_WIRE_LOSS_RTOL)
            continue
        np.testing.assert_allclose(losses, ref_losses, rtol=1e-5)
        for path, r in ref.items():
            np.testing.assert_allclose(
                np.asarray(params[path]), np.asarray(r), atol=2e-6,
                rtol=1e-5, err_msg=f"stage {stage} "
                                   f"{jax.tree_util.keystr(path)}")


# ---------------------------------------------------------------------------
# the gradient scatter's ring (zero._ring_scatter): a leaf of RING_MIN_BYTES
# or more reaches its owner by hops of ppermute and local adds
# ---------------------------------------------------------------------------

# (shape, wire, seq, ring order | None = the mesh's own, rides the ring)
RING_CASES = {
    # [512, 1024]: the leaf's own last dimension at nd <= 8, 2 MiB
    "leaf_shaped": ((512, 1024), "fp32", 1, None, True),
    # flat and tail-padded: a shard's block is ONE lane tile, cut by rows
    "flat_128_columns": ((300_000,), "fp32", 1, None, True),
    # 64 KB: under the threshold, still one psum_scatter
    "under_threshold": ((16, 1024), "fp32", 1, None, False),
    # hops over 'data' under a 'seq' axis, then the pmean over 'seq'
    "seq_axis": ((512, 1024), "fp32", 2, None, True),
    # the hops carry and sum bf16, as the collective does
    "wire_bf16": ((1024, 1024), "bf16", 1, None, True),
    # an order that is not the axis's: what ring_order gives a 2 x 2 host
    "ring_0132": ((512, 1024), "fp32", 1, "twisted", True),
}


@pytest.mark.parametrize("nd", [2, 4, 8])
@pytest.mark.parametrize("case", RING_CASES)
def test_ring_scatter_is_psum_scatter_on_the_same_owner(eight_devices, case,
                                                        nd):
    """The ring's ``scatter_leaf`` equals ``psum_scatter``'s to the
    re-association of an f32 sum of nd terms, shard i holds
    ``own_columns(view, nd, i)`` of the mean, and the size rule alone
    decides which of the two a leaf compiles to."""
    import jax.numpy as jnp
    from dtf_tpu.runtime.mesh import SEQ_AXIS
    from dtf_tpu.train import zero as zero_lib
    from jax import lax
    shape, wire, seq, order, rides = RING_CASES[case]
    if nd * seq > len(eight_devices):
        nd = len(eight_devices) // seq      # 'seq' takes half the devices
    mesh = _data_mesh(eight_devices, nd, seq)
    ring = zero_lib.ring_order(mesh)
    assert ring == tuple(range(nd))         # CPU devices have no coords
    if order == "twisted":
        # pairs swapped from the second on: 0-1-3-2 at nd 4
        ring = tuple(i ^ (i >> 1 & 1) for i in range(nd))
    wire_dt = jnp.bfloat16 if wire == "bf16" else jnp.float32
    view_sds = jax.ShapeDtypeStruct(zero_lib.slice_view(shape, nd), wire_dt)
    assert (zero_lib.ring_halves(view_sds, nd) is not None) == rides
    if case == "flat_128_columns":
        assert view_sds.shape[1] == nd * 128
        assert zero_lib.ring_halves(view_sds, nd)[0] == 0   # cut by rows
    g = np.random.default_rng(3).normal(
        size=(nd, seq) + shape).astype(np.float32)

    def local(g, ring):
        idx = lax.axis_index(DATA_AXIS)
        return zero_lib.scatter_leaf(
            P(), g[0, 0], nd, (DATA_AXIS, SEQ_AXIS), dict(mesh.shape),
            False, idx, wire=wire_dt, ring=zero_lib.ring_hops(ring, idx))

    def scattered(ring):
        fn = jax.jit(jax.shard_map(
            lambda g: local(g, ring), mesh=mesh,
            in_specs=(P(DATA_AXIS, SEQ_AXIS),),
            out_specs=zero_lib.zero_leaf_spec(P()), check_vma=False))
        hops = fn.lower(g).as_text().count("collective_permute")
        return np.asarray(fn(g)), hops

    got, hops = scattered(ring)
    native, none = scattered(None)
    assert none == 0
    assert hops == (2 * (nd - 1) if rides else 0)
    want = np.asarray(zero_lib.as_view(g.mean((0, 1)), nd))
    tol = dict(rtol=2e-2, atol=2e-2) if wire == "bf16" else dict(
        rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(got, native, **tol)
    np.testing.assert_allclose(got, want, **tol)


@pytest.mark.parametrize("nd,stage,accum", [(4, 2, 2), (2, 3, 2), (4, 1, 1)])
def test_ring_inside_the_step_matches_plain_dp(eight_devices, nd, stage,
                                               accum):
    """The whole step with a leaf over the threshold: the hops sit in
    the stage-2/3 accumulation scan's body (or, stage 1, after the
    backward), and three steps end where plain data parallelism's do."""
    texts = []
    ref_losses, ref = _mlp_steps(0, nd, accum, "fp32", width=2048,
                                 lowered=texts)
    losses, params = _mlp_steps(stage, nd, accum, "fp32", width=2048,
                                lowered=texts)
    ref_text, text = texts
    assert "collective_permute" not in ref_text
    # one leaf rides: 2 x (nd - 1) hops at its one call site
    assert text.count("collective_permute") == 2 * (nd - 1)
    if accum > 1:
        assert "collective_permute" in text[text.index("stablehlo.while"):]
    np.testing.assert_allclose(losses, ref_losses, rtol=1e-5)
    for path, r in ref.items():
        np.testing.assert_allclose(
            np.asarray(params[path]), np.asarray(r), atol=2e-6, rtol=1e-5,
            err_msg=jax.tree_util.keystr(path))


def test_ring_order_follows_the_chips_coords():
    """A 2 x 2 host's data axis is the cycle 0-1-3-2 (every hop one ICI
    link), CPU devices ring in axis order, and an axis of one or one
    that leaves its slice has no ring."""
    import types
    from jax.sharding import Mesh
    from dtf_tpu.runtime.mesh import MESH_AXES
    from dtf_tpu.train import zero as zero_lib

    def mesh_of(chips, **axes):
        shape = [axes.get(a, 1) for a in MESH_AXES]
        arr = np.empty(len(chips), object)
        arr[:] = chips
        return types.SimpleNamespace(devices=arr.reshape(shape),
                                     axis_names=MESH_AXES)

    def chip(x, y, slice_index=0):
        return types.SimpleNamespace(coords=(x, y, 0),
                                     slice_index=slice_index)

    host = [chip(0, 0), chip(1, 0), chip(0, 1), chip(1, 1)]
    assert zero_lib.ring_order(mesh_of(host, data=4)) == (0, 1, 3, 2)
    # data 2 x model 2: the data axis's first column is chips 0 and 2
    assert zero_lib.ring_order(mesh_of(host, data=2, model=2)) == (0, 1)
    assert zero_lib.ring_order(mesh_of(host[:1], data=1)) is None
    row = [chip(x, 0) for x in range(4)]        # no link closes a line
    assert zero_lib.ring_order(mesh_of(row, data=4)) == (0, 1, 2, 3)
    grid = [chip(x, y) for y in range(4) for x in range(4)]
    ring = zero_lib.ring_order(mesh_of(grid, data=16))
    assert sorted(ring) == list(range(16))
    for a, b in zip(ring, ring[1:] + ring[:1]):
        assert sum(abs(p - q) for p, q in
                   zip(grid[a].coords, grid[b].coords)) == 1
    across = [chip(0, 0), chip(1, 0), chip(0, 0, 1), chip(1, 0, 1)]
    assert zero_lib.ring_order(mesh_of(across, data=4)) is None
    cpu = Mesh(np.array(jax.devices()[:4]).reshape(4, 1, 1), MESH_AXES)
    assert zero_lib.ring_order(cpu) == (0, 1, 2, 3)
