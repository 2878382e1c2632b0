"""LM benchmark: transformer training throughput + kernel/pipeline micro-numbers.

Every LM performance number quoted in README.md / docs/DESIGN.md is
produced by this script, so the driver (and anyone else) can re-measure
and regression-track them.  Prints ONE JSON line per invocation,
bench.py contract: {"metric", "value", "unit", "vs_baseline", ...}.
The reference workload is vision-only (SURVEY §5.7) so there is no
reference LM baseline; ``vs_baseline`` tracks round-over-round against
the r2 recorded number instead.

Variants:
  python bench_lm.py                  # headline: GPT-2-small-class train step
  python bench_lm.py --remat          # same with jax.checkpoint per block
  python bench_lm.py --variant flash  # Pallas kernel micro: fwd ms, bwd/fwd
  python bench_lm.py --variant gpipe  # GPipe M-scaling on the 8-dev CPU mesh

Headline model: 12×768, 6 heads × d_head 128 (the TPU-native layout —
identical parameter shapes to GPT-2-small's 12 × 64; pass --heads 12
for that comparison number), d_ff 3072, seq 2048, vocab 32k (≈137 M
params), bf16 activations, AdamW, flash-attention Pallas kernels — the
long-context flagship (docs/DESIGN.md).  MFU is XLA's own flop count
for the compiled step over the chip's peak bf16 FLOP/s (same
convention as bench.py); `mfu_6n` is the classic 6·N·tokens/s estimate
for cross-checking; `mfu_model` is the honest one — 6·N matmul flops
plus the S²-dominant causal-attention flops XLA's count can't see
(the Pallas kernels), constant ~56% across context lengths.

mfu_model's attention convention, stated explicitly: fwd + 2.5×fwd for
the backward = 3.5× total.  The extra 0.5× beyond the recompute-free
3.0× counts ONE softmax/S recompute as model flops (flash backward
must rebuild S from Q·K before it can form dV/dQ/dK — the recompute is
algorithmically forced by not materializing S, not an implementation
choice).  Since r5's fused single-pass backward (the default where its
VMEM gate allows, seq ≤ 4096 at d 128 — ops/flash_attention.py), the
hardware performs exactly that one recompute, so the convention
matches the machine at the flagship shape; the split kernels used
beyond the gate recompute S and dP once in EACH of dq and dk/dv, and
that excess is NOT counted — it shows up as lost MFU, which is the
point.  A strict recompute-free convention would use 3.0×: to convert,
rescale ONLY the attention term (attn_flops · 3.0/3.5) and leave the
6·N matmul term alone — it is convention-independent.
Cross-seq-length comparisons are valid either way.

6·N uses `matmul_params` = N minus the embedding + position tables
(their lookups are gathers, not matmuls).  LayerNorm scales/biases and
matmul biases stay in the count; at these dims they are <0.1% of N and
intentionally ignored rather than itemized.
"""

import json
import os
import sys

# The gpipe variant measures a relative pipeline schedule, which needs
# >=2 devices — force the 8-virtual-device CPU mesh before jax import.
if "--variant" in sys.argv and any(
        v in sys.argv for v in ("gpipe", "gpipe_mem", "zero_mem")):
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=8")

import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import is_oom, peak_tflops  # shared helpers

# r2 recorded numbers (README.md) — round-over-round baselines.
# (the r2 flash bwd/fwd=0.70 ratio was retired with the r4 protocol:
# it was a dispatch-dominated artifact, incomparable to loop-differenced
# timings)
R2_TOKENS_PER_SEC = 99_000.0
R2_REMAT_TOKENS_PER_SEC = 81_000.0
R2_GPIPE_SPEEDUP = 1.62

SEQ = 2048
VOCAB = 32_768
# flagship model dims — build_trainer and the mfu_model formula derive
# from these
D_MODEL = 768
LAYERS = 12
D_FF = 3072


def _sync(x):
    return float(jax.device_get(x))


# TPU-native head layout: 6 heads × d_head 128 — identical parameter
# shapes/count to GPT-2-small's 12 × 64 (768 = 12·64 = 6·128), but the
# MXU runs 128-wide attention tiles at full rate where 64-wide tiles
# run at half rate.  Measured +33% end-to-end tokens/s at equal
# params; pass --heads 12 for the GPT-2-layout comparison number.
DEFAULT_HEADS = 6


def build_trainer(batch: int, remat: bool, seq: int = SEQ,
                  heads: int = DEFAULT_HEADS, report_acc: bool = False,
                  remat_policy: str | None = None,
                  optimizer_sharding: bool = False):
    import dataclasses

    from dtf_tpu.config import Config
    from dtf_tpu.data.base import LM
    from dtf_tpu.models import build_model
    from dtf_tpu.runtime import initialize
    from dtf_tpu.train import Trainer

    # benchmark purity default: the reference's own
    # --report_accuracy_metrics false (common.py:277-278) — the
    # in-step argmax otherwise reads the full [B·S, 32k] f32 logits
    # every step (measured 3-7 ms of a 246 ms step).  Loss is still
    # computed and synced.
    cfg = Config(model="transformer", dataset="lm", dtype="bf16",
                 batch_size=batch, distribution_strategy="tpu",
                 optimizer="adamw", skip_eval=True, train_steps=1,
                 remat=remat, report_accuracy_metrics=report_acc,
                 remat_policy=remat_policy,
                 optimizer_sharding=optimizer_sharding)
    rt = initialize(cfg)
    rt.shard_seq = True
    model, _ = build_model("transformer", num_classes=VOCAB,
                           dtype=jnp.bfloat16, num_layers=LAYERS,
                           d_model=D_MODEL, num_heads=heads, d_ff=D_FF,
                           max_seq_len=seq,
                           remat=remat, remat_policy=remat_policy)
    trainer = Trainer(cfg, rt, model, 0.0,
                      dataclasses.replace(LM, seq_len=seq))
    return trainer, rt


def _batch_cands(seq: int):
    """Per-chip batch candidates, largest first, scaling down with
    sequence length — shared by train_bench (OOM fallback) and
    remat_mem so the memory table measures the same programs the
    throughput numbers time.

    16 at seq 2048 is measured-optimal, not just memory-safe: r5
    probed 24 (132.8k tokens/s) and 32 (125.0k) under the fused
    backward — both compile and run but LOSE to 16's ~147k (the
    larger working set degrades XLA's scheduling well before OOM,
    the same shape as the ResNet batch-512 negative)."""
    return list(dict.fromkeys(
        max(1, m * SEQ // seq) for m in (16, 8, 4)))


def train_bench(remat: bool, warmup: int = 3, iters: int = 10,
                seq: int = SEQ, heads: int = DEFAULT_HEADS,
                remat_policy: str | None = None):
    n_chips = len(jax.devices())
    err = None
    for per_chip in _batch_cands(seq):
        batch = per_chip * n_chips
        try:
            trainer, rt = build_trainer(batch, remat, seq, heads,
                                        remat_policy=remat_policy)
            tokens, labels = _flagship_tokens(batch, seq)
            state = trainer.init_state(jax.random.key(0), (tokens, labels))
            sharded = rt.shard_batch((tokens, labels))

            step_flops = None
            try:
                ca = trainer.train_step.lower(
                    state, *sharded).compile().cost_analysis()
                ca = ca[0] if isinstance(ca, (list, tuple)) else ca
                step_flops = float(ca.get("flops", 0.0)) or None
            except Exception:
                pass
            n_params = sum(x.size for x in
                           jax.tree_util.tree_leaves(state.params))

            for _ in range(warmup):
                state, metrics = trainer.train_step(state, *sharded)
            _sync(metrics["loss"])
            # sync-cancelling windows + spread
            # (bench.windowed_step_seconds documents the protocol)
            from bench import timed_train_steps
            step_s, step_min_s, step_max_s, _, state = timed_train_steps(
                trainer.train_step, state, sharded, windows=3,
                short=3, long=13)
            rates = [batch * seq / s / n_chips
                     for s in (step_max_s, step_s, step_min_s)]
            per_chip_tps = rates[1]
            peak = peak_tflops(jax.devices()[0])
            mfu = ((step_flops / step_s) / (peak * 1e12)
                   if step_flops and peak else None)
            mfu_6n = ((6.0 * n_params * per_chip_tps) / (peak * 1e12)
                      if peak else None)
            # true model flops: XLA's count excludes the Pallas
            # attention kernels, and 6N ignores attention entirely —
            # at long sequence the S² attention term DOMINATES (causal
            # halves the live blocks, backward does 2.5x forward — the
            # 3.5x total counts ONE forced softmax recompute as model
            # flops; see module docstring for the convention).
            # heads·d_head = d_model, so the term is
            # head-layout-independent.
            # matmul_params: N minus the two lookup tables; LN/bias
            # params (<0.1% of N) intentionally stay in the count.
            matmul_params = n_params - (VOCAB + seq) * D_MODEL
            attn_flops = (LAYERS * 4 * batch * seq * seq * D_MODEL
                          / 2 * 3.5)
            model_flops = 6.0 * matmul_params * batch * seq + attn_flops
            mfu_model = ((model_flops / n_chips / step_s) / (peak * 1e12)
                         if peak else None)
            return dict(per_chip_tps=per_chip_tps,
                        per_chip_tps_min=rates[0],
                        per_chip_tps_max=rates[2],
                        windows=3, step_ms=step_s * 1e3,
                        mfu=mfu, mfu_6n=mfu_6n, mfu_model=mfu_model,
                        n_params=n_params,
                        per_chip_batch=per_chip, n_chips=n_chips,
                        seq=seq)
        except Exception as e:
            if not is_oom(e):
                raise
            err = e
    raise err


def flash_bench(seq: int = 8192, fused=None):
    """Kernel micro: Pallas flash fwd vs bwd wall time, [2, seq, 8, 128]
    bf16 causal — the shape quoted in ops/flash_attention.py.  Timed
    with _loop_time (a single-dispatch window carries the host sync
    and its jitter; one recorded run produced bwd = 0.19x fwd from
    exactly that).  ``fused`` forces the
    single-pass backward on/off (None = the production auto gate)."""
    from dtf_tpu.ops.flash_attention import flash_attention

    rng = jax.random.key(0)
    qk, kk, vk = jax.random.split(rng, 3)
    shape = (2, seq, 8, 128)
    q = jax.random.normal(qk, shape, jnp.bfloat16)
    k = jax.random.normal(kk, shape, jnp.bfloat16)
    v = jax.random.normal(vk, shape, jnp.bfloat16)

    fwd_ms, fwdbwd_ms = _flash_times(q, k, v, n2_fwd=72, n2_fb=40,
                                     fused=fused)
    bwd_ms = max(fwdbwd_ms - fwd_ms, 0.0)
    return dict(fwd_ms=fwd_ms, bwd_ms=bwd_ms,
                bwd_over_fwd=bwd_ms / fwd_ms if fwd_ms > 0 else None,
                seq=seq, shape=list(shape))


def _loop_time(body, init, n1: int = 16, n2: int = 144, reps: int = 5):
    """Per-op seconds via a compiled fori_loop at two lengths:
    (t(n2) - t(n1)) / (n2 - n1) cancels the constant dispatch + sync
    floor, and min-over-reps suppresses its jitter (both make
    single-dispatch micro-timings unusable — flash_bench's docstring
    records the 0.19x-fwd artifact one produced).
    """
    from jax import lax
    ts = {}
    for n in (n1, n2):
        f = jax.jit(lambda x: lax.fori_loop(0, n, body, x))
        f(init)
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            out = f(init)
            jax.device_get(jax.tree_util.tree_leaves(out)[0].ravel()[0])
            best = min(best, time.perf_counter() - t0)
        ts[n] = best
    return (ts[n2] - ts[n1]) / (n2 - n1)


def _flash_times(q, k, v, n2_fwd: int = 72, n2_fb: int = 40, fused=None):
    """(fwd_ms, fwd+bwd_ms) of the causal flash kernels at q/k/v's
    shapes, loop-differenced; the fwd value is clamped positive (a
    jitter-inflated short window could otherwise difference ≤ 0).
    Shared by flash_bench and dhead_bench so both time the same
    chaining construction."""
    from dtf_tpu.ops.flash_attention import flash_attention

    fwd = _loop_time(
        lambda i, o: flash_attention(o, k, v, causal=True), q,
        n1=8, n2=n2_fwd)

    def fb(i, qq):
        g = jax.grad(lambda q, k, v: jnp.sum(
            flash_attention(q, k, v, causal=True,
                            fused_bwd=fused).astype(jnp.float32)),
            argnums=(0, 1, 2))(qq, k, v)
        return (g[0] + g[1] + g[2]).astype(jnp.bfloat16)

    fwdbwd = _loop_time(fb, q, n1=8, n2=n2_fb)
    return max(fwd, 1e-9) * 1e3, max(fwdbwd, 1e-9) * 1e3


def dhead_bench(batch: int = 16, seq: int = SEQ):
    """The d_head-64 penalty, measured at the flagship step shapes —
    and WHY it is intrinsic to the MXU, not a kernel deficiency.

    Two facts this prints (TPU v5 lite, bf16):
      1. matmul passes bill ceil(d/128) MXU passes per 128x128 output
         tile, and a 64-deep pass still costs ~0.6-0.75 of a 128-deep
         one (mm64_ms vs mm128_ms: [8192,d]x[d,8192]).  So two d=64
         score/PV matmuls always cost >= one d=128 matmul of equal
         model FLOPs, and any "pack two 64-heads per 128-lane tile"
         construction (block-diagonal operands, sum/difference tricks)
         doubles output tiles or contraction passes and cancels out —
         output_tiles x ceil(contraction/128) is conserved.
      2. 12x64 attention also computes 2x the softmax score elements
         of 6x128 (12*S^2 vs 6*S^2) — the VPU work doubles with head
         count no matter how heads are packed.
    Hence flash f+b at [16,2048,12,64] runs ~2.1x [16,2048,6,128]
    (fwd64_ms etc. below) at identical parameter count, and the
    TPU-native fix is the 6x128 layout itself (models/registry.py
    transformer_tpu — the flagship default), not a kernel change.
    """
    key = jax.random.key(0)
    out = {"metric": "dhead_attention_penalty", "unit": "ms",
           "batch": batch, "seq": seq}
    for h, d in ((6, 128), (12, 64)):
        q = jax.random.normal(key, (batch, seq, h, d), jnp.bfloat16)
        k = jax.random.normal(key, (batch, seq, h, d), jnp.bfloat16)
        v = jax.random.normal(key, (batch, seq, h, d), jnp.bfloat16)
        fwd_ms, fwdbwd_ms = _flash_times(q, k, v, n2_fwd=144, n2_fb=144)
        out[f"fwd{d}_ms"] = round(fwd_ms, 3)
        out[f"fwdbwd{d}_ms"] = round(fwdbwd_ms, 3)
    out["fwdbwd_penalty_x"] = round(out["fwdbwd64_ms"]
                                    / out["fwdbwd128_ms"], 2)
    n = 8192
    for d in (64, 128):
        a = jax.random.normal(key, (n, d), jnp.bfloat16)
        b = jax.random.normal(key, (d, n), jnp.bfloat16)

        def mm(i, a):
            s = jnp.dot(a, b, preferred_element_type=jnp.float32)
            # consume every element so XLA cannot slice away columns
            return a + jnp.sum(s, axis=1)[:, None].astype(jnp.bfloat16) * 1e-9
        # ~0.1 ms/op: needs a much wider loop span than the ~ms flash
        # timings for the loop-differenced subtraction to resolve it
        out[f"mm{d}_ms"] = round(
            _loop_time(mm, a, n1=64, n2=1088) * 1e3, 4)
    out["mm_depth64_cost_of_128"] = round(out["mm64_ms"] / out["mm128_ms"], 2)
    return out


def _gpipe_trainer(pp: int, m: int, interleave: int, remat: bool,
                   mesh, batch: int, seq: int, vocab: int):
    import functools

    from dtf_tpu.config import Config
    from dtf_tpu.data.base import DatasetSpec
    from dtf_tpu.models.pipeline_lm import (PipelinedTransformerLM,
                                            pipeline_param_partition_specs)
    from dtf_tpu.runtime.mesh import MODEL_AXIS, MeshRuntime
    from dtf_tpu.train import Trainer

    spec = DatasetSpec("lm", 0, 0, vocab, 1024, 128, one_hot=False,
                       seq_len=seq)
    rt = MeshRuntime(mesh=mesh, strategy="mirrored", shard_seq=True)
    cfg = Config(model="pipeline_transformer", dataset="lm",
                 batch_size=batch, train_steps=1, skip_eval=True,
                 optimizer="adamw")
    model = PipelinedTransformerLM(
        vocab_size=vocab, num_layers=2 * pp, d_model=64, num_heads=4,
        d_ff=256, max_seq_len=seq, num_microbatches=m,
        pipe_axis=MODEL_AXIS, interleave=interleave, remat=remat)
    trainer = Trainer(cfg, rt, model, 0.0, spec,
                      param_spec_fn=functools.partial(
                          pipeline_param_partition_specs,
                          pipe_axis=MODEL_AXIS))
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, vocab, (batch, seq)).astype(np.int32)
    labels = np.roll(tokens, -1, axis=1)
    state = trainer.init_state(jax.random.key(0), (tokens, labels))
    sharded = rt.shard_batch((tokens, labels))
    return trainer, state, sharded


def _gpipe_mesh(pp: int):
    from dtf_tpu.runtime.mesh import MESH_AXES
    from jax.sharding import Mesh
    devices = jax.devices()
    assert len(devices) >= pp, f"need {pp} devices, have {len(devices)}"
    dp = len(devices) // pp
    mesh = Mesh(np.array(devices[:dp * pp]).reshape(dp, 1, pp), MESH_AXES)
    return mesh, dp


def gpipe_bench(pp: int = 4, warmup: int = 2, iters: int = 5):
    """Relative schedule measurement on the virtual CPU mesh: step time
    at M = pp (worst bubble) vs the auto-scaled M = 4·pp, plus the
    interleaved (two-virtual-stages-per-device) schedule at both M.
    Absolute CPU times are meaningless; the ratios are the claims."""
    mesh, dp = _gpipe_mesh(pp)
    seq, vocab, batch = 128, 512, dp * 16

    def step_time(m, interleave=1):
        trainer, state, sharded = _gpipe_trainer(
            pp, m, interleave, False, mesh, batch, seq, vocab)
        for _ in range(warmup):
            state, metrics = trainer.train_step(state, *sharded)
        _sync(metrics["loss"])
        t0 = time.perf_counter()
        for _ in range(iters):
            state, metrics = trainer.train_step(state, *sharded)
        _sync(metrics["loss"])
        return (time.perf_counter() - t0) / iters * 1e3

    worst = step_time(pp)        # bubble (pp-1)/(2pp-1) = 3/7 at pp=4
    best = step_time(4 * pp)     # bubble (pp-1)/(5pp-1) = 3/19 at pp=4
    il_low = step_time(pp, interleave=2)    # (pp-1)/(3pp-1) in half-ticks
    il_high = step_time(4 * pp, interleave=2)
    return dict(pp=pp, m_low=pp, m_high=4 * pp,
                step_ms_m_low=round(worst, 1),
                step_ms_m_high=round(best, 1),
                step_ms_m_low_interleaved=round(il_low, 1),
                step_ms_m_high_interleaved=round(il_high, 1),
                speedup=worst / best,
                interleave_speedup_at_m_low=worst / il_low,
                interleave_speedup_at_m_high=best / il_high)


def gpipe_mem(pp: int = 4):
    """Peak-memory table: XLA's own buffer assignment (temp + args +
    output − donated-state alias, see _buffer_sizes) for the compiled
    train step, M x remat x interleave.  The GPipe memory story the
    docs quote comes from this."""
    mesh, dp = _gpipe_mesh(pp)
    seq, vocab, batch = 128, 512, dp * 16
    rows = []
    for m in (pp, 2 * pp, 4 * pp):
        for remat in (False, True):
            for il in (1, 2):
                trainer, state, sharded = _gpipe_trainer(
                    pp, m, il, remat, mesh, batch, seq, vocab)
                row = dict(m=m, remat=remat, interleave=il)
                try:
                    compiled = trainer.train_step.lower(
                        state, *sharded).compile()
                    temp, total = _buffer_sizes(compiled)
                    row["temp_mb"] = round(temp / 2**20, 1)
                    row["total_mb"] = round(total / 2**20, 1)
                except Exception as e:  # backend without memory stats
                    row["error"] = str(e)[:80]
                rows.append(row)
    return dict(pp=pp, batch=batch, seq=seq, rows=rows)


def _buffer_sizes(compiled):
    """(temp_bytes, total_bytes) from a compiled step's XLA buffer
    assignment — the one unwrap/sum shared by every memory table.

    The train step donates its state (jit donate_argnums), and a
    donated buffer is reported in FULL under both argument and output
    sizes with the overlap in alias_size_in_bytes — subtract it or the
    table overstates HBM need by the whole train-state size."""
    ma = compiled.memory_analysis()
    ma = ma[0] if isinstance(ma, (list, tuple)) else ma
    total = (ma.temp_size_in_bytes + ma.argument_size_in_bytes
             + ma.output_size_in_bytes
             - getattr(ma, "alias_size_in_bytes", 0))
    return ma.temp_size_in_bytes, total


def _flagship_tokens(batch: int, seq: int):
    """The one token/label recipe every flagship-step bench shares —
    the memory table must measure the same program the throughput
    numbers time."""
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, VOCAB, (batch, seq)).astype(np.int32)
    labels = np.roll(tokens, -1, axis=1)
    return tokens, labels


def _mem_row(seq: int, build_fn):
    """Candidate-fallback compile-and-measure shared by remat_mem and
    zero_mem: try per-chip batch candidates largest-first against
    ``build_fn(batch) -> (trainer, rt)``, compiling from abstract avals
    (no chip allocation), and return (row, n_params) — the row carries
    temp_gb/total_gb or the error ("OOM" falls through to the next
    candidate; anything else stops)."""
    row, n_params = {}, None
    for per_chip in _batch_cands(seq):
        batch = per_chip * len(jax.devices())
        row = dict(per_chip_batch=per_chip)
        try:
            trainer, rt = build_fn(batch)
            tokens, labels = _flagship_tokens(batch, seq)
            state_avals = jax.eval_shape(
                trainer.init_state, jax.random.key(0), (tokens, labels))
            n_params = sum(
                int(np.prod(a.shape)) for a in
                jax.tree_util.tree_leaves(state_avals.params))
            batch_avals = tuple(jax.ShapeDtypeStruct(a.shape, a.dtype)
                                for a in (tokens, labels))
            compiled = trainer.train_step.lower(
                state_avals, *batch_avals).compile()
            temp, total = _buffer_sizes(compiled)
            row["temp_gb"] = round(temp / 2**30, 2)
            row["total_gb"] = round(total / 2**30, 2)
            break
        except Exception as e:
            err = "OOM" if is_oom(e) else str(e)[:80]
            row["error"] = err
            if err != "OOM":
                break
    return row, n_params


def remat_mem():
    """Peak-memory table for the remat frontier: XLA's buffer
    assignment (temp + args + output − donated-state alias, see
    _buffer_sizes) of the compiled flagship step at none / dots / full
    remat across the seq lengths the README quotes.  This table is what
    falsified the r2/r3 belief that long context needs remat: the
    no-remat step fits through seq 32768 (14.9 GB total on a 16 GB
    v5e) and runs faster than either remat flavor at every length.

    Compiles from abstract avals (jax.eval_shape of init_state) — no
    state is ever allocated on the chip, so marginal configs see the
    true buffer requirement, not one inflated by a previous config's
    still-referenced arrays."""
    rows = []
    for seq in (SEQ, 16384, 32768):
        # the throughput bench falls back to smaller candidates on OOM
        # — _mem_row mirrors it, recording the candidate compiled at
        for policy in ("none", "dots", "full"):
            row, _ = _mem_row(seq, lambda batch: build_trainer(
                batch, policy == "full", seq, DEFAULT_HEADS,
                remat_policy="dots" if policy == "dots" else None))
            rows.append(dict(seq=seq, policy=policy, **row))
    return dict(rows=rows)


def zero_mem():
    """ZeRO-2 decision table (VERDICT r4 #8): does gradient sharding
    buy real headroom at the flagship recipe, or does ZeRO-1 suffice?

    Measured per-device XLA buffer totals on the dp-device mesh with
    ZeRO-1 off/on, plus the ANALYTIC upper bound of what ZeRO-2 could
    further save: sharding the f32 gradient tree leaves at most
    (dp-1)/dp · 4·N bytes to reclaim (the local backward still has to
    materialize full-size local grads before any reduce-scatter — in
    an SPMD formulation ZeRO-2 beyond ZeRO-1 is only the freeing of
    the full grad buffers before peak).  The verdict rule: if the
    next-larger (batch, seq) candidate's measured memory need exceeds
    the current fit by MORE than that bound, ZeRO-2 provably cannot
    unlock it and ZeRO-1 suffices; if the gap is within the bound,
    ZeRO-2 is worth building.  Run on the virtual CPU mesh:
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8
    """
    dp = len(jax.devices())
    rows = []
    n_params = None
    # seq 32768 omitted: the CPU-backend compile of the 12-layer
    # blockwise-attention program at 32k is minutes-long on the 1-core
    # box, and remat_mem's on-chip row already pins its total (14.9 GB)
    for seq in (SEQ, 16384):
        for zero1 in (False, True):
            row, n = _mem_row(seq, lambda batch: build_trainer(
                batch, False, seq, DEFAULT_HEADS,
                optimizer_sharding=zero1))
            n_params = n_params or n
            rows.append(dict(seq=seq, zero1=zero1, **row))
    # no fabricated zeros: if nothing compiled, the decision number is
    # null, not "ZeRO-2 saves 0.0 GB"
    grad_f32_gb = (4.0 * n_params / 2**30 if n_params else None)
    return dict(dp=dp, n_params=n_params, rows=rows,
                grad_tree_f32_gb=(round(grad_f32_gb, 3)
                                  if grad_f32_gb else None),
                zero2_max_additional_saving_gb=(
                    round(grad_f32_gb * (dp - 1) / dp, 3)
                    if grad_f32_gb else None),
                note="zero2 bound = (dp-1)/dp of the f32 grad tree; "
                     "compare against the total_gb gap between "
                     "adjacent batch/seq candidates")


def main():
    variant = None
    if "--variant" in sys.argv:
        variant = sys.argv[sys.argv.index("--variant") + 1]
    remat = "--remat" in sys.argv
    usage = ("usage: bench_lm.py [--seq N] [--heads N] [--remat] "
             "[--remat_policy dots] [--fused 0|1] "
             "[--variant flash|gpipe|gpipe_mem|remat_mem|zero_mem|dhead]\n"
             "  --fused 1 forces the single-pass backward past its VMEM "
             "gate; pair it with --seq <= 4096 (the [Sq,128] f32 dq "
             "scratch must fit — flash defaults to seq 8192)")
    remat_policy = None
    if "--remat_policy" in sys.argv:
        i = sys.argv.index("--remat_policy")
        if i + 1 >= len(sys.argv) or sys.argv[i + 1] != "dots":
            sys.exit(usage)
        remat_policy = sys.argv[i + 1]

    def int_flag(name, default):
        if name not in sys.argv:
            return default
        i = sys.argv.index(name)
        if i + 1 >= len(sys.argv):
            sys.exit(usage)
        return int(sys.argv[i + 1])

    seq = int_flag("--seq", SEQ)
    heads = int_flag("--heads", DEFAULT_HEADS)

    if variant == "flash":
        fused = int_flag("--fused", None)
        if fused is not None:
            fused = bool(fused)
        r = flash_bench(seq=seq if "--seq" in sys.argv else 8192,
                        fused=fused)
        print(json.dumps({
            "metric": "flash_attention_bwd_over_fwd",
            "value": round(r["bwd_over_fwd"], 3),
            "unit": "ratio",
            # r2/r3 recorded 0.70x under the dispatch-dominated
            # protocol (both fwd and bwd swamped by the host
            # sync); the r4 sync-cancelled ratio ~3x is the
            # physical one (bwd does 2.5x the FLOPs) — incomparable,
            # so no vs_baseline
            "vs_baseline": None,
            "protocol": "loop-differenced (r4)",
            "fwd_ms": round(r["fwd_ms"], 2), "bwd_ms": round(r["bwd_ms"], 2),
            # which backward formulation ran: "auto" = the production
            # VMEM gate decided; else the forced arm — recorded so A/B
            # JSON lines are attributable without shell history
            "fused_bwd": "auto" if fused is None else fused,
            "seq": r["seq"], "shape": r["shape"],
            "device_kind": jax.devices()[0].device_kind,
        }))
        return
    if variant == "gpipe":
        r = gpipe_bench()
        print(json.dumps({
            "metric": "gpipe_m_scaling_speedup",
            "value": round(r["speedup"], 2),
            "unit": "x (step time, M=4pp vs M=pp)",
            "vs_baseline": round(r["speedup"] / R2_GPIPE_SPEEDUP, 2),
            "pp": r["pp"], "m_low": r["m_low"], "m_high": r["m_high"],
            "step_ms_m_low": r["step_ms_m_low"],
            "step_ms_m_high": r["step_ms_m_high"],
            "step_ms_m_low_interleaved": r["step_ms_m_low_interleaved"],
            "step_ms_m_high_interleaved": r["step_ms_m_high_interleaved"],
            "interleave_speedup_at_m_low": round(
                r["interleave_speedup_at_m_low"], 2),
            "interleave_speedup_at_m_high": round(
                r["interleave_speedup_at_m_high"], 2),
            "backend": jax.default_backend(),
        }))
        return
    if variant == "dhead":
        r = dhead_bench()
        print(json.dumps({
            **r, "value": r["fwdbwd_penalty_x"],
            "vs_baseline": None,
            "device_kind": jax.devices()[0].device_kind,
        }))
        return
    if variant == "gpipe_mem":
        r = gpipe_mem()
        print(json.dumps({
            "metric": "gpipe_memory_table",
            "value": len(r["rows"]), "unit": "configs",
            "vs_baseline": None, **r,
            "backend": jax.default_backend(),
        }))
        return
    if variant == "remat_mem":
        r = remat_mem()
        print(json.dumps({
            "metric": "remat_memory_table",
            "value": len(r["rows"]), "unit": "configs",
            "vs_baseline": None, **r,
            "backend": jax.default_backend(),
        }))
        return

    if variant == "zero_mem":
        r = zero_mem()
        print(json.dumps({
            "metric": "zero2_decision_table",
            "value": r["zero2_max_additional_saving_gb"],
            "unit": "GB (zero2 max additional per-device saving)",
            "vs_baseline": None, **r,
            "backend": jax.default_backend(),
        }))
        return

    r = train_bench(remat, seq=seq, heads=heads, remat_policy=remat_policy)
    base = R2_REMAT_TOKENS_PER_SEC if remat else R2_TOKENS_PER_SEC
    if remat_policy:
        # a distinct recipe with no recorded round-over-round series —
        # folding it into the remat/no-remat metric names would pollute
        # both baselines
        metric = f"lm_tokens_per_sec_per_chip_remat_{remat_policy}"
    elif remat:
        metric = "lm_tokens_per_sec_per_chip_remat"
    else:
        metric = "lm_tokens_per_sec_per_chip"
    print(json.dumps({
        "metric": metric,
        "value": round(r["per_chip_tps"], 0),
        "tps_min": round(r["per_chip_tps_min"], 0),
        "tps_max": round(r["per_chip_tps_max"], 0),
        "windows": r["windows"],
        "unit": "tokens/sec/chip",
        # round-over-round baseline is the seq-2048 default-layout
        # recipe; other seqs/head counts/policies have no recorded
        # baseline
        "vs_baseline": (round(r["per_chip_tps"] / base, 2)
                        if seq == SEQ and heads == DEFAULT_HEADS
                        and not remat_policy
                        else None),
        "step_ms": round(r["step_ms"], 2),
        # r4 recipe change: in-step accuracy metrics off (the
        # reference's benchmark-purity flag); ~+3% vs the r2/r3 recipe
        "acc_metrics": False,
        "mfu": round(r["mfu"], 4) if r["mfu"] is not None else None,
        "mfu_6n": round(r["mfu_6n"], 4) if r["mfu_6n"] is not None else None,
        # includes attention FLOPs (S²-dominant at long seq; XLA's
        # count excludes the Pallas kernels, 6N excludes attention)
        "mfu_model": (round(r["mfu_model"], 4)
                      if r["mfu_model"] is not None else None),
        "n_params": r["n_params"],
        "per_chip_batch": r["per_chip_batch"],
        "n_chips": r["n_chips"],
        "seq_len": seq,
        "num_heads": heads,
        "remat": remat,
        "remat_policy": remat_policy,
        "device_kind": jax.devices()[0].device_kind,
    }))


if __name__ == "__main__":
    main()
