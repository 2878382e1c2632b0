"""LM step roofline: where does the flagship's step go? (VERDICT r3 #3)

Step history as the kernels improved: ~254 ms (r3) → ~228 (r4
scratch-store bwd kernels) → ~223 ms (r5 fused single-pass backward,
mfu_model ~0.59).

Sibling of bench_profile.py (the ResNet roofline), for the LM flagship
(transformer_tpu: 12x768, 6 heads x d_head 128, seq 2048, bf16, AdamW,
per-chip batch 16).  Independent views of one step:

1. measured wall time, with and without in-step accuracy metrics (the
   reference's own benchmark-purity flag, common.py:277-278: the
   argmax reads the full [B*S, 32k] f32 logits every step);
2. XLA cost_analysis aggregates -> achieved FLOP/s + HBM bandwidth
   (NOTE: XLA does not count the Pallas attention kernels' FLOPs, so
   an analytic model-FLOPs MFU is reported alongside);
3. per-dot table from the optimized HLO: FLOPs + minimal bytes per
   matmul class, compute/bandwidth floors;
4. isolated component timings (dispatch-jitter-proof fori_loop
   differencing): flash attention f+b x layers, lm_head+CE f+b;
5. the blocked-CE measurement (r3 #3's proposed lever): computing the
   loss over row chunks with remat instead of materializing the
   [B*S, 32k] f32 logits.  MEASURED NEGATIVE on this chip: the head
   is compute-bound, not logits-bandwidth-bound — isolated f+b 24.3
   (materialized) vs 21.3-24.3 ms (chunked, best case ~12%/~3 ms of a
   254 ms step), because chunking adds a full logits recompute pass
   (+1.65 TFLOP) to save ~17 GB of traffic that XLA largely overlaps
   with compute anyway.  Kept out of the production loss path; this
   bench carries the evidence.

Prints ONE JSON line.
"""

import functools
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bench import peak_tflops
from bench_lm import _loop_time as _bench_lm_loop_time
from bench_lm import build_trainer
from bench_profile import conv_table, hbm_gbps

from bench_lm import D_FF, D_MODEL, LAYERS, SEQ, VOCAB  # flagship dims

BATCH = 16
HEADS, D_HEAD = 6, D_MODEL // 6

# shared loop-differenced harness (bench_lm documents the rationale)
_loop_time = functools.partial(_bench_lm_loop_time, n1=8, n2=72, reps=6)


def build_step(report_acc: bool):
    """The flagship step — same recipe object as bench_lm's headline
    (build_trainer), so the roofline decomposes exactly the benched
    step."""
    trainer, rt = build_trainer(BATCH, remat=False, seq=SEQ, heads=HEADS,
                                report_acc=report_acc)
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, VOCAB, (BATCH, SEQ)).astype(np.int32)
    labels = np.roll(tokens, -1, axis=1)
    state = trainer.init_state(jax.random.key(0), (tokens, labels))
    sharded = rt.shard_batch((tokens, labels))
    return trainer, state, sharded


def step_time(step_fn, state, sharded, warmup=3):
    """``step_fn``: the jitted trainer.train_step OR the AOT-compiled
    executable (reusing the AOT object avoids a second multi-minute
    compile of the same 137M-param graph on this host).  Timing via
    bench.timed_train_steps (sync-cancelling)."""
    from bench import timed_train_steps
    for _ in range(warmup):
        state, m = step_fn(state, *sharded)
    jax.device_get(m["loss"])
    med, _, _, _, state = timed_train_steps(step_fn, state, sharded,
                                            short=3, long=13)
    return med, state


def isolated_attention():
    from dtf_tpu.ops.flash_attention import flash_attention
    key = jax.random.key(0)
    q = jax.random.normal(key, (BATCH, SEQ, HEADS, D_HEAD), jnp.bfloat16)
    k = jax.random.normal(key, (BATCH, SEQ, HEADS, D_HEAD), jnp.bfloat16)
    v = jax.random.normal(key, (BATCH, SEQ, HEADS, D_HEAD), jnp.bfloat16)

    def fb(i, qq):
        g = jax.grad(lambda q, k, v: jnp.sum(
            flash_attention(q, k, v, causal=True).astype(jnp.float32)),
            argnums=(0, 1, 2))(qq, k, v)
        return (g[0] + g[1] + g[2]).astype(jnp.bfloat16)
    return _loop_time(fb, q)


def isolated_head_ce(chunk_rows=None):
    import optax
    n = BATCH * SEQ
    key = jax.random.key(0)
    x = jax.random.normal(key, (n, D_MODEL), jnp.bfloat16)
    w = jax.random.normal(key, (D_MODEL, VOCAB), jnp.bfloat16) * 0.02
    labels = jax.random.randint(key, (n,), 0, VOCAB)

    def ce(x, w):
        if chunk_rows is None:
            logits = (x @ w).astype(jnp.float32)
            return jnp.mean(
                optax.softmax_cross_entropy_with_integer_labels(
                    logits, labels))
        xs = x.reshape(n // chunk_rows, chunk_rows, D_MODEL)
        ls = labels.reshape(n // chunk_rows, chunk_rows)

        @jax.checkpoint
        def chunk_loss(xc, lc):
            logits = (xc @ w).astype(jnp.float32)
            return jnp.sum(
                optax.softmax_cross_entropy_with_integer_labels(
                    logits, lc))
        tot, _ = lax.scan(
            lambda acc, args: (acc + chunk_loss(*args), None),
            jnp.float32(0.0), (xs, ls))
        return tot / n

    def fb(i, xx):
        g = jax.grad(ce, argnums=(0, 1))(xx, w)
        # fold BOTH grads into the carry (scaled to numerical no-ops):
        # a discarded g[1] lets XLA dead-code-eliminate the ~1.65 TFLOP
        # weight-gradient matmul and undercount the backward
        return (xx + g[0] * jnp.bfloat16(1e-30)
                + jnp.sum(g[1]).astype(jnp.bfloat16) * jnp.bfloat16(1e-30))
    return _loop_time(fb, x)


def isolated_embed_ln():
    """Embed f+b (the bwd is a scatter-add into the [32k, 768] table —
    a suspected TPU sink, measured negligible) and one LayerNorm f+b
    (×24 in the step).  Closes the decomposition's remainder."""
    import flax.linen as nn
    key = jax.random.key(0)
    tokens = jax.random.randint(key, (BATCH, SEQ), 0, VOCAB)
    emb = nn.Embed(VOCAB, D_MODEL, dtype=jnp.bfloat16)
    eparams = emb.init(key, tokens)

    def eloss(p, tokens):
        return jnp.sum(emb.apply(p, tokens).astype(jnp.float32) ** 2)

    def efb(i, carry):
        # params depend on the carry so the grad is loop-VARIANT —
        # XLA hoists loop-invariant computations out of fori_loop and
        # the differenced timing would measure a scalar add
        p = jax.tree_util.tree_map(
            lambda l: l + carry.astype(l.dtype), eparams)
        g = jax.tree_util.tree_leaves(jax.grad(eloss)(p, tokens))[0]
        return carry + jnp.sum(g).astype(jnp.float32) * 1e-20

    embed_s = _loop_time(efb, jnp.float32(0.0))

    x = jax.random.normal(key, (BATCH * SEQ, D_MODEL), jnp.bfloat16)
    ln = nn.LayerNorm(dtype=jnp.bfloat16)
    lp = ln.init(key, x)

    def lnfb(i, xx):
        g = jax.grad(lambda p, x: jnp.sum(
            ln.apply(p, x).astype(jnp.float32) ** 2), argnums=1)(lp, xx)
        # 1e-30, not 0: mul-by-zero would let XLA DCE the backward
        return xx + g.astype(jnp.bfloat16) * jnp.bfloat16(1e-30)

    ln_s = _loop_time(lnfb, x, n1=8, n2=136)
    return embed_s, ln_s


def main():
    device = jax.devices()[0]
    peak = peak_tflops(device) or 0.0
    gbps = hbm_gbps(device) or 0.0

    trainer, state, sharded = build_step(report_acc=True)
    compiled = trainer.train_step.lower(state, *sharded).compile()
    ca = compiled.cost_analysis()
    ca = ca[0] if isinstance(ca, (list, tuple)) else (ca or {})
    hlo = compiled.as_text()
    # time the AOT executable itself — the jit path would recompile
    # the identical graph
    step_s, state = step_time(compiled, state, sharded)

    trainer2, state2, sharded2 = build_step(report_acc=False)
    step_noacc_s, _ = step_time(trainer2.train_step, state2, sharded2)

    xla_flops = float(ca.get("flops", 0.0))
    xla_bytes = float(ca.get("bytes accessed", 0.0))

    # XLA:TPU lowers every dot to a 1x1 convolution — the ResNet
    # roofline's conv_table parses exactly this form.  Its channel
    # heuristic targets spatial convs; for 1x1 dot-convs the exact
    # symmetric identity is K = sqrt(prod(op1)*prod(op2)/prod(out))
    # (prod(op1)*prod(op2) = rows*K * K*cols and prod(out) = rows*cols),
    # so recompute flops = 2*prod(out)*K per row.
    dots = conv_table(hlo)
    for r in dots:
        p_out = float(np.prod(r["out"], dtype=np.float64))
        p_ops = (np.prod(r["kernel"], dtype=np.float64)
                 * np.prod(r["act"], dtype=np.float64))
        if p_out > 0 and p_ops > 0:
            r["flops"] = 2.0 * p_out * float(np.sqrt(p_ops / p_out))
    dots.sort(key=lambda r: -r["flops"])
    dot_flops = sum(r["flops"] for r in dots)
    dot_floor_ms = sum(max(r["flops"] / (peak * 1e12),
                           r["bytes_min"] / (gbps * 1e9))
                       for r in dots) * 1e3 if peak and gbps else None
    # aggregate per op class ("fc1/dot_general" → fc1)
    by_class: dict = {}
    for r in dots:
        parts = r.get("name", "").split("/")
        cls = parts[-2] if len(parts) >= 2 else (parts[-1] or "?")
        agg = by_class.setdefault(cls, {"n": 0, "flops": 0.0, "bytes": 0.0})
        agg["n"] += 1
        agg["flops"] += r["flops"]
        agg["bytes"] += r["bytes_min"]
    classes = [
        {"class": c, "n": a["n"], "tflops": round(a["flops"] / 1e12, 2),
         "floor_ms": round(max(a["flops"] / (peak * 1e12),
                               a["bytes"] / (gbps * 1e9)) * 1e3, 2)
         if peak and gbps else None}
        for c, a in sorted(by_class.items(),
                           key=lambda kv: -kv[1]["flops"])]

    attn_fb = isolated_attention()
    head_fb = isolated_head_ce()
    head_fb_chunked = isolated_head_ce(chunk_rows=8192)
    embed_fb, ln_fb = isolated_embed_ln()

    # analytic model FLOPs (XLA's count excludes the Pallas kernels):
    # 6*matmul_params per token + attention 12*S*d_model per token f+b
    n_params = sum(x.size for x in
                   jax.tree_util.tree_leaves(state.params))
    embed_params = VOCAB * D_MODEL + SEQ * D_MODEL
    matmul_params = n_params - embed_params
    tokens = BATCH * SEQ
    attn_flops = LAYERS * 4 * BATCH * HEADS * SEQ * SEQ * D_HEAD / 2 * 3.5
    model_flops = 6.0 * matmul_params * tokens + attn_flops

    out = {
        "metric": "lm_step_roofline",
        "value": round(model_flops / step_noacc_s / (peak * 1e12), 4)
        if peak else None,
        "unit": "model-flops mfu (no-acc step)",
        "vs_baseline": None,
        "step_ms": round(step_s * 1e3, 2),
        "step_noacc_ms": round(step_noacc_s * 1e3, 2),
        "tokens_per_sec_noacc": round(tokens / step_noacc_s, 0),
        "xla_flops_t": round(xla_flops / 1e12, 2),
        # same denominator as the headline model-flops MFU (the acc-on
        # compile's flops are fine: argmax contributes none), so the
        # xla_mfu↔value gap is purely the Pallas FLOPs XLA doesn't see
        "xla_mfu": (round(xla_flops / step_noacc_s / (peak * 1e12), 4)
                    if peak else None),
        "model_flops_t": round(model_flops / 1e12, 2),
        "xla_bytes_gb": round(xla_bytes / 1e9, 2),
        "achieved_hbm_gbps": round(xla_bytes / step_s / 1e9, 1),
        "compute_floor_ms": (round(model_flops / (peak * 1e12) * 1e3, 2)
                             if peak else None),
        "hbm_floor_ms": (round(xla_bytes / (gbps * 1e9) * 1e3, 2)
                         if gbps else None),
        # measured component split (isolated, f+b, per step)
        "attention_fb_ms_total": round(attn_fb * LAYERS * 1e3, 2),
        "head_ce_fb_ms": round(head_fb * 1e3, 2),
        "head_ce_fb_chunked_ms": round(head_fb_chunked * 1e3, 2),
        "blocked_ce_saving_ms": round((head_fb - head_fb_chunked) * 1e3, 2),
        "embed_fb_ms": round(embed_fb * 1e3, 2),
        # 2 per block + the final ln_f = 25 LayerNorms in the step
        "layernorms_fb_ms_total": round(ln_fb * (2 * LAYERS + 1) * 1e3, 2),
        "acc_metrics_cost_ms": round((step_s - step_noacc_s) * 1e3, 2),
        "n_dots_in_hlo": len(dots),
        "dot_flops_t": round(dot_flops / 1e12, 2),
        "dot_floor_sum_ms": (round(dot_floor_ms, 2)
                             if dot_floor_ms is not None else None),
        "dot_classes": classes[:12],
        "peak_tflops": peak, "peak_hbm_gbps": gbps,
        "device_kind": device.device_kind,
    }
    print(json.dumps(out))


if __name__ == "__main__":
    main()
