"""A serving cell: the program's ``ServeEngine`` (paged cache, chunked
prefill, its defaults for everything the cell does not name) fed through
``submit(on_token=...)`` by one load thread, open or closed loop, with
every stamp taken on the client's side.

Set-up: weights on the device from the seed in one jitted call; one
warm-up request per prompt length of the mix (so every prefill body and
the decode body are compiled or loaded), with token ids from a stream of
their own, so that no request of the load finds a warm-up prompt in the
engine's prefix registry; the agreement sample — the tokens served and
the logits the engine's own bodies give when they are replayed — held
to the plain reference the configuration names; then the load runs for
``ramp_s`` before the window opens.  The load keeps running after the
window until every request that fell due inside it has finished (at most
``drain_s``), so the tail sees the same system as the head.  A traced
open-loop run stops the profiler while that load still arrives, and the
stop takes as long as the window's events are many: its schedule gets one
more phase, ``STOP_PHASE_S`` long, behind the untraced run's three, so the
prepared requests outlast the stop and the first three phases are the
untraced run's to the request.
"""

from __future__ import annotations

import os
import queue
import threading
import time
import types

import numpy as np

from benchmark import families
from benchmark.lib import stats, traffic
from benchmark.lib.runtime import (RunContext, Ticker, TracedWindow,
                                   memory_peak_bytes)


class _Sent:
    """One request as its client saw it."""
    __slots__ = ("req", "due", "submitted", "stamps", "handle", "shed")

    def __init__(self, req, due):
        self.req, self.due = req, due
        self.submitted = None
        self.stamps = []        # monotonic time of every on_token
        self.handle = None
        self.shed = False

    @property
    def finished(self) -> bool:
        return len(self.stamps) >= self.req.max_new_tokens


class _Load(threading.Thread):
    """The one load thread.  Open loop: submits each request when it falls
    due, whatever the system does.  Closed loop: ``clients`` requests in
    flight, the next one sent when one finishes."""

    def __init__(self, engine, requests, mix):
        super().__init__(daemon=True, name="bench-load")
        self.engine, self.requests, self.mix = engine, requests, mix
        self.sent = []
        self.t0 = None
        self._halt = threading.Event()
        self._finished_q = queue.Queue()
        self.exhausted = False

    def _submit(self, req, due):
        from dtf_tpu.serve.engine import Backpressure
        rec = _Sent(req, due)

        def on_token(_tok, rec=rec):
            rec.stamps.append(time.monotonic())
            if rec.finished:
                self._finished_q.put(rec)
        rec.submitted = time.monotonic()
        try:
            rec.handle = self.engine.submit(
                req.prompt, max_new_tokens=req.max_new_tokens,
                temperature=0.0, on_token=on_token)
        except Backpressure:
            rec.shed = True
            self._finished_q.put(rec)       # a closed-loop client moves on
        self.sent.append(rec)

    def run(self):
        self.t0 = time.monotonic()
        it = iter(self.requests)
        if self.mix["arrivals"] == "closed":
            for _ in range(int(self.mix["clients"])):
                self._submit(next(it), time.monotonic())
            while not self._halt.is_set():
                try:
                    self._finished_q.get(timeout=0.05)
                except queue.Empty:
                    continue
                req = next(it, None)
                if req is None:
                    self.exhausted = True
                    return
                self._submit(req, time.monotonic())
            return
        for req in it:
            due = self.t0 + req.due_s
            while not self._halt.is_set():
                wait = due - time.monotonic()
                if wait <= 0:
                    break
                self._halt.wait(min(wait, 0.05))
            if self._halt.is_set():
                return
            self._submit(req, due)
        self.exhausted = True

    def halt(self):
        self._halt.set()


def _reset_histograms(engine, names):
    for n in names:
        engine.metrics.get(n).reset()


QUANTILES = (50, 90, 95, 99)
# the profiler's stop took 11.0-13.3 s after a 6 s window of the loaded cell
# (my chip runs, PR 37) and 30-36 s after a closed loop's 36 s (PR 45)
STOP_PHASE_S = 90.0


def _histogram_read(engine, names):
    """count, mean and percentiles of what each histogram observed since
    its reset, through its public API (same interpolation as
    lib/stats.py)."""
    out = {}
    for n in names:
        h = engine.metrics.get(n)
        out[n] = {"count": h.count, "mean": h.mean,
                  "q": {q: h.percentile(q) for q in QUANTILES}}
    return out


# serve_decode_live_pages: a sample per decode step, the pages its rows'
# contexts occupy
HISTOGRAMS = ("serve_decode_step_s", "serve_queue_wait_s",
              "serve_decode_live_pages")


def model_and_sample(ctx: RunContext):
    """The cell's model with weights from the seed, its settings, and one
    warm-up prompt per prompt length of the mix; ``sample`` is the places
    of the prompts the agreement check reads."""
    import jax
    import jax.numpy as jnp

    from dtf_tpu.models import build_model

    cell, wl, mix = ctx.cell, ctx.cell.workload, dict(ctx.cell.traffic)
    toy = ctx.toy or {}
    mix.update(toy.get("traffic", {}))
    engine_kw = dict(wl["engine"], **toy.get("engine", {}))
    model_kw = dict(cell.config["build_model"]["kwargs"],
                    **toy.get("model_kwargs", {}))
    vocab = toy.get("vocab_size", cell.config["vocab_size"])
    dtype = {"bf16": jnp.bfloat16, "fp32": jnp.float32}[cell.config["dtype"]]
    model, _ = build_model(cell.config["build_model"]["name"],
                           num_classes=vocab, dtype=dtype, **model_kw)
    # the parameter shapes do not depend on the attention formulation; the
    # plain one traces at any length
    params = jax.jit(model.clone(use_pallas=False).init)(
        jax.random.key(ctx.key_seed),
        jnp.zeros((1, engine_kw["kv_page_size"]), jnp.int32))["params"]
    agree = dict(wl["agreement"], **toy.get("agreement", {}))
    # one prompt per prompt length of the mix, token ids from a stream that
    # is not the load's
    rng = np.random.default_rng([ctx.seed, 1])
    lengths = mix["prompt_len"].get("snap_to") or [mix["prompt_len"]["max"]]
    prompts = [rng.integers(0, vocab, size=int(n), dtype=np.int32)
               for n in lengths]
    sample = [i for i, n in enumerate(lengths)
              if int(n) in agree["prompt_lens"]]
    return types.SimpleNamespace(
        model=model, params=params, vocab=vocab, mix=mix, engine_kw=engine_kw,
        agree=agree, prompts=prompts, sample=sample)


def replay_logits(engine, prompts, served) -> list:
    """The logits the engine's own compiled bodies give where they chose
    each served token, a [tokens, vocab] array a prompt: every prompt
    prefilled again in the engine's chunk plan, then all decoded in
    lockstep with the served tokens fed back.  The bodies return these
    logits beside the token; the engine drops them and ``ServeResult``
    carries none, so the idle engine runs this on its own thread, cache
    and pages (``run_on_engine``), and gets the pages back after."""
    from dtf_tpu.serve.engine import chunk_plan
    dec, page = engine.decoder, engine.page_size
    n, slots, budget = len(prompts), engine.max_batch, len(served[0])
    assert n <= slots and all(len(t) == budget for t in served)

    def job():
        tables = np.zeros((slots, dec.pages_per_slot), np.int32)
        held = []
        for r, (p, t) in enumerate(zip(prompts, served)):
            pages = engine.pool.alloc(-(-(len(p) + len(t)) // page))
            if pages is None:
                raise RuntimeError("no free pages for the replay")
            held.extend(pages)
            tables[r, :len(pages)] = pages
        cache = engine._cache
        out = [np.zeros((budget, dec.model.vocab_size), np.float32)
               for _ in served]
        for r, p in enumerate(prompts):
            for start, clen in chunk_plan(len(p), engine.prefill_chunk,
                                          page):
                chunk = np.zeros((clen,), np.int32)
                real = p[start:start + clen]
                chunk[:len(real)] = real
                _, cache, last = dec.prefill_chunk(
                    cache, chunk, tables[r], start, len(real) - 1, 0.0,
                    seed=0)
            out[r][0] = np.asarray(last, np.float32)
        index = np.zeros((slots,), np.int32)
        index[:n] = [len(p) for p in prompts]
        for j in range(1, budget):
            tokens = np.zeros((slots,), np.int32)
            tokens[:n] = [t[j - 1] for t in served]
            _, cache, step = dec.decode_step(
                cache, tokens, index, np.zeros((slots,), np.float32),
                seeds=np.zeros((slots,), np.uint32), block_tables=tables)
            step = np.asarray(step[:n], np.float32)
            for r in range(n):
                out[r][j] = step[r]
            index[:n] += 1
        engine._cache = cache
        engine.pool.free(held)
        return out
    return engine.run_on_engine(job, timeout=600)


def setup(ctx: RunContext):
    """Weights, engine, warm-up of every body the mix needs, agreement
    with the plain reference.  Returns (engine, mix, vocab, agreement)."""
    from dtf_tpu.serve.engine import ServeEngine

    cell, m = ctx.cell, model_and_sample(ctx)
    engine = ServeEngine(m.model, m.params, seed=ctx.key_seed, **m.engine_kw)

    # one request per prompt length: every prefill body and the decode body
    handles = [engine.submit(p, max_new_tokens=int(m.agree["new_tokens"]))
               for p in m.prompts]
    served = [list(h.result(timeout=1100).tokens) for h in handles]
    if engine.error is not None:
        raise RuntimeError("engine thread died in warm-up") from engine.error
    t_warm = time.monotonic()
    reference = families.load_reference(cell.config, cell.root)
    sample = [m.prompts[i] for i in m.sample]
    sample_served = [served[i] for i in m.sample]
    agreement = reference.served_tokens_agree(
        m.params, sample, sample_served, float(m.agree["logit_rtol"]),
        replay_logits(engine, sample, sample_served),
        float(m.agree["logit_rms_limit"]))
    ctx.note(phase="warm",
             prefill_bodies=traffic.prefill_bodies(
                 m.mix, m.engine_kw["prefill_chunk"],
                 m.engine_kw["kv_page_size"]),
             compiled_bodies=engine.decoder.compiled_count,
             compiles_total=ctx.compiles.total,
             compile_cache_hits=ctx.compiles.hits,
             warm_s=t_warm - ctx.t_process,
             agreement_s=time.monotonic() - t_warm, agreement=agreement)
    return engine, m.mix, m.vocab, agreement


def load_phases(mix: dict, seconds: float, traced: bool) -> list:
    """The lengths of the load's phases: ramp, window, tail — and behind
    them, in a traced open-loop run alone, the arrivals that fall due while
    the profiler stops.  A phase is drawn from its own number
    (``traffic.phase_draw``) and the token ids in order, so the first
    three phases are the untraced run's to the request."""
    phases = [mix["ramp_s"], seconds, mix["drain_s"] + 5]
    if traced and mix["arrivals"] != "closed":
        phases.append(STOP_PHASE_S)
    return phases


def measure(ctx: RunContext, engine, mix: dict, vocab: int, seconds: float,
            traced: TracedWindow = None) -> dict:
    """Ramp, window, drain under ``mix``; what the clients saw."""
    requests = traffic.make_requests(
        mix, ctx.seed, load_phases(mix, seconds, traced is not None), vocab)
    load = _Load(engine, requests, mix)
    load.start()
    time.sleep(mix["ramp_s"])
    if traced is not None:
        traced.start()
    engine.reset_measurement()
    _reset_histograms(engine, HISTOGRAMS)
    t_open, wall_open = time.monotonic(), time.time()
    ticker = Ticker()
    time.sleep(seconds / 2)
    outstanding_mid = engine.outstanding
    time.sleep(max(0.0, t_open + seconds - time.monotonic()))
    t_close, wall_close = time.monotonic(), time.time()
    ticker_late_max_s = ticker.close()
    histograms = _histogram_read(engine, HISTOGRAMS)
    decode_steps = histograms["serve_decode_step_s"]["count"]
    if traced is not None:
        traced.stop()
    trace_stop_s = time.monotonic() - t_close
    pool_high_water = engine.pool.high_water
    outstanding_close = engine.outstanding

    def in_window(rec):
        return t_open <= rec.due < t_close
    # the load goes on for drain_s, so the window's last requests meet the
    # same system as its first; whoever has its first token by then is being
    # served (a long answer may take longer than any drain worth its time)
    deadline = time.monotonic() + mix["drain_s"]
    while time.monotonic() < deadline:
        if all(r.stamps or r.shed for r in list(load.sent) if in_window(r)):
            break
        time.sleep(0.1)
    load.halt()
    load.join(timeout=10)
    sent = list(load.sent)
    for rec in sent:
        if rec.handle is not None and not rec.handle.done():
            rec.handle.cancel()
    deadline = time.monotonic() + 30
    while engine.outstanding and time.monotonic() < deadline:
        time.sleep(0.05)            # the cancelled slots retire

    mine = [r for r in sent if in_window(r)]
    shed = sum(r.shed for r in mine)
    unfinished = sum(not r.finished and not r.shed for r in mine)
    unserved = sum(not r.stamps and not r.shed for r in mine)
    short = 0
    for r in mine:
        if r.finished and r.handle.done():
            res = r.handle.result(timeout=1)
            short += (res.cancelled
                      or len(res.tokens) != r.req.max_new_tokens)
    tokens_in_window = sum(t_open <= t < t_close
                           for r in sent for t in r.stamps)
    gaps = [b - a for r in sent for a, b in zip(r.stamps, r.stamps[1:])
            if t_open <= b < t_close]
    ttfts = [r.stamps[0] - r.due for r in mine if r.stamps]
    lateness = [r.submitted - r.due for r in mine]
    window_s = t_close - t_open
    reasons = []
    if short:
        reasons.append(f"{short} finished requests did not return their "
                       f"full token budget")
    compiles_in_window = ctx.compiles.between(t_open, t_close)
    compiled_in_window = ctx.compiles.names_between(t_open, t_close)
    if compiles_in_window:
        reasons.append(f"{compiles_in_window} compilations inside the "
                       f"window: {compiled_in_window}")
    if load.exhausted:
        reasons.append("the prepared requests ran out before the run "
                       "ended; raise prepare_per_s in the traffic file")
    # what the clients saw, for the readers of the cells whose tails are
    # per-layer metrics (BENCHMARK.json says in which cells they are judged)
    client = {"ttft_ms": {q: 1e3 * stats.percentile(ttfts, q)
                          for q in (50, 90, 99)} if ttfts else None,
              "gap_ms": {q: 1e3 * stats.percentile(gaps, q)
                         for q in (50, 95, 99)} if gaps else None}
    end_to_end = {"serve_tok_s": tokens_in_window / window_s}
    if mix["arrivals"] != "closed" and ttfts and gaps:
        end_to_end["ttft_p90_ms"] = client["ttft_ms"][90]
        end_to_end["gap_p95_ms"] = client["gap_ms"][95]
    note = dict(
        window_s=window_s, setup_s=t_open - ctx.t_process,
        rate_per_s=mix.get("rate_per_s"), requests_in_window=len(mine),
        tokens_offered_in_window=sum(r.req.max_new_tokens for r in mine),
        shed=shed, unserved=unserved, unfinished_at_halt=unfinished,
        short=short,
        tokens_in_window=tokens_in_window, gaps=len(gaps),
        ttft_ms=client["ttft_ms"], gap_ms=client["gap_ms"],
        generator_late_ms_max=1e3 * max(lateness) if lateness else None,
        gap_max_ms=1e3 * max(gaps) if gaps else None,
        ticker_late_max_ms=1e3 * ticker_late_max_s,
        decode_steps=decode_steps,
        decode_step_ms_median=1e3 * histograms["serve_decode_step_s"]["q"][50],
        pool_pages_high_water=pool_high_water,
        outstanding_mid=outstanding_mid, outstanding_close=outstanding_close,
        max_concurrent=engine.max_concurrent,
        compiles_in_window=compiles_in_window,
        compiled_in_window=compiled_in_window,
        live_pages_per_step=histograms["serve_decode_live_pages"]["mean"],
        trace_stop_s=trace_stop_s if traced is not None else None,
        requests_prepared=len(requests),
        offered_per_s=len(mine) / window_s, requests_total=len(sent),
        serve_tok_s=end_to_end["serve_tok_s"])
    ctx.note(phase="serve_window", **note)
    return {"reasons": reasons, "attempted": len(mine),
            # a closed loop above capacity always has requests waiting for
            # their turn at its end: only an open loop, below the knee, owes
            # every request of the window a first token by the drain's end
            "failed": shed + short + (0 if mix["arrivals"] == "closed"
                                      else unserved),
            "setup_s": t_open - ctx.t_process, "end_to_end": end_to_end,
            "note": note,
            "readers": {"decode_steps": decode_steps, "window_s": window_s,
                        "histograms": histograms, "client": client,
                        "window_wall": (wall_open, wall_close),
                        # the sizes the run really had (a rehearsal's are
                        # the toy's), for readers/span_mfu.py
                        "engine": {"max_batch": engine.max_batch,
                                   "page_size": engine.page_size},
                        "prompt_lens": mix["prompt_len"].get("snap_to")}}


def run(ctx: RunContext) -> dict:
    from dtf_tpu.obs import trace

    span_dir = os.path.join(ctx.out_dir, "spans")
    if ctx.traced:
        trace.configure(span_dir)       # the program's spans: traced runs only
    engine, mix, vocab, agreement = setup(ctx)
    traced = None
    seconds = ctx.seconds
    if ctx.traced:
        traced = TracedWindow(os.path.join(ctx.out_dir, "profile"))
        seconds = min(seconds, float(ctx.cell.workload["trace_seconds"]))
    result = measure(ctx, engine, mix, vocab, seconds, traced)
    engine.stop(drain=True, timeout=60)
    reasons = result["reasons"]
    if not agreement["ok"]:
        reasons.append(f"served tokens disagree with the plain reference: "
                       f"{agreement}")
    if engine.error is not None:
        reasons.append(f"the engine thread died: {engine.error!r}")
    records = []
    if ctx.traced:
        trace.disable()
        records = trace.read_records(
            os.path.join(span_dir, "trace_rank0.jsonl"))
    result["readers"].update(
        records=records, profile_dir=traced.trace_dir if traced else None)
    result.update(correct=not reasons,
                  memory_peak_bytes=memory_peak_bytes(),
                  # each number the agreement compared, beside its limit
                  compared={"logit_rms": [agreement.get("logit_rms"),
                                          agreement.get("logit_rms_limit")],
                            "worst_gap": [agreement["worst_gap"],
                                          agreement["allowed_gap"]]})
    return result
