"""Dynamic batching engine: request queue → slot-based continuous
batching over the KV-cache decoder.

Serving traffic is many small requests arriving at random times;
accelerators want big fixed-shape batches.  The engine bridges the two
with the standard production recipe:

  admission control — ``submit`` validates size up front: a request
      whose prompt + budget cannot fit the cache is rejected loudly
      (ValueError) instead of being admitted and truncated silently.
  backpressure      — the queue is bounded.  A full queue sheds the
      request with :class:`Backpressure` carrying ``retry_after``
      (an EWMA-based estimate), and logs the shed — "loud shed":
      capacity problems must be visible, never silent latency.
  max-batch / max-delay — a fresh batch waits up to ``max_delay_s``
      after the first arrival to fill up to ``max_batch`` slots, then
      goes; once decoding, new arrivals join at any step boundary.
  continuous batching — the decode step always runs the full
      [num_slots, 1] shape (compiled exactly once); each slot carries
      its own ``cache_index``, so sequences of different lengths
      coexist, finish independently, and free their slot for the next
      queued request without draining the batch.

The KV cache is PAGED (``kv_page_size`` tokens a page), which brings
two more production levers:

  paged admission — HBM is a shared page pool (:class:`PagePool`), and
      a request is admitted when its worst-case page count
      (⌈(prompt + budget) / page_size⌉) is free — so concurrency is
      bounded by TOKENS IN FLIGHT, not num_slots × max_seq_len.  A
      pool sized at 50% of one full reservation per slot serves the
      same slot count whenever mean request length < 50% of
      max_seq_len.
      When the head of the queue cannot get pages it WAITS (FIFO —
      large requests are not starved by small ones slipping past);
      retiring slots free their pages for the next admit.
  chunked prefill — prompts prefill in ``prefill_chunk``-token
      page-aligned chunks, ONE chunk per engine iteration, with a
      decode step for running slots between chunks — a max-length
      prompt adds bounded (chunk-sized) gaps to running decodes
      instead of head-of-line-blocking them for the whole prompt.
      The first chunk of every prompt runs pure causal self-attention
      through the flash kernel (no cache gather at all), so short
      prompts — the common case — never touch the gather path.

With prefix sharing (on by default) the pool pages are
REFCOUNTED and a registry keyed by token-id hash maps every request's
full prompt-prefix pages to their physical pages:

  prefix hits — an admitted prompt whose leading full pages match a
      registered prefix (verified against the stored token ids — a
      hash collision degrades to a miss, never a wrong share) SHARES
      those physical pages instead of allocating + prefilling them: a
      common system prompt costs ONE physical copy across the whole
      batch, and admission needs only the unshared tail's pages.
      The registry OWNS one holder per registered page (cache
      semantics), so a warm prefix survives its requests retiring;
      when admission starves for pages, registry-only pages are
      EVICTED deepest-first (so surviving shallower entries stay a
      valid chain) until the admit fits — cached prefixes never
      block live traffic.
  copy-on-write — shared pages are never written.  The one write that
      can target a shared page (a prompt that is ENTIRELY a registered
      prefix must still re-decode its last token for the first-token
      logits) copies the page onto a fresh one first
      (``Decoder.copy_page``), then diverges there.
  release on retire — refcounts drop at retire; a page returns to the
      free list (and its registry entry is dropped) only when the last
      holder releases it.

Token STREAMING: every handle exposes ``stream()`` — an iterator
yielding each generated token as its decode step retires, and
``submit(on_token=...)`` — a per-token callback from the engine thread.
First-token latency is then one decode step after prefill, not the
whole generation; the ``serve_stream_lag_s`` histogram records how far
consumers run behind the engine.

Tensor-parallel decode: pass ``mesh`` (runtime/mesh, 'model' axis = N)
and the decoder runs every prefill/decode under shard_map with params
and the KV page pool sharded over the axis (serve/decode.py).  The
engine's host-side logic — slots, pages, scheduling — is unchanged:
block tables are replicated, sharding is the decoder's concern.

Single engine thread owns ALL device work (prefill, decode, sampling);
``submit`` only enqueues — so there is no cross-thread jit contention.
Each decode step syncs the sampled tokens to the host (the EOS/budget
check needs them); at CPU/test scale this is negligible, on a real TPU
serving stack the next optimization would be a lookahead pipeline.
"""

from __future__ import annotations

import dataclasses
import hashlib
import itertools
import logging
import queue as queue_mod
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

from dtf_tpu import chaos
from dtf_tpu.obs import trace
from dtf_tpu.obs.ledger import Ledger
from dtf_tpu.obs.registry import MetricsRegistry
from dtf_tpu.serve.decode import Decoder

log = logging.getLogger("dtf_tpu")


# default chunked-prefill unit, in pages (64 tokens at the default page
# size, and a page multiple at ANY page size)
DEFAULT_PREFILL_PAGES = 4

# a TRACED engine stamps the two clocks against each other (_clock_anchor)
# before every ANCHOR_TURNS-th decode launch: one anchor a 0.3–0.8 s of a
# busy window, each a tiny synced program (PERF.md §6 PR 52 has what it
# costs).  An untraced engine never does
ANCHOR_TURNS = 32


def chunk_plan(plen: int, prefill_chunk: int, page_size: int,
               start: int = 0):
    """[(start, len), ...] page-aligned chunks covering [start, plen)
    of a prompt (``start`` — the first position NOT covered by shared
    prefix pages — must be page-aligned).  Full ``prefill_chunk``-token
    chunks, then one final chunk padded to the page size (so the final
    chunk always contains the last real prompt token — the sampled
    position).  prefill_chunk == 0: the whole remainder is one
    page-aligned chunk."""
    chunk = prefill_chunk or -(-(plen - start) // page_size) * page_size
    plan = []
    while plen - start > chunk:
        plan.append((start, chunk))
        start += chunk
    rem = plen - start
    plan.append((start, -(-rem // page_size) * page_size))
    return plan


class Backpressure(RuntimeError):
    """Request shed: the queue is full.  ``retry_after`` (seconds) is
    the engine's estimate of when capacity frees up."""

    def __init__(self, retry_after: float):
        super().__init__(
            f"serving queue full — shed; retry after {retry_after:.2f}s")
        self.retry_after = retry_after


@dataclasses.dataclass
class ServeRequest:
    prompt: np.ndarray                  # 1-D int32 token ids
    max_new_tokens: int = 32
    temperature: float = 0.0            # 0 = greedy
    eos_id: Optional[int] = None        # stop token (included in output)
    # distributed-tracing span context: the trace id follows the
    # request across processes (router → wire → here); trace_parent is
    # the upstream span id the per-request records link back to
    trace_id: Optional[str] = None
    trace_parent: Optional[str] = None
    # per-request sampling seed: sampled tokens are a pure function of
    # (rng_seed, position), so a re-dispatched SAMPLED request replays
    # token-exactly on any replica with identical params.  None at
    # submit = the engine derives one from (engine seed, request id)
    rng_seed: Optional[int] = None
    # filled by the engine
    id: int = -1
    submit_time: float = 0.0
    admit_time: float = 0.0
    first_token_time: float = 0.0
    finish_time: float = 0.0


@dataclasses.dataclass
class ServeResult:
    request_id: int
    tokens: List[int]                   # generated tokens (prompt excluded)
    prompt_len: int
    queue_wait_s: float
    time_to_first_token_s: float
    latency_s: float
    # absolute timestamps (time.time()), so metrics can reconstruct the
    # serving window across requests without trusting the caller
    submit_time: float = 0.0
    finish_time: float = 0.0
    cancelled: bool = False
    trace_id: Optional[str] = None      # the request's distributed-trace id


class _Handle:
    """Future-lite returned by submit() — plus a token stream.

    ``result()`` is the retire-granular view (all tokens at once);
    ``stream()`` yields each token as its decode step retires, so a
    client renders output at first-token latency instead of
    full-generation latency.  Both views see the same tokens."""

    def __init__(self, req: ServeRequest,
                 on_token: Optional[Callable] = None,
                 stream_lag_hist=None, cond=None):
        self.request = req
        self._event = threading.Event()
        self._result: Optional[ServeResult] = None
        self._on_token = on_token
        self._lag_hist = stream_lag_hist
        self._q: "queue_mod.Queue" = queue_mod.Queue()
        self._cancel = threading.Event()
        self._cond = cond

    def done(self) -> bool:
        return self._event.is_set()

    @property
    def cancelled(self) -> bool:
        return self._cancel.is_set()

    def cancel(self) -> None:
        """Ask the engine to stop working on this request.  The engine
        thread acts at its next iteration: a queued request resolves
        immediately (``cancelled=True``, no tokens), a running slot
        retires with the tokens decoded so far and frees its pages —
        the capacity a deadline-exceeded, failed-over, or losing-hedge
        attempt would otherwise burn decoding an answer nobody reads.
        Safe from any thread; idempotent."""
        self._cancel.set()
        if self._cond is not None and self._cond.acquire(blocking=False):
            try:
                self._cond.notify_all()
            finally:
                self._cond.release()

    def result(self, timeout: Optional[float] = None) -> ServeResult:
        if not self._event.wait(timeout):
            raise TimeoutError(
                f"request {self.request.id} not finished in {timeout}s")
        return self._result

    def stream(self, timeout: Optional[float] = None):
        """Iterator over generated tokens, yielding as each retires
        from a decode step.  ``timeout`` bounds the wait for EACH
        token (TimeoutError past it).  Ends when the request finishes
        (or is cancelled — check ``result().cancelled``).  Observes
        the engine's ``serve_stream_lag_s`` histogram: time from the
        engine emitting a token to the consumer receiving it — the
        slow-consumer signal."""
        while True:
            try:
                kind, payload = self._q.get(timeout=timeout)
            except queue_mod.Empty:
                raise TimeoutError(
                    f"request {self.request.id}: no token in {timeout}s"
                ) from None
            if kind == "done":
                return
            tok, t_emit = payload
            if self._lag_hist is not None:
                self._lag_hist.observe(max(0.0, time.time() - t_emit))
            yield tok

    def _emit(self, token: int):
        """Engine thread: one token retired."""
        self._q.put(("token", (int(token), time.time())))
        if self._on_token is not None:
            try:
                self._on_token(int(token))
            except Exception:  # noqa: BLE001 — a client callback must
                # never take down the engine thread
                log.exception("serve: on_token callback raised")

    def _deliver(self, result: ServeResult):
        self._result = result
        self._event.set()
        self._q.put(("done", None))


class PagePool:
    """Host-side REFCOUNTED free-list allocator over the shared KV
    page pool.

    Page 0 is the SCRATCH page — never handed to a request.  Inactive
    rows of the fixed-shape decode batch carry all-zeros block-table
    rows, so their garbage writes/gathers land there and can never
    touch a live sequence (ops.paged_attention has the full invariant).

    Refcounts carry prefix sharing: ``alloc`` grants fresh pages at
    refcount 1, ``share`` adds a holder to a live page, and ``free``
    releases one holder — a page physically returns to the free list
    only when its LAST holder releases it.  ``high_water`` records the
    peak physical pages in use — the number that proves both that
    retired pages are reclaimed AND that shared prefixes really cost
    one physical copy."""

    def __init__(self, num_pages: int):
        if num_pages < 2:
            raise ValueError(f"page pool needs >= 2 pages (page 0 is "
                             f"scratch), got {num_pages}")
        self.num_pages = int(num_pages)
        # LIFO free stack: a just-retired request's pages go to the
        # next admit — maximally warm reuse, and the reclamation tests
        # can assert the high-water mark stays at the concurrent need
        self._free = list(range(self.num_pages - 1, 0, -1))
        self._ref: Dict[int, int] = {}
        self.high_water = 0

    @property
    def usable_pages(self) -> int:
        return self.num_pages - 1

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def used_pages(self) -> int:
        return self.usable_pages - len(self._free)

    @property
    def shared_refs(self) -> int:
        """Extra holders beyond the first across all pages — how many
        page allocations prefix sharing is currently saving."""
        return sum(c - 1 for c in self._ref.values() if c > 1)

    def refcount(self, page: int) -> int:
        return self._ref.get(page, 0)

    def alloc(self, n: int) -> Optional[List[int]]:
        """n fresh pages at refcount 1, or None when the pool cannot
        cover them (caller waits for a retire — never a partial
        grant)."""
        if n > len(self._free):
            return None
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            self._ref[p] = 1
        self.high_water = max(self.high_water, self.used_pages)
        return pages

    def share(self, pages: List[int]):
        """Add one holder to each (live) page — the prefix-hit grant."""
        for p in pages:
            if self._ref.get(p, 0) < 1:
                raise ValueError(
                    f"page {p} is not allocated — sharing a freed page "
                    f"would alias a future grant")
            self._ref[p] += 1

    def free(self, pages: List[int]) -> List[int]:
        """Release one holder per page; pages whose last holder left
        return to the free list.  Returns the PHYSICALLY freed pages
        (the engine drops their prefix-registry entries)."""
        freed: List[int] = []
        for p in pages:
            c = self._ref.get(p, 0)
            if c < 1:
                raise ValueError(f"double free of page {p}")
            if c == 1:
                del self._ref[p]
                self._free.append(p)
                freed.append(p)
            else:
                self._ref[p] = c - 1
        return freed


def _tctx(trace_id, parent=None) -> Dict[str, str]:
    """Span-context attrs for a per-request trace record — empty when
    the request carries no trace id (tracing off, or an untraced
    caller), so untagged records stay exactly as small as before."""
    if trace_id is None:
        return {}
    out = {"trace": trace_id}
    if parent is not None:
        out["parent_span"] = parent
    return out


def _page_digest(prev: str, page_tokens: np.ndarray) -> str:
    """Chained content key: depth-d digest = sha1(depth-(d−1) digest ‖
    page d's int32 token bytes).  Chaining makes the whole registry
    walk O(pages) — hashing the full growing prefix at every depth
    would be O(pages²·page_size) sha1 bytes per admission attempt, on
    the engine thread, repeated while a starved head-of-line request
    waits.  Collisions are astronomically unlikely, and the registry
    verifies the stored page tokens on every hit anyway (module-level
    so tests can monkeypatch a colliding hash and pin the guard)."""
    return hashlib.sha1(
        prev.encode()
        + np.ascontiguousarray(page_tokens, np.int32).tobytes()
    ).hexdigest()


class PrefixRegistry:
    """Token-id-hash → physical-page map for FULL prompt-prefix pages.

    Entry at depth d maps the CHAINED digest of
    ``prompt[: (d+1)·page_size]`` (depth-d digest = sha1(depth-(d−1)
    digest ‖ page d's tokens) — same information as hashing the full
    prefix, at O(pages) total work) to the physical page holding
    positions [d·ps, (d+1)·ps) of that prefix — valid because KV
    content is a pure function of (token ids, absolute positions), and
    prefix pages are position-aligned by construction.  Entries are OWNING (cache semantics): the engine
    registers a request's prefix pages when its prefill completes and
    the registry takes one pool holder per newly-registered page — a
    warm prefix outlives the request that wrote it.  Later admits
    share entries (refcount++), and an entry dies two ways: the pool
    physically frees the page (``drop_page``), or the engine EVICTS it
    to un-starve admission (deepest-first; only pages whose sole
    holder is the registry).  Lookup walks depths 0, 1, ... and stops
    at the first miss (prefix property) or at the first stored-token
    mismatch (the hash-collision guard: a colliding digest degrades to
    a miss, never to serving another prompt's KV)."""

    def __init__(self, page_size: int):
        self.page_size = int(page_size)
        # (depth, chain digest) -> (physical page, THAT page's token
        # bytes).  Storing only the page's own tokens suffices: lookup
        # walks from depth 0, so when every ancestor's stored block
        # already matched, matching this block proves the full prefix
        # by induction — O(pages) storage and verification
        self._entries: Dict[Tuple[int, str], Tuple[int, bytes]] = {}
        self._by_page: Dict[int, Tuple[int, str]] = {}

    def __len__(self) -> int:
        return len(self._entries)

    def lookup(self, prompt: np.ndarray) -> List[int]:
        """Longest registered chain of the prompt's full pages —
        pages[d] holds positions [d·ps, (d+1)·ps)."""
        ps = self.page_size
        pages: List[int] = []
        digest = ""
        for depth in range(int(len(prompt)) // ps):
            block = np.ascontiguousarray(
                prompt[depth * ps: (depth + 1) * ps], np.int32)
            digest = _page_digest(digest, block)
            ent = self._entries.get((depth, digest))
            if ent is None or ent[1] != block.tobytes():
                break
            pages.append(ent[0])
        return pages

    def register(self, prompt: np.ndarray, pages: List[int]) -> List[int]:
        """Record a request's full prompt pages (pages[d] = physical
        page of depth d).  First writer wins per key; a page backs at
        most one entry.  Returns the NEWLY registered pages — the
        engine gives the registry one pool holder for exactly those."""
        ps = self.page_size
        fresh: List[int] = []
        digest = ""
        for depth, page in enumerate(pages):
            block = np.ascontiguousarray(
                prompt[depth * ps: (depth + 1) * ps], np.int32)
            digest = _page_digest(digest, block)
            key = (depth, digest)
            if key in self._entries or page in self._by_page:
                continue
            self._entries[key] = (page, block.tobytes())
            self._by_page[page] = key
            fresh.append(page)
        return fresh

    def pages_by_depth_desc(self) -> List[int]:
        """All registered pages, deepest entries first — the eviction
        scan order (evicting depth d+1 before d keeps every surviving
        chain contiguous from depth 0, which is all lookup can use)."""
        return [page for (depth, _), (page, _) in sorted(
            self._entries.items(), key=lambda kv: -kv[0][0])]

    def drop_page(self, page: int):
        """The pool physically freed this page — its content is about
        to be someone else's."""
        key = self._by_page.pop(page, None)
        if key is not None:
            self._entries.pop(key, None)


@dataclasses.dataclass
class _Slot:
    handle: _Handle
    tokens: List[int]                   # generated so far
    last_token: int                     # next decode step's input
    index: int                          # current sequence length
    pages: List[int]                    # pool pages owned by this slot
    block_row: np.ndarray               # [M] int32 page ids
    phase: str = "decode"               # "prefill" until the prompt is in
    prompt_padded: Optional[np.ndarray] = None  # page-aligned prompt
    chunk_plan: Optional[List] = None   # [(start, len), ...]
    chunk_i: int = 0                    # next chunk to run


class ServeEngine:
    """Dynamic batcher over a :class:`~dtf_tpu.serve.decode.Decoder`.

    ``model`` is a TransformerLM (training configuration); ``params``
    its param pytree (from serve.bridge).  ``max_seq_len`` bounds
    prompt + generation per request and fixes the cache shapes.

    ``kv_page_size`` is the KV page's tokens (>= 1).
    ``kv_pool_pages`` sizes the shared pool in TOTAL pages incl. the
    scratch page (0/None = one full ``max_seq_len`` reservation per
    slot; size it down to provision for actual tokens in flight).
    ``prefill_chunk`` is the chunked-prefill unit in tokens (multiple
    of the page size; 0 = whole prompts prefill as one page-aligned
    chunk; None = the default, 4 pages).

    ``prefix_sharing`` (default on) shares full prompt-prefix pages
    across requests via the refcounted pool + prefix registry (module
    docstring).  ``mesh`` selects tensor-parallel decode
    (serve/decode.py Decoder).

    ``heartbeat`` (obs.watchdog.Heartbeat) is beaten once per ENGINE
    ITERATION with step = completed-request count — serving liveness
    for the launcher's hang watchdog and the router's health probe.
    Beating from the engine loop (not a side thread) is the point: a
    deadlocked engine thread stops beating, which is exactly the
    signal a health checker needs (the chatty-deadlock case a log- or
    thread-alive check misses).

    LOCK DISCIPLINE: ``_cond`` guards the submit-side state shared
    between client threads and the engine thread — declared in
    ``_GUARDED_BY`` and enforced statically by tools/dtflint (rule
    lock-guard).  NOT guarded, deliberately: ``_slots`` and ``_cache``
    are ENGINE-THREAD state (only ``_loop_body``/``_step``/``_admit``/
    ``_retire`` touch them — single-writer by construction), ``_stop``
    is a threading.Event, and ``completed`` is append-only from the
    engine thread with len() reads elsewhere (GIL-atomic)."""

    _GUARDED_BY = {
        "_pending": "_cond", "_draining": "_cond",
        "_ewma_latency": "_cond",
    }

    def __init__(self, model, params, *, max_batch: int = 8,
                 max_seq_len: Optional[int] = None,
                 max_delay_s: float = 0.005, queue_size: int = 64,
                 seed: int = 0, kv_page_size: int = 16,
                 kv_pool_pages: Optional[int] = None,
                 prefill_chunk: Optional[int] = None,
                 prefix_sharing: bool = True, mesh=None,
                 heartbeat=None):
        if max_batch < 1 or queue_size < 1:
            raise ValueError("max_batch and queue_size must be >= 1")
        if not kv_page_size or int(kv_page_size) < 1:
            raise ValueError(f"kv_page_size must be >= 1, got "
                             f"{kv_page_size!r}")
        self.max_batch = int(max_batch)
        self.max_seq_len = int(max_seq_len or model.max_seq_len)
        self.max_delay_s = float(max_delay_s)
        self.queue_size = int(queue_size)
        # metrics registry must exist before the decoder: the MFU/cost
        # ledger (obs/ledger.py) exports through it, and the decoder
        # registers each compiled body's XLA flop/byte counts there
        self.metrics = MetricsRegistry()
        self.ledger = Ledger(self.metrics)
        self.page_size = int(kv_page_size)
        # None = default (DEFAULT_PREFILL_PAGES); 0 = whole-prompt
        # single chunks
        self.prefill_chunk = (DEFAULT_PREFILL_PAGES * self.page_size
                              if prefill_chunk is None
                              else int(prefill_chunk))
        if self.prefill_chunk and self.prefill_chunk % self.page_size:
            raise ValueError(
                f"prefill_chunk ({self.prefill_chunk}) must be a "
                f"multiple of kv_page_size ({self.page_size})")
        self.decoder = Decoder(
            model, params, num_slots=self.max_batch,
            max_seq_len=self.max_seq_len,
            kv_page_size=self.page_size,
            kv_pool_pages=(int(kv_pool_pages) if kv_pool_pages
                           else None), mesh=mesh,
            ledger=self.ledger)
        compact = self.decoder.summary      # (window, chunk) or None
        if compact is not None and (not self.prefill_chunk
                                    or compact[0] % self.prefill_chunk):
            raise ValueError(
                f"prefill_chunk ({self.prefill_chunk}) must divide the "
                f"model's summary_window ({compact[0]}): a chunk may not "
                f"straddle a window's close")
        self.pool = PagePool(self.decoder.pool_pages)
        # a compact table's entry is not a function of the prompt's leading
        # tokens alone (a closed window's first page holds its summaries,
        # the others the next window's tokens): such a model shares no
        # prefix pages
        self.prefix_sharing = bool(prefix_sharing) and compact is None
        self.registry = PrefixRegistry(self.page_size)
        self._cache = self.decoder.fresh_cache()
        # base for per-request sampling seeds (requests that arrive
        # without one): a pure function of (engine seed, request id),
        # so two same-seeded engines fed the same submission order
        # sample identically — replica-interchangeable even for
        # direct (router-less) callers
        self._seed = int(seed)

        self._cond = threading.Condition()
        self._pending: List[_Handle] = []
        self._slots: List[Optional[_Slot]] = [None] * self.max_batch
        self._stop = threading.Event()
        self._draining = False
        self._ids = itertools.count()
        # metrics: the raw result list stays (collect_stats consumes
        # it); live operational state goes through the obs registry —
        # queue depth / slot occupancy gauges, shed/admit/complete
        # counters, latency histogram — so benches and the benchmark
        # file logger read one API instead of scraping log lines
        self.completed: List[ServeResult] = []
        self._m_queue_depth = self.metrics.gauge("serve_queue_depth",
                                                 unit="requests")
        self._m_occupancy = self.metrics.gauge("serve_slot_occupancy",
                                               unit="fraction")
        self._m_shed = self.metrics.counter("serve_shed_total",
                                            unit="requests")
        self._m_admitted = self.metrics.counter("serve_admitted_total",
                                                unit="requests")
        self._m_completed = self.metrics.counter("serve_completed_total",
                                                 unit="requests")
        self._m_latency = self.metrics.histogram("serve_latency_s", unit="s")
        self._m_queue_wait = self.metrics.histogram("serve_queue_wait_s",
                                                    unit="s")
        # per-engine-iteration samples of the same two signals, so a
        # finished run still has a distribution (the gauges only hold
        # the final — drained — values)
        self._m_queue_sampled = self.metrics.histogram(
            "serve_queue_depth_sampled", unit="requests")
        self._m_occ_sampled = self.metrics.histogram(
            "serve_slot_occupancy_sampled", unit="fraction")
        # page-pool operational signals: pool occupancy (a gauge; traced
        # runs have it an iteration, `pages_used` on the serve_iteration
        # record), prefill chunks run, and the decode-step
        # GAP — wall time between consecutive decode steps while slots
        # are decoding.  The gap p99 is the head-of-line-blocking
        # number chunked prefill exists to bound.
        self._m_pages_used = self.metrics.gauge("serve_kv_pages_used",
                                                unit="pages")
        # what a page holds beside its tokens' K and V: the bytes of the
        # running-state entries (0 for a model whose layers all attend)
        self.metrics.gauge("serve_state_bytes_per_page", unit="bytes").set(
            self.decoder.state_bytes_per_page)
        # what a token holds in the pools of every layer that keeps one: K
        # and V, latent rows, index keys (with the gauge above, the two
        # kinds of bytes a model of pools BESIDE state entries lays out)
        self.metrics.gauge("serve_kv_bytes_per_token", unit="bytes").set(
            self.decoder.kv_bytes_per_token)
        # what a token holds beside its cache rows: a lightning indexer's
        # keys over the layers that choose (0 for every other model)
        self.metrics.gauge("serve_index_bytes_per_token", unit="bytes").set(
            self.decoder.index_bytes_per_token)
        # pages that closed windows gave back to their rows (a model that
        # keeps summaries; 0 for every other)
        self._m_pages_reclaimed = self.metrics.gauge(
            "serve_pages_reclaimed_total", unit="pages")
        self._m_prefill_chunks = self.metrics.counter(
            "serve_prefill_chunks_total", unit="chunks")
        self._m_decode_gap = self.metrics.histogram("serve_decode_gap_s",
                                                    unit="s")
        # per-axis decode metrics: the mesh's tensor-parallel ways and
        # the decode-step time distribution — tokens/s-per-chip and
        # TP-scaling come straight from these two
        self._m_tp_ways = self.metrics.gauge("serve_tp_ways", unit="ways")
        self._m_tp_ways.set(getattr(self.decoder, "tp", 1))
        self._m_step_time = self.metrics.histogram("serve_decode_step_s",
                                                   unit="s")
        # 1 where the decode body's paged kernel scores a block all
        # heads at once, 0 head by head (Decoder.decode_all_heads)
        self.metrics.gauge("serve_paged_decode_allheads", unit="bool").set(
            int(self.decoder.decode_all_heads))
        # pages the paged decode kernel is asked to attend in a step:
        # ceil((index + 1) / page) summed over the rows, idle rows
        # (index 0) included — they read the scratch page.  Over
        # max_batch x pages_per_slot it is the share of the block
        # table that is live, and times the page's bytes the KV a step
        # has to read
        self._m_live_pages = self.metrics.histogram(
            "serve_decode_live_pages", unit="pages")
        # prefix sharing: pages shared instead of allocated, COW
        # copies, and the live shared-holder count
        self._m_prefix_hits = self.metrics.counter(
            "serve_prefix_hit_pages_total", unit="pages")
        self._m_cow = self.metrics.counter("serve_prefix_cow_total",
                                           unit="pages")
        self._m_evicted = self.metrics.counter(
            "serve_prefix_evicted_total", unit="pages")
        self._m_shared = self.metrics.gauge("serve_kv_pages_shared_refs",
                                            unit="refs")
        # streaming: engine-emit → consumer-receive delay per token
        self._m_stream_lag = self.metrics.histogram("serve_stream_lag_s",
                                                    unit="s")
        # KV-page migration (serve/migrate.py): pages shipped out /
        # pulled in over the replica wire, live migration holds (pages
        # pinned above eviction while a transfer is in flight), and
        # torn transfers caught by the payload digest
        self._m_pages_exported = self.metrics.counter(
            "serve_pages_exported_total", unit="pages")
        self._m_pages_imported = self.metrics.counter(
            "serve_pages_imported_total", unit="pages")
        self._m_mig_holds = self.metrics.gauge("serve_migration_holds",
                                               unit="pages")
        self._m_mig_torn = self.metrics.counter(
            "serve_migration_torn_total", unit="pages")
        # migration jobs: wire threads enqueue closures here; the
        # engine thread drains the queue once per iteration, so every
        # pool/registry/_cache touch stays single-writer (the queue is
        # a thread-safe queue.Queue — not _cond-guarded state)
        self._mig_q: "queue_mod.Queue" = queue_mod.Queue()
        self._mig_hold_pages = 0        # engine-thread only
        # cancellation: requests whose caller stopped wanting the
        # answer (deadline-exceeded, failed-over, losing hedge) —
        # each one freed a slot + pages that would otherwise decode
        # a full budget into the stale-discard bin
        self._m_cancelled = self.metrics.counter("serve_cancelled_total",
                                                 unit="requests")
        self._heartbeat = heartbeat
        # the exception that killed the engine thread, if one did —
        # callers holding cancelled results read the cause here
        self.error: Optional[BaseException] = None
        self._last_step_t: Optional[float] = None
        # decode steps and prefill chunks launched so far: the ordinals a
        # traced turn's record carries (_iteration_counts)
        self._step_launches = 0
        self._chunk_launches = 0
        self._prefill_rr = -1           # round-robin cursor (chunk sched)
        self.max_concurrent = 0         # peak simultaneously-active slots
        self._ewma_latency = 0.25       # seed estimate for retry_after
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="serve-engine")
        self._thread.start()

    @property
    def shed_count(self) -> int:
        """Total requests shed (single source of truth: the registry
        counter the benchmark export reads)."""
        return self._m_shed.value

    @property
    def outstanding(self) -> int:
        """Queued + in-flight requests — the load number a router's
        least-loaded placement and a replica's stats report expose."""
        with self._cond:
            return (len(self._pending)
                    + sum(s is not None for s in self._slots))

    def reset_measurement(self) -> int:
        """Zero the peak/distribution measurement state (decode-gap
        histogram, peak concurrency, pool high-water) under the engine
        lock, and return the current completed-request count — the
        slice point for post-warmup stats.  Benches call this after
        their warmup traffic drains so compile time and idle spans
        don't masquerade as serving behavior; holding ``_cond`` keeps
        the reset from racing the engine thread's own peak updates."""
        with self._cond:
            self._m_decode_gap.reset()
            self._last_step_t = None
            self.max_concurrent = 0
            self.pool.high_water = self.pool.used_pages
            return len(self.completed)

    # -- KV-page migration surface (serve/migrate.py) ------------------
    # Every entry point below MARSHALS its work onto the engine thread
    # (run_on_engine): the pool, registry and cache are single-writer
    # engine-thread state, and migration must serialize with admission,
    # eviction and retire — not race them.  Wire threads block on the
    # job's completion; the engine loop drains the job queue once per
    # iteration (≤0.1s latency when idle).

    def _run_migration_jobs(self):
        """Engine thread: run queued migration closures."""
        while True:
            try:
                fn, box, ev = self._mig_q.get_nowait()
            except queue_mod.Empty:
                return
            try:
                box["result"] = fn()
            except Exception as e:  # noqa: BLE001 — the error belongs
                # to the waiting wire thread, never the engine loop
                box["error"] = e
            ev.set()

    def run_on_engine(self, fn, timeout: float = 60.0):
        """Run ``fn()`` on the engine thread; return its result (or
        re-raise its exception) in the calling thread.  Deadlocks by
        construction if called FROM the engine thread — callers are
        wire/client threads only."""
        if self._stop.is_set():
            raise RuntimeError("engine is stopped")
        box: dict = {}
        ev = threading.Event()
        self._mig_q.put((fn, box, ev))
        with self._cond:
            self._cond.notify_all()      # wake an idle engine loop
        if not ev.wait(timeout):
            raise TimeoutError(f"engine job not run in {timeout}s")
        if "error" in box:
            raise box["error"]
        return box.get("result")

    def _chain_digests(self, prompt: np.ndarray, depths: int) -> List[str]:
        ps = self.page_size
        out: List[str] = []
        digest = ""
        for d in range(depths):
            digest = _page_digest(digest, prompt[d * ps:(d + 1) * ps])
            out.append(digest)
        return out

    def export_chain_begin(self, prompt) -> Tuple[List[int], List[str]]:
        """Look up the registry's verified page chain for ``prompt``
        and take a MIGRATION HOLD on it (one extra pool holder per
        page).  Held pages have refcount ≥ 2, which puts them above
        ``_evict_for``'s refcount-1 bar — an in-transfer page can never
        be evicted, by construction, not by bookkeeping.  Returns
        (pages, chained digests); release with
        :meth:`export_chain_end` (transfer done OR aborted — the hold
        must not outlive its transfer)."""
        if not self.prefix_sharing:
            return [], []
        prompt = np.asarray(prompt, np.int32).reshape(-1)

        def job():
            pages = self.registry.lookup(prompt)
            self.pool.share(pages)
            self._mig_hold_pages += len(pages)
            self._m_mig_holds.set(self._mig_hold_pages)
            return pages, self._chain_digests(prompt, len(pages))

        return self.run_on_engine(job)

    def export_chain_read(self, pages: List[int], lo: int, n: int):
        """Host payloads (decoder leaf lists) for ``pages[lo:lo+n]`` —
        one bounded window of an in-flight transfer.  The caller must
        hold the chain (export_chain_begin): the window read trusts
        that the physical pages still carry the chain's KV."""
        def job():
            out = [self.decoder.read_page(self._cache, p)
                   for p in pages[lo:lo + n]]
            self._m_pages_exported.inc(len(out))
            return out

        return self.run_on_engine(job)

    def export_chain_end(self, pages: List[int]) -> None:
        """Drop the migration hold (transfer complete or aborted)."""
        if not pages:
            return

        def job():
            for p in self.pool.free(pages):
                self.registry.drop_page(p)
            self._mig_hold_pages -= len(pages)
            self._m_mig_holds.set(self._mig_hold_pages)

        self.run_on_engine(job)

    def import_chain(self, prompt, payloads) -> int:
        """Write a fetched page chain (``payloads[d]`` = decoder leaf
        list for depth d, verified by the caller) into the local pool
        and register it, so the next admit of this prompt prefix
        SHARES the migrated pages instead of prefilling.  Depths the
        local registry already holds are skipped.  Ownership
        transfers: the fresh pages' alloc holder becomes the
        registry's holder — after import the pages are ordinary warm
        registry pages (refcount 1, evictable under pressure).
        Returns the number of pages imported."""
        if not self.prefix_sharing:
            raise RuntimeError("page import needs prefix sharing on")
        prompt = np.asarray(prompt, np.int32).reshape(-1)

        def job():
            existing = self.registry.lookup(prompt)
            todo = payloads[len(existing):]
            if not todo:
                return 0
            need = len(todo)
            pages = self.pool.alloc(need)
            if pages is None:
                self._evict_for(need)
                pages = self.pool.alloc(need)
            if pages is None:
                raise RuntimeError(
                    f"import starved: {need} pages needed, "
                    f"{self.pool.free_pages} free")
            for page, leaves in zip(pages, todo):
                self._cache = self.decoder.write_page(self._cache, page,
                                                      leaves)
            fresh = self.registry.register(prompt, existing + pages)
            # pages the registry refused (key raced in / collision
            # guard) go straight back — nothing may own an
            # unregistered imported page
            stray = [p for p in pages if p not in fresh]
            for p in self.pool.free(stray):
                self.registry.drop_page(p)
            self._m_pages_imported.inc(len(fresh))
            return len(fresh)

        return self.run_on_engine(job)

    # -- client side ---------------------------------------------------
    def submit(self, prompt, max_new_tokens: int = 32,
               temperature: float = 0.0,
               eos_id: Optional[int] = None,
               on_token: Optional[Callable] = None,
               trace_id: Optional[str] = None,
               trace_parent: Optional[str] = None,
               rng_seed: Optional[int] = None) -> _Handle:
        """Enqueue a request.  ``on_token`` is an optional per-token
        callback invoked FROM THE ENGINE THREAD as each token retires
        (keep it cheap — it sits on the decode path); the returned
        handle's ``stream()`` is the pull-based alternative.

        ``trace_id``/``trace_parent`` carry the distributed span
        context: the router mints a trace id per client request and
        sends it over the replica wire; a direct caller may pass its
        own.  When tracing is on and no id arrives, the engine mints
        one, so every request's lifecycle records (submit → admit →
        prefill chunks → decode steps → retire) share one id.

        ``rng_seed`` pins the request's SAMPLING identity: every
        sampled token is fold_in(key(rng_seed), position) — so a
        failover replay with the same seed (the router re-ships it)
        is token-exact.  None = derived from (engine seed, request
        id)."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size == 0:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got "
                             f"{max_new_tokens}")
        total = int(prompt.size) + int(max_new_tokens)
        if total > self.max_seq_len:
            raise ValueError(
                f"oversized request: prompt ({prompt.size}) + "
                f"max_new_tokens ({max_new_tokens}) = {total} exceeds "
                f"max_seq_len {self.max_seq_len}; shorten the prompt or "
                f"lower the budget")
        need = self.decoder.pages_for(total)
        if need > self.pool.usable_pages:
            raise ValueError(
                f"oversized request for the page pool: needs {need} "
                f"pages of {self.page_size} tokens but the pool has "
                f"{self.pool.usable_pages} usable — it could never "
                f"be admitted; grow --kv_pool_pages or shrink the "
                f"request")
        if trace_id is None and trace.enabled():
            trace_id = trace.new_trace_id()
        req = ServeRequest(prompt=prompt, max_new_tokens=int(max_new_tokens),
                           temperature=float(temperature), eos_id=eos_id,
                           trace_id=trace_id, trace_parent=trace_parent,
                           rng_seed=(None if rng_seed is None
                                     else int(rng_seed)))
        handle = _Handle(req, on_token=on_token,
                        stream_lag_hist=self._m_stream_lag,
                        cond=self._cond)
        with self._cond:
            # checked under the lock: a submit racing stop() must either
            # land in _pending BEFORE the stop (and get drained or
            # cancelled there) or raise here — never enqueue onto a
            # stopped engine, where nothing would ever deliver it
            if self._stop.is_set():
                if self.error is not None:
                    raise RuntimeError(
                        f"engine is stopped — engine thread died: "
                        f"{self.error!r}") from self.error
                raise RuntimeError("engine is stopped")
            if self._draining:
                # SIGTERM drain: admissions stop the moment the signal
                # lands; already-queued + in-flight work still finishes.
                # Shed, not error — the client retries against another
                # replica after retry_after, exactly like a full queue
                self._m_shed.inc()
                retry = max(0.05, self._ewma_latency)
                log.warning("serve: draining — shedding request "
                            "(retry_after=%.2fs)", retry)
                trace.anomaly("serve_shed", reason="draining",
                              shed_total=self.shed_count,
                              retry_after=retry,
                              **_tctx(trace_id, trace_parent))
                raise Backpressure(retry)
            if len(self._pending) >= self.queue_size:
                self._m_shed.inc()
                retry = max(0.05, self._ewma_latency
                            * (1 + len(self._pending) / self.max_batch))
                log.error(
                    "serve: queue full (%d pending, %d slots) — shedding "
                    "request (%d total shed); retry_after=%.2fs",
                    len(self._pending), self.max_batch, self.shed_count,
                    retry)
                trace.anomaly("serve_shed", pending=len(self._pending),
                              shed_total=self.shed_count,
                              retry_after=retry,
                              **_tctx(trace_id, trace_parent))
                raise Backpressure(retry)
            req.id = next(self._ids)
            req.submit_time = time.time()
            if req.rng_seed is None:
                # deterministic per (engine seed, request id); bounded
                # to 31 bits so the wire carries a plain JSON int
                req.rng_seed = (self._seed * 1_000_003 + req.id
                                + 12_345) & 0x7FFFFFFF
            self._pending.append(handle)
            self._m_queue_depth.set(len(self._pending))
            if trace_id is not None:
                trace.event("serve_submit", request=req.id,
                            prompt_len=int(prompt.size),
                            queue_depth=len(self._pending),
                            **_tctx(trace_id, trace_parent))
            self._cond.notify_all()
        return handle

    def generate(self, prompt, **kw) -> ServeResult:
        """Blocking convenience: submit + wait."""
        return self.submit(prompt, **kw).result(timeout=600)

    # -- engine thread -------------------------------------------------
    def _drain_migration_jobs(self):
        """Engine exit: fail queued migration jobs instead of leaving
        their wire threads to time out against a dead loop."""
        while True:
            try:
                _, box, ev = self._mig_q.get_nowait()
            except queue_mod.Empty:
                return
            box["error"] = RuntimeError("engine is stopped")
            ev.set()

    def _loop(self):
        try:
            self._loop_body()
            self._drain_migration_jobs()
        except Exception as e:
            # a dead engine thread must not strand clients blocked in
            # result(): fail loudly and deliver cancellations
            self.error = e
            log.exception("serve engine thread died — cancelling all "
                          "in-flight and queued requests")
            with self._cond:
                self._stop.set()
                stranded = ([s.handle for s in self._slots
                             if s is not None] + list(self._pending))
                self._slots = [None] * self.max_batch
                self._pending.clear()
            for handle in stranded:
                req = handle.request
                handle._deliver(ServeResult(
                    request_id=req.id, tokens=[], prompt_len=0,
                    queue_wait_s=0.0, time_to_first_token_s=0.0,
                    latency_s=0.0, cancelled=True))
            self._drain_migration_jobs()

    def _loop_body(self):
        # one `serve_iteration` span a turn, cut into the laps named in
        # _iteration: what the engine thread did, in order, so that a
        # reader of the device's timeline can say what the host was at
        # in each of the device's idle gaps.  Tracing off: the shared
        # no-op span, and trace.lap() is a None check
        while True:
            with trace.lap_span("serve_iteration") as it:
                done = self._iteration(it)
            if done:
                return

    def _iteration(self, it) -> bool:
        """One turn of the engine thread; True when the loop is over.

        ``it``, the turn's span, keeps its laps, closed by trace.lap in
        loop order (a name may repeat): ``sweep`` (heartbeat, migration
        jobs, a cancellation sweep), ``wait`` (nothing to do, or holding
        the door for ``max_delay_s``), ``admit`` (plans, page grants,
        binding), ``chunk_host`` / ``chunk_sync`` (_advance_prefill),
        ``gauges``, then _step's ``build``, ``launch_args`` and
        ``launch_call`` (closed by Decoder.decode_step), ``ready`` and
        ``emit``.  Traced, it also carries the turn's counts
        (_iteration_counts), and a traced run adds no lap name of its own
        but two stretches to these: a non-final chunk's wait for the
        model's counts is a ``chunk_sync`` (_model_counts), and every
        ANCHOR_TURNS-th ``launch_args`` begins with a clock anchor's round
        trip (_clock_anchor: the ``clock_anchor`` span inside the lap says
        how much of it)."""
        rec = trace.enabled()
        if rec:
            before = (self._m_completed.value, self._m_cancelled.value,
                      self._step_launches, self._chunk_launches)
        if self._heartbeat is not None:
            # serving liveness: the beat interval gate is inside
            # beat(), so this is one clock read per iteration
            self._heartbeat.beat(step=self._m_completed.value)
        # migration jobs run HERE, on the engine thread, between
        # iterations: exports/imports touch the pool, registry and
        # cache, which are single-writer engine-thread state — a
        # wire thread mutating them directly would race _retire
        self._run_migration_jobs()
        with self._cond:
            # cancellation sweep (queued half): a cancelled request
            # that never reached a slot resolves right here —
            # before it can cost an admission's pages
            cancelled_pending = [h for h in self._pending
                                 if h._cancel.is_set()]
            for handle in cancelled_pending:
                self._pending.remove(handle)
                self._finish_cancelled(handle)
            if cancelled_pending:
                # the idle branch below may wait before the normal
                # gauge refresh runs — a cancelled-empty queue must
                # not report phantom depth in the meantime
                self._m_queue_depth.set(len(self._pending))
            active = any(s is not None for s in self._slots)
            trace.lap("sweep")
            if not self._pending and not active:
                if self._stop.is_set():
                    return True
                # idle: the next decode step's gap would span this
                # wait, which is queue emptiness, not head-of-line
                # blocking — don't let it poison the gap histogram
                self._last_step_t = None
                # empty queue: sleep until a submit (or stop) pokes us
                self._cond.wait(timeout=0.1)
                trace.lap("wait")
                if rec:
                    self._iteration_counts(it, before, len(self._pending))
                return False
            if not active and self._pending and self.max_delay_s > 0:
                # fresh batch: hold the door up to max_delay after the
                # FIRST pending arrival so the batch can fill
                first = self._pending[0].request.submit_time
                while (len(self._pending) < self.max_batch
                       and not self._stop.is_set()):
                    remaining = first + self.max_delay_s - time.time()
                    if remaining <= 0:
                        break
                    self._cond.wait(timeout=remaining)
                trace.lap("wait")
            admitted = []
            for i, slot in enumerate(self._slots):
                if slot is None and self._pending:
                    req = self._pending[0].request
                    shared, need, cow = self._admission_plan(req)
                    # hold the shared pages BEFORE any alloc/
                    # eviction: a registry-only page this admit
                    # is about to share must not be evicted out
                    # from under it
                    self.pool.share(shared)
                    pages = self.pool.alloc(need)
                    if pages is None:
                        self._evict_for(need)
                        pages = self.pool.alloc(need)
                    if pages is None:
                        # head-of-line FIFO wait: the next
                        # retire frees pages; small requests do
                        # NOT slip past a starved big one.
                        # Un-hold the speculative shares (the
                        # registry's own holder keeps them
                        # warm for the retry)
                        for p in self.pool.free(shared):
                            self.registry.drop_page(p)
                        break
                    if shared:
                        self._m_prefix_hits.inc(len(shared))
                    admitted.append((i, self._pending.pop(0),
                                     (pages, shared, cow)))
            pending_depth = len(self._pending)
            self._m_queue_depth.set(pending_depth)
        if self._stop.is_set() and not any(
                s is not None for s in self._slots) and not admitted:
            return True
        if admitted:
            # batch formation: bind each admitted request to its
            # slot (pages granted above; plan chunks here, prefill
            # advances below — interleaved with decode steps).
            # The span carries the admitted requests' trace ids so
            # `trace_main --request` finds the batch work a request
            # rode in (a batch span serves MANY requests — a list,
            # not a single ambient context)
            attrs = {"admitted": len(admitted)}
            if trace.enabled():
                tids = [h.request.trace_id for _, h, _ in admitted
                        if h.request.trace_id]
                if tids:
                    attrs["traces"] = tids
            with trace.span("serve_batch_form", **attrs):
                for i, handle, grant in admitted:
                    self._admit(i, handle, grant)
            self._m_admitted.inc(len(admitted))
        trace.lap("admit")
        # cancellation sweep (running half): a cancelled slot
        # retires NOW — pages back to the pool, the slot to the
        # next queued request — instead of decoding out its budget
        # into the stale-discard bin (slots are engine-thread
        # state; no lock needed)
        for i, s in enumerate(self._slots):
            if s is not None and s.handle._cancel.is_set():
                self._retire(i, cancelled=True)
        trace.lap("sweep")
        # chunked prefill: ONE chunk per iteration TOTAL (round-
        # robin across prefilling slots), so the gap running
        # decodes see is bounded by a single chunk's compute no
        # matter how many prompts are prefilling concurrently
        prefilling = [i for i, s in enumerate(self._slots)
                      if s is not None and s.phase == "prefill"]
        if prefilling:
            nxt = next((i for i in prefilling
                        if i > self._prefill_rr), prefilling[0])
            self._advance_prefill(nxt)
            self._prefill_rr = nxt
            trace.lap("chunk_host")
        active = sum(s is not None for s in self._slots)
        decoding = sum(s is not None and s.phase == "decode"
                       for s in self._slots)
        self.max_concurrent = max(self.max_concurrent, active)
        self._m_occupancy.set(active / self.max_batch)
        self._m_pages_used.set(self.pool.used_pages)
        self._m_pages_reclaimed.set(self.decoder.pages_reclaimed)
        self._m_shared.set(self.pool.shared_refs)
        if active:
            self._m_occ_sampled.observe(active / self.max_batch)
            # pending_depth was read under the lock above — the
            # list mutates under _cond, so len() here would race
            self._m_queue_sampled.observe(pending_depth)
        trace.lap("gauges")
        if decoding:
            self._step()
        else:
            # no running decodes: the next decode-step gap is not a
            # head-of-line measurement
            self._last_step_t = None
        if rec:
            self._iteration_counts(
                it, before, pending_depth, admitted=len(admitted),
                decoding=decoding, prefilling=active - decoding)
        return False

    def _iteration_counts(self, it, before, pending, **counts):
        """The ``serve_iteration`` record's counts (traced runs only):
        requests ``retired`` and ``cancelled`` in this turn (``before``:
        the two counters and the two ordinals at its start), the queue's
        depth as read under
        ``_cond``, the pool's pages in use, and the caller's rows by
        phase.  ``step`` and ``chunk`` are the ordinals, since the engine
        started, of the decode step and of the prefill chunk the turn
        launched (absent where it launched none): a reader of the
        device's timeline pairs the n-th run of a body with them."""
        if self._step_launches != before[2]:
            counts["step"] = self._step_launches
        if self._chunk_launches != before[3]:
            counts["chunk"] = self._chunk_launches
        it.attrs.update(
            counts, pending=pending, pages_used=self.pool.used_pages,
            retired=self._m_completed.value - before[0],
            cancelled=self._m_cancelled.value - before[1])

    def _evict_for(self, need: int):
        """Free registry-only pages (deepest entries first) until
        ``need`` pages are free or nothing evictable remains — cached
        prefixes yield to live traffic, never the other way around.
        Pages any live slot still holds (refcount > 1) are skipped."""
        if not self.prefix_sharing:
            return
        for page in self.registry.pages_by_depth_desc():
            if self.pool.free_pages >= need:
                return
            if self.pool.refcount(page) == 1:
                for p in self.pool.free([page]):
                    self.registry.drop_page(p)
                self._m_evicted.inc()

    def _pages_needed(self, req: ServeRequest) -> int:
        """Worst-case pages for a request: prompt + full budget.
        Reserving up front means a decode step can never OOM the pool
        mid-generation (no preemption machinery needed)."""
        return self.decoder.pages_for(
            int(req.prompt.size) + int(req.max_new_tokens))

    def _admission_plan(self, req: ServeRequest):
        """(shared pages, fresh pages needed, cow) for one request —
        engine thread, under the lock.

        ``shared``: the registry's longest verified chain of this
        prompt's full prefix pages.  ``cow`` is True when the chain
        covers the ENTIRE prompt (plen an exact page multiple, all its
        pages registered): the slot then skips prefill and re-decodes
        its last prompt token for the first-token logits — a write
        into the last shared page, which therefore needs a fresh
        copy-on-write target (+1 fresh page)."""
        total_pages = self._pages_needed(req)
        shared = (self.registry.lookup(req.prompt)
                  if self.prefix_sharing else [])
        cow = bool(shared) and len(shared) * self.page_size >= int(
            req.prompt.size)
        if cow and (self.decoder.carries_state
                    or total_pages + 1 > self.pool.usable_pages):
            # the COW target makes physical demand total_pages + 1 —
            # past the submit guard's total_pages <= usable bound, so
            # a request sized exactly to the pool would LIVELOCK here
            # (its own share holds the chain above eviction's
            # refcount-1 bar).  Degrade: drop the chain's last page
            # and prefill it instead — demand is back to total_pages.
            # A cache that carries state degrades always: the copied
            # page's state entry is already past the token the slot
            # would replay, and the page before it holds the carry the
            # prefill of the last page starts from
            shared = shared[:-1]
            cow = False
        need = total_pages - len(shared) + (1 if cow else 0)
        return shared, need, cow

    def _chunk_plan(self, plen: int, start: int = 0):
        return chunk_plan(plen, self.prefill_chunk, self.page_size, start)

    def _admit(self, slot_idx: int, handle: _Handle, grant):
        req = handle.request
        req.admit_time = time.time()
        fresh, shared, cow = grant
        if req.trace_id is not None:
            # prefix-share depth in TOKENS (pages are an engine
            # detail; the capacity simulator replays recorded hits
            # without knowing this engine's page size)
            trace.event("serve_admit", request=req.id, slot=slot_idx,
                        queue_wait_s=req.admit_time - req.submit_time,
                        shared_tokens=len(shared) * self.page_size,
                        **_tctx(req.trace_id, req.trace_parent))
        plen = int(req.prompt.size)
        ps = self.page_size
        fresh = list(fresh)
        shared = list(shared)
        if cow:
            # the whole prompt is a registered prefix: the slot's only
            # compute is re-decoding its last prompt token (for the
            # first-token logits), and that WRITES position plen−1 —
            # into the last shared page.  Copy-on-write: the write goes
            # to a fresh physical copy; the original stays pristine for
            # its other holders.
            src = shared.pop()
            dst = fresh.pop(0)
            self._cache = self.decoder.copy_page(self._cache, src, dst)
            self._m_cow.inc()
            for p in self.pool.free([src]):   # release our share
                self.registry.drop_page(p)
            logical = shared + [dst] + fresh
        else:
            logical = shared + fresh
        k = len(shared) + (1 if cow else 0)   # depths covered pre-prefill
        block_row = np.zeros((self.decoder.pages_per_slot,), np.int32)
        block_row[:len(logical)] = logical
        # pages this slot must RELEASE at retire: one holder per page
        # it sits on (shared pages decrement, fresh/COW pages free)
        owned = logical
        if cow:
            # no prefill: straight to decode, replaying the last
            # prompt token (its KV write lands in the COW page)
            slot = _Slot(handle=handle, tokens=[],
                         last_token=int(req.prompt[-1]), index=plen - 1,
                         pages=owned, block_row=block_row)
            self._slots[slot_idx] = slot
            return
        plan = self._chunk_plan(plen, start=k * ps)
        padded_len = plan[-1][0] + plan[-1][1]
        prompt_padded = np.zeros((padded_len,), np.int32)
        prompt_padded[:plen] = req.prompt
        self._slots[slot_idx] = _Slot(
            handle=handle, tokens=[], last_token=0, index=0,
            phase="prefill", pages=owned, block_row=block_row,
            prompt_padded=prompt_padded, chunk_plan=plan, chunk_i=0)

    def _advance_prefill(self, slot_idx: int):
        """One chunk of one prefilling slot.  Laps of the turn's span:
        ``chunk_host`` is the host's side (the caller closes the last
        one), ``chunk_sync`` a wait for the chunk's result."""
        slot = self._slots[slot_idx]
        req = slot.handle.request
        start, clen = slot.chunk_plan[slot.chunk_i]
        is_last = slot.chunk_i == len(slot.chunk_plan) - 1
        plen = int(req.prompt.size)
        # the chunk's last real token: its real length less one
        sample_pos = plen - 1 - start if is_last else clen - 1
        t0 = time.perf_counter()
        pre_compiled = self.decoder.compiled_count
        self._chunk_launches += 1
        attrs = _tctx(req.trace_id, req.trace_parent)
        if trace.enabled():
            # which launch this is, of which program, and how much of the
            # padded chunk is prompt: a reader of the device's timeline
            # pairs the program's n-th run with ``chunk``
            attrs.update(chunk=self._chunk_launches,
                         program=self.decoder.program("chunk"),
                         real_tokens=sample_pos + 1)
        with trace.span("serve_prefill_chunk", slot=slot_idx, start=start,
                        tokens=clen, last=is_last, **attrs) as span:
            tok, self._cache, _ = self.decoder.prefill_chunk(
                self._cache, slot.prompt_padded[start:start + clen],
                slot.block_row, start, sample_pos, req.temperature,
                seed=req.rng_seed)
            self._model_counts(span)
        self._m_prefill_chunks.inc()
        slot.chunk_i += 1
        if is_last:
            trace.lap("chunk_host")
            first = int(tok)
            trace.lap("chunk_sync")
            # the int(tok) sync above makes this the one chunk whose
            # wall time spans a real device sync — the only honest
            # sample the MFU ledger takes for the chunk executable
            # (earlier chunks retire asynchronously; syncing them
            # would reintroduce the head-of-line gap chunking bounds).
            # A call that COMPILED is dropped: its wall is XLA, not
            # compute
            if self.decoder.compiled_count == pre_compiled:
                self.ledger.observe(f"serve_prefill_chunk_c{clen}",
                                    time.perf_counter() - t0)
            req.first_token_time = time.time()
            slot.tokens = [first]
            slot.last_token = first
            slot.index = plen
            slot.phase = "decode"
            if self.prefix_sharing and plen // self.page_size:
                # the slot's full prompt pages are now written and
                # immutable (decode writes land past the prompt) —
                # publish them so later admits with the same prefix
                # share instead of re-prefilling.  The registry takes
                # its own holder on each newly-registered page (cache
                # semantics: the prefix survives this request's
                # retire; eviction reclaims it under pool pressure)
                self.pool.share(self.registry.register(
                    req.prompt,
                    [int(p) for p in slot.block_row[: plen // self.page_size]]))
            slot.handle._emit(first)
            if self._finished(slot):
                self._retire(slot_idx)

    def _step(self):
        """One decode step of every decoding row.  Laps of the turn's span:
        ``build`` (the step's arrays), ``launch_args`` (they are packed
        into one host array and sent through the one operands program) and
        ``launch_call`` (the body's call returning; Decoder.decode_step
        closes both), ``ready`` (blocked on the step's tokens: the
        device's time), ``emit`` (the walk after).  Traced, the
        ``serve_decode`` span says what it launched (``step``: the
        launch's ordinal, the turn's; ``program``: the body's name on a
        profile's module line; ``rows`` in phase decode and
        ``context_tokens``, the positions their queries see), and every
        ANCHOR_TURNS-th launch is preceded by a clock anchor."""
        now = time.perf_counter()
        if self._last_step_t is not None:
            self._m_decode_gap.observe(now - self._last_step_t)
        tokens = np.zeros((self.max_batch,), np.int32)
        index = np.zeros((self.max_batch,), np.int32)
        temps = np.zeros((self.max_batch,), np.float32)
        seeds = np.zeros((self.max_batch,), np.uint32)
        tables = np.zeros((self.max_batch, self.decoder.pages_per_slot),
                          np.int32)
        for i, s in enumerate(self._slots):
            if s is not None and s.phase == "decode":
                tokens[i] = s.last_token
                index[i] = s.index
                temps[i] = s.handle.request.temperature
                seeds[i] = s.handle.request.rng_seed
                # prefilling / empty rows keep all-zeros rows →
                # their garbage goes to the scratch page
                tables[i] = s.block_row
        attrs = {}
        if trace.enabled():
            tids = [s.handle.request.trace_id for s in self._slots
                    if s is not None and s.phase == "decode"
                    and s.handle.request.trace_id]
            if tids:
                attrs["traces"] = tids
            decoding = [s.index for s in self._slots
                        if s is not None and s.phase == "decode"]
            attrs.update(step=self._step_launches + 1,
                         program=self.decoder.program("decode"),
                         rows=len(decoding),
                         context_tokens=sum(decoding) + len(decoding))
        self._m_live_pages.observe(
            int((self.decoder.table_index(index) // self.page_size
                 + 1).sum()))
        pre_compiled = self.decoder.compiled_count
        self._step_launches += 1
        trace.lap("build")
        if trace.enabled() and self._step_launches % ANCHOR_TURNS == 0:
            self._clock_anchor()
        with trace.span("serve_decode", **attrs) as span:
            out, self._cache, _ = self.decoder.decode_step(
                self._cache, tokens, index, temps, seeds=seeds,
                block_tables=tables)
            # dtflint: sync-point (the EOS/budget check needs the
            # sampled tokens on the host; the MFU ledger's
            # serve_decode_step wall time is honest BECAUSE this syncs)
            out = self._model_counts(span, out)
            trace.lap("ready")
        step_dt = time.perf_counter() - now
        self._m_step_time.observe(step_dt)
        # MFU ledger: np.asarray(out) above synced the step, so this
        # wall time is real device time, not async dispatch; the step
        # that COMPILED is dropped (its wall is XLA, not compute)
        if self.decoder.compiled_count == pre_compiled:
            self.ledger.observe("serve_decode_step", step_dt)
        # chaos slow_replica@replica<K>:<F>: stretch each decode step to
        # F× its measured time — the straggler-replica signature the
        # router's deadline + least-loaded placement must absorb.  A
        # None-check when chaos is off, like every probe.
        slow = chaos.slow_replica()
        if slow > 1.0:
            time.sleep((slow - 1.0) * step_dt)
        for i, s in enumerate(self._slots):
            if s is None or s.phase != "decode":
                continue
            tok = int(out[i])
            s.tokens.append(tok)
            s.last_token = tok
            s.index += 1
            req = s.handle.request
            if req.first_token_time == 0.0:
                # the COW fast path skips prefill entirely — its first
                # token comes out of this decode step
                req.first_token_time = time.time()
            s.handle._emit(tok)
            if self._finished(s):
                self._retire(i)
        trace.lap("emit")
        self._last_step_t = time.perf_counter()

    def _clock_anchor(self):
        """One stamp in both clocks (traced runs only): a tiny program
        launched AND waited for under a ``clock_anchor`` span (``n``: its
        ordinal; ``program``: its name on a profile's module line), so
        that the run lies between the span's two edges whatever else the
        host's clock and the device's owe each other — the offset between
        them to the program's round trip, with no appeal to the order the
        engine does things in.  Both edges are readings of the turn's own
        clock (obs/trace.py ``_Span``), the one its laps are offsets on.

        WHERE: after ``build``, inside the turn's ``launch_args`` lap and
        before the step's operands are sent — the synchronous loop holds
        the last step's tokens and the chip is idle unless a chunk of this
        turn is still running (the anchor then waits behind it and bounds
        loosely; the window's other anchors bound tightly).  Not at the
        turn's end, where the chip is as idle: the benchmark's causal join
        (``benchmark/readers/host_laps.py`` ``pair``) holds the first
        program to run after a body to start no sooner than the first
        ``chunk_host`` or ``launch_args`` lap after that body's ``ready``,
        and a program launched under any other lap name breaks it."""
        with trace.span("clock_anchor", n=self.decoder.anchors_run + 1,
                        program=self.decoder.program("anchor")):
            self.decoder.clock_anchor()

    def _model_counts(self, span, out=None):
        """``out`` on the host.  With tracing on, what the model counted
        in the call that produced it (``Decoder.last_stats``: one small
        vector named by the model's ``stats_names`` — assignments to
        experts, experts touched, KV tokens read by layer kind; None for
        a model that counts nothing) comes over in the same transfer and
        becomes attributes of ``span``.  In a traced run this makes a
        non-final prefill chunk wait for the device, which an untraced
        one does not: that wait is the turn's ``chunk_sync`` lap (a
        step's is inside its ``ready``, which _step closes).  It is one
        of the two stretches of lap time only a traced run has; the other
        is a clock anchor's round trip at the head of every
        ANCHOR_TURNS-th ``launch_args`` (_clock_anchor)."""
        stats = self.decoder.last_stats if trace.enabled() else None
        if stats is None:
            return None if out is None else np.asarray(out)
        chunk = out is None
        if chunk:
            trace.lap("chunk_host")
        # dtflint: sync-point (traced runs only; see above)
        out, counts = jax.device_get((out, stats["counts"]))
        if chunk:
            trace.lap("chunk_sync")
        span.attrs.update(zip(
            self.decoder.model.call_stats_names(
                span.attrs["tokens"] if chunk else 1),
            (int(c) for c in counts)))
        if self.decoder.summary is not None:
            # what the decoder launched before this call's body
            span.attrs["windows_closed"] = self.decoder.last_closed
        return out

    @staticmethod
    def _finished(slot: _Slot) -> bool:
        req = slot.handle.request
        return (len(slot.tokens) >= req.max_new_tokens
                or (req.eos_id is not None
                    and slot.tokens[-1] == req.eos_id))

    def _finish_cancelled(self, handle: _Handle) -> None:
        """Resolve a cancelled request that never occupied a slot."""
        req = handle.request
        self._m_cancelled.inc()
        if req.trace_id is not None:
            trace.event("serve_cancelled", request=req.id, tokens=0,
                        queued=True,
                        **_tctx(req.trace_id, req.trace_parent))
        handle._deliver(ServeResult(
            request_id=req.id, tokens=[], prompt_len=int(req.prompt.size),
            queue_wait_s=0.0, time_to_first_token_s=0.0, latency_s=0.0,
            submit_time=req.submit_time, finish_time=time.time(),
            cancelled=True, trace_id=req.trace_id))

    def _retire(self, slot_idx: int, cancelled: bool = False):
        slot = self._slots[slot_idx]
        self._slots[slot_idx] = None
        # reclaim: each page loses this slot's holder; pages whose
        # LAST holder left return to the free list, and their
        # prefix-registry entries die with them (the physical page
        # is about to hold someone else's KV)
        for p in self.pool.free(slot.pages):
            self.registry.drop_page(p)
        req = slot.handle.request
        req.finish_time = time.time()
        result = ServeResult(
            request_id=req.id,
            tokens=list(slot.tokens),
            prompt_len=int(req.prompt.size),
            queue_wait_s=req.admit_time - req.submit_time,
            # a slot cancelled mid-prefill never produced a first
            # token — 0.0, not (0.0 − epoch) ≈ −1.7e9
            time_to_first_token_s=(
                req.first_token_time - req.submit_time
                if req.first_token_time else 0.0),
            latency_s=req.finish_time - req.submit_time,
            submit_time=req.submit_time, finish_time=req.finish_time,
            cancelled=cancelled, trace_id=req.trace_id)
        if cancelled:
            # an abandoned answer, not a served one: the pages are
            # reclaimed above, but the request must not pollute the
            # latency/completion statistics real traffic is judged by
            self._m_cancelled.inc()
            if req.trace_id is not None:
                trace.event("serve_cancelled", request=req.id,
                            tokens=len(slot.tokens), queued=False,
                            **_tctx(req.trace_id, req.trace_parent))
            slot.handle._deliver(result)
            with self._cond:
                self._cond.notify_all()
            return
        if req.trace_id is not None:
            trace.event("serve_retire", request=req.id,
                        tokens=len(slot.tokens),
                        latency_s=result.latency_s,
                        **_tctx(req.trace_id, req.trace_parent))
        self._m_completed.inc()
        self._m_latency.observe(result.latency_s)
        self._m_queue_wait.observe(result.queue_wait_s)
        self.completed.append(result)
        slot.handle._deliver(result)
        with self._cond:
            # under the lock: submit's retry_after estimate reads it
            self._ewma_latency = (0.8 * self._ewma_latency
                                  + 0.2 * result.latency_s)
            self._cond.notify_all()

    # -- lifecycle -----------------------------------------------------
    @property
    def draining(self) -> bool:
        with self._cond:
            return self._draining

    def begin_drain(self) -> None:
        """Graceful-shutdown phase 1 (called from the SIGTERM handler,
        so it must be async-signal-tolerant: no blocking lock, no
        logging — the interrupted frame may already hold either lock).
        New submits shed with ``retry_after``; queued and in-flight
        requests keep decoding to completion.  Follow with
        ``stop(drain=True)`` to wait them out and join the engine
        thread — then exit 0: a drained process is a CLEAN exit, not a
        casualty."""
        # dtflint: disable=lock-guard (SIGTERM-handler path: taking
        # _cond here could deadlock against the interrupted frame; the
        # store is GIL-atomic and monotonic, readers see it at their
        # next lock acquisition)
        self._draining = True
        if self._cond.acquire(blocking=False):  # best-effort wake
            try:
                self._cond.notify_all()
            finally:
                self._cond.release()

    def stop(self, drain: bool = True, timeout: float = 60.0):
        """Stop the engine.  ``drain=True`` finishes in-flight AND
        already-queued work first; False cancels queued requests."""
        with self._cond:
            if not drain:
                for handle in self._pending:
                    req = handle.request
                    handle._deliver(ServeResult(
                        request_id=req.id, tokens=[], prompt_len=0,
                        queue_wait_s=0.0, time_to_first_token_s=0.0,
                        latency_s=0.0, cancelled=True))
                self._pending.clear()
            self._stop.set()
            self._cond.notify_all()
        self._thread.join(timeout=timeout)
        # MFU/cost summary into the trace stream (`trace_main --ledger`
        # reads these; the gauges stay live on engine.metrics)
        self.ledger.emit_summary()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.stop(drain=False)
