"""Data-service tests (dtf_tpu/data/service): sharded deterministic
readers, the multi-process worker pool, the decode-once cache tier —
plus the satellites that rode the same PR (reader-lag watchdog,
Prometheus scrape endpoint, metadata preemption poller, flag
validation, and the legacy pipeline's loud resume refusal).

The contract under test, stated once: merged batch ``n`` is a pure
function of ``(seed, process, num_shards, n)`` — invariant to worker
count, process lifetime, and cache state — so ``start_step=n`` replays
the exact stream suffix and killed-at-K resume is bit-exact on
imagenet (the e2e form runs in tools/data_service_smoke.py as a CI
stage; the slow-marked test here drives the same tool).
"""

import io
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest
from PIL import Image

from dtf_tpu import chaos
from dtf_tpu.data import records
from dtf_tpu.data.service import (DecodeCache, ServiceStream, ShardReader,
                                  index_tfrecord_file, make_reader,
                                  shard_positions)
from dtf_tpu.obs.registry import MetricsRegistry
from dtf_tpu.obs.watchdog import ReaderLagWatchdog

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def clean_chaos():
    yield
    chaos.disable()


def _make_jpeg(rng, h=48, w=64):
    arr = rng.integers(0, 256, (h, w, 3), dtype=np.uint8)
    buf = io.BytesIO()
    Image.fromarray(arr).save(buf, format="JPEG", quality=85)
    return buf.getvalue()


def _write_shards(root, num_files=3, per_file=16):
    rng = np.random.default_rng(0)
    for shard in range(num_files):
        recs = []
        for i in range(per_file):
            recs.append(records.build_example({
                "image/encoded": _make_jpeg(rng),
                "image/class/label": [1 + (shard * per_file + i) % 1000],
                "image/object/bbox/ymin": [0.1],
                "image/object/bbox/xmin": [0.1],
                "image/object/bbox/ymax": [0.9],
                "image/object/bbox/xmax": [0.9],
            }))
        records.write_tfrecord_file(
            os.path.join(root, f"train-{shard:05d}-of-01024"), recs)
    return root


@pytest.fixture(scope="module")
def shards_dir(tmp_path_factory):
    return _write_shards(str(tmp_path_factory.mktemp("svc_shards")))


def _collect(stream, n):
    out = [next(stream) for _ in range(n)]
    stream.close()
    return out


def _streams_equal(got, want):
    assert len(got) == len(want)
    for i, ((gi, gl), (wi, wl)) in enumerate(zip(got, want)):
        assert np.array_equal(gi, wi), f"batch {i}: images differ"
        assert np.array_equal(gl, wl), f"batch {i}: labels differ"


# ---------------------------------------------------------------------------
# reader: indexing + position-derived batches
# ---------------------------------------------------------------------------

def test_index_tfrecord_file(shards_dir):
    path = os.path.join(shards_dir, "train-00000-of-01024")
    idx = index_tfrecord_file(path)
    assert len(idx) == 16
    raws = list(records.read_tfrecord_file(path))
    with open(path, "rb") as f:
        for (off, length), raw in zip(idx, raws):
            f.seek(off)
            assert f.read(length) == raw


def test_index_rejects_truncated(tmp_path, shards_dir):
    src = os.path.join(shards_dir, "train-00000-of-01024")
    trunc = tmp_path / "trunc"
    trunc.write_bytes(open(src, "rb").read()[:-7])
    with pytest.raises(IOError):
        index_tfrecord_file(str(trunc))


def test_shard_reader_validation(shards_dir):
    files = sorted(os.path.join(shards_dir, f) for f in os.listdir(shards_dir))
    with pytest.raises(ValueError, match="outside"):
        ShardReader(files, shard=3, num_shards=3, batch_size=4)
    with pytest.raises(ValueError, match="at least one file"):
        ShardReader(files, shard=3, num_shards=4, batch_size=4)
    with pytest.raises(ValueError, match="fewer"):
        # shard 1 of 3 holds one 16-record file < batch 32
        ShardReader(files, shard=1, num_shards=3, batch_size=32)
    with pytest.raises(ValueError, match="wire"):
        ShardReader(files, shard=0, num_shards=3, batch_size=4, wire="u16")


def test_batch_is_pure_function_of_position(shards_dir):
    """The core contract: batch(k) is identical across calls, call
    orders, and reader lifetimes — nothing but position in the key."""
    kw = dict(data_dir=shards_dir, shard=0, num_shards=2, batch_size=4,
              seed=11)
    r1 = make_reader(**kw)
    a7, b7 = r1.batch(7)
    a3, _ = r1.batch(3)      # out-of-order access
    a7b, b7b = r1.batch(7)   # repeat
    r1.close()
    r2 = make_reader(**kw)   # fresh lifetime
    a7c, b7c = r2.batch(7)
    r2.close()
    assert np.array_equal(a7, a7b) and np.array_equal(a7, a7c)
    assert np.array_equal(b7, b7b) and np.array_equal(b7, b7c)
    assert not np.array_equal(a7, a3)  # different position, different batch
    assert a7.dtype == np.uint8 and a7.shape == (4, 224, 224, 3)


def test_epoch_reshuffles_and_seed_rederives(shards_dir):
    r = make_reader(shards_dir, 0, 2, batch_size=4, seed=11)
    assert not np.array_equal(r.order(0), r.order(1))
    r2 = make_reader(shards_dir, 0, 2, batch_size=4, seed=12)
    assert not np.array_equal(r.order(0), r2.order(0))
    r.close()
    r2.close()


def test_shard_positions_round_robin():
    # after n merged batches, shard s owes batch positions such that
    # sum == n and the first n % S shards are one ahead
    assert shard_positions(0, 3) == [0, 0, 0]
    assert shard_positions(7, 3) == [3, 2, 2]
    for n in range(17):
        pos = shard_positions(n, 4)
        assert sum(pos) == n
        assert max(pos) - min(pos) <= 1


# ---------------------------------------------------------------------------
# merged stream: resume replay + worker invariance + chaos respawn
# ---------------------------------------------------------------------------

def test_stream_resume_replays_exact_suffix(shards_dir):
    want = _collect(ServiceStream(shards_dir, 4, seed=3, num_shards=2), 10)
    resumed = ServiceStream(shards_dir, 4, seed=3, num_shards=2,
                            start_step=6)
    assert resumed.position == 6
    _streams_equal(_collect(resumed, 4), want[6:])


def test_stream_num_shards_changes_stream(shards_dir):
    """num_shards is part of the stream identity (what the resume
    validation in cli/runner.py protects)."""
    a = _collect(ServiceStream(shards_dir, 4, seed=3, num_shards=2), 4)
    b = _collect(ServiceStream(shards_dir, 4, seed=3, num_shards=3), 4)
    assert not all(np.array_equal(x[0], y[0]) for x, y in zip(a, b))


def test_auto_worker_count_resolves(shards_dir):
    """num_workers=-1 (the flag default) sizes to the host: one worker
    per core capped by shards, inline on a 1-core box — and never
    touches the stream (pinned by the invariance test below)."""
    s = ServiceStream(shards_dir, 4, seed=1, num_shards=2, num_workers=-1)
    cores = os.cpu_count() or 1
    expect = 0 if cores < 2 else min(2, cores)
    try:
        assert s.num_workers == expect
    finally:
        s.close()


def test_stream_invariant_to_worker_count(shards_dir):
    """Workers decide WHO computes a batch, never WHAT it is: the
    spawned 2-worker pool yields the inline stream bit-exactly."""
    want = _collect(ServiceStream(shards_dir, 4, seed=7, num_shards=3,
                                  num_workers=0), 9)
    got = _collect(ServiceStream(shards_dir, 4, seed=7, num_shards=3,
                                 num_workers=2), 9)
    _streams_equal(got, want)


def test_reader_crash_respawns_with_unchanged_stream(shards_dir):
    """chaos reader_crash@batch:N SIGKILLs the owning shard worker as
    the consumer reaches batch N; the supervisor respawn makes the
    fault invisible to the merged stream."""
    want = _collect(ServiceStream(shards_dir, 4, seed=7, num_shards=2),
                    8)
    chaos.configure("reader_crash@batch:3")
    reg = MetricsRegistry()
    s = ServiceStream(shards_dir, 4, seed=7, num_shards=2, num_workers=1,
                      registry=reg)
    got = _collect(s, 8)
    _streams_equal(got, want)
    assert s.respawns >= 1
    assert reg.get("data_reader_respawns").value >= 1


def test_reader_crash_writes_supervisor_event_with_positions(
        shards_dir, tmp_path, monkeypatch):
    """Under the launcher (DTF_HEARTBEAT_DIR exported), a reader
    respawn appends a `reader_crash` record to supervisor_events.jsonl
    carrying the recorded per-shard positions — post-mortems see the
    data position next to the restart decision."""
    import json

    monkeypatch.setenv("DTF_HEARTBEAT_DIR", str(tmp_path))
    monkeypatch.setenv("DTF_PROCESS_ID", "3")
    chaos.configure("reader_crash@batch:3")
    s = ServiceStream(shards_dir, 4, seed=7, num_shards=2, num_workers=1)
    _collect(s, 8)
    assert s.respawns >= 1
    path = tmp_path / "supervisor_events.jsonl"
    recs = [json.loads(ln) for ln in open(path)]
    crash = [r for r in recs if r["event"] == "reader_crash"]
    assert len(crash) == s.respawns
    r = crash[0]
    assert r["rank"] == 3 and r["worker"] == 0
    # positions recorded per shard, at/after the crash batch — the
    # respawned worker resumes exactly there
    assert set(r["shard_positions"]) == {"0", "1"}
    assert all(isinstance(v, int) and v >= 1
               for v in r["shard_positions"].values())
    assert "ts" in r and r["respawns"] >= 1


def _wait_blocked_in_pipe_write(pid, timeout_s=5.0):
    """True once some thread of ``pid`` sleeps in the kernel's
    pipe_write; False when the kernel does not say (no /proc wchan) —
    the caller has then simply waited ``timeout_s``."""
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        for task in os.listdir(f"/proc/{pid}/task"):
            try:
                with open(f"/proc/{pid}/task/{task}/wchan") as f:
                    if "pipe_write" in f.read():
                        return True
            except OSError:
                pass
        time.sleep(0.05)
    return False


@pytest.mark.parametrize("how", ["terminate", "kill"])
def test_worker_death_mid_write_respawns(shards_dir, how):
    """The invariant, not the luck: a worker killed while a batch is
    half-written into its pipe — the consumer not reading, so the torn
    message SITS there — is seen as dead at the next read, respawned
    once, and the stream carries on bit-exactly.  A batch of 8 is
    1.2 MB of pixels, twenty pipe buffers: a worker nobody reads from
    is always stopped in the middle of one."""
    want = _collect(ServiceStream(shards_dir, 8, seed=7, num_shards=2), 6)
    s = ServiceStream(shards_dir, 8, seed=7, num_shards=2, num_workers=1)
    try:
        got = [next(s), next(s)]
        worker = s._procs[0]
        _wait_blocked_in_pipe_write(worker.pid)
        getattr(worker, how)()
        worker.join(timeout=5.0)
        assert not worker.is_alive()
        t0 = time.monotonic()
        got.append(next(s))
        # detection is the next read, not a poll that times out first;
        # the rest is the respawned worker's start-up
        assert time.monotonic() - t0 < 60 * s.GET_TIMEOUT_S
        got += [next(s) for _ in range(3)]
    finally:
        s.close()
    _streams_equal(got, want)
    assert s.respawns == 1


def test_reader_crash_inline_is_harmless(shards_dir):
    chaos.configure("reader_crash@batch:2")
    want = _collect(ServiceStream(shards_dir, 4, seed=7, num_shards=2), 4)
    assert len(want) == 4  # no worker process to kill; stream proceeds


def _write_torn_shard(root):
    _write_shards(root, num_files=1, per_file=8)
    path = os.path.join(root, "train-00000-of-01024")
    blob = open(path, "rb").read()
    open(path, "wb").write(blob[:-9])  # torn final record
    return root


def test_worker_error_surfaces_loudly(tmp_path):
    """A deterministic reader failure (corrupt shard) must raise in the
    consumer, not burn the respawn budget silently."""
    with pytest.raises(OSError, match="truncated"):
        ServiceStream(_write_torn_shard(str(tmp_path)), 4, num_shards=1,
                      num_workers=0)


def test_worker_error_reaches_the_consumer(tmp_path):
    """The same failure inside a worker process: its message comes
    through the pipe before the process exits, and the consumer raises
    it at once instead of respawning a reader that fails every time."""
    s = ServiceStream(_write_torn_shard(str(tmp_path)), 4, num_shards=1,
                      num_workers=1)
    try:
        with pytest.raises(RuntimeError, match="worker 0 failed.*truncated"):
            next(s)
        assert s.respawns == 0
    finally:
        s.close()


# ---------------------------------------------------------------------------
# decode-once cache tier
# ---------------------------------------------------------------------------

def test_cache_epoch2_bit_identical_and_served_from_cache(shards_dir,
                                                         tmp_path):
    """Cached and uncached runs are bit-identical by construction, and
    epoch >= 2 is served from the cache (libjpeg skipped)."""
    bare = make_reader(shards_dir, 0, 2, batch_size=4, seed=5)
    bpe = bare.batches_per_epoch
    want = [bare.batch(k) for k in range(2 * bpe)]
    bare.close()
    cached = make_reader(shards_dir, 0, 2, batch_size=4, seed=5,
                         cache_dir=str(tmp_path))
    for k, (wi, wl) in enumerate(want):
        gi, gl = cached.batch(k)
        assert np.array_equal(gi, wi) and np.array_equal(gl, wl), k
    hits, lookups = cached.cache_stats()
    assert lookups == 2 * bpe * 4
    assert hits >= bpe * 4  # the whole second epoch (at least) hit
    cached.close()


def test_cache_survives_reopen_and_drops_torn_tail(tmp_path):
    rng = np.random.default_rng(0)
    img_a = rng.integers(0, 256, (8, 9, 3), dtype=np.uint8)
    img_b = rng.integers(0, 256, (6, 7, 3), dtype=np.uint8)
    c = DecodeCache(str(tmp_path), shard=0, limit_bytes=0)
    assert c.put(0, img_a, 17, np.array([[0.1, 0.2, 0.3, 0.4]], np.float32))
    assert c.put(1, img_b, 23, None)
    assert not c.put(1, img_b, 23, None)  # dup insert is a no-op
    c.close()
    # torn mid-put crash: payload bytes of record 1 cut short
    with open(c.data_path, "r+b") as f:
        f.truncate(img_a.nbytes + 10)
    c2 = DecodeCache(str(tmp_path), shard=0, limit_bytes=0)
    img, label, bbox = c2.get(0)
    assert np.array_equal(img, img_a) and label == 17
    assert bbox.shape == (1, 4) and abs(bbox[0][2] - 0.3) < 1e-6
    assert c2.get(1) is None  # torn entry dropped, a miss not a crash
    c2.close()


def test_cache_limit_stops_inserting(tmp_path):
    rng = np.random.default_rng(0)
    img = rng.integers(0, 256, (8, 8, 3), dtype=np.uint8)
    c = DecodeCache(str(tmp_path), shard=0, limit_bytes=img.nbytes + 1)
    assert c.put(0, img, 1, None)
    assert not c.put(1, img, 2, None)  # would exceed the bound
    assert c.get(0) is not None and c.get(1) is None
    c.close()


def test_cache_identity_is_in_the_filename(tmp_path):
    """The same directory reused with a different sharding must build a
    FRESH cache (the key is the shard-local record index)."""
    a = DecodeCache(str(tmp_path), 0, 0, num_shards=2)
    b = DecodeCache(str(tmp_path), 0, 0, num_shards=4)
    assert a.data_path != b.data_path
    rng = np.random.default_rng(0)
    a.put(0, rng.integers(0, 256, (4, 4, 3), dtype=np.uint8), 1, None)
    assert b.get(0) is None  # no cross-contamination
    a.close()
    b.close()


# ---------------------------------------------------------------------------
# observability: lag gauge + watchdog, Prometheus endpoint
# ---------------------------------------------------------------------------

def test_stream_exports_lag_and_hit_gauges(shards_dir, tmp_path):
    reg = MetricsRegistry()
    s = ServiceStream(shards_dir, 4, seed=1, num_shards=2,
                      cache_dir=str(tmp_path), registry=reg)
    _collect(s, 4)
    assert reg.get("data_reader_lag_s").value >= 0.0
    assert "data_cache_hit_ratio" in reg.names()


def test_reader_lag_watchdog_flags_stall_over_floor():
    wd = ReaderLagWatchdog(factor=10.0, min_lag_s=0.5, warmup=4)
    for i in range(8):
        assert not wd.observe(i, 0.01)
    # 40x the median but under the absolute floor: jitter, not a page
    assert not wd.observe(8, 0.4)
    assert wd.observe(9, 0.9)
    assert wd.trigger_count == 1
    # the triggering value is not absorbed into the baseline
    assert wd.observe(10, 0.9)


def test_reader_lag_watchdog_validates():
    with pytest.raises(ValueError):
        ReaderLagWatchdog(factor=1.0)


def test_prometheus_text_and_scrape():
    import urllib.request
    from dtf_tpu.obs.prom import MetricsServer, prometheus_text
    reg = MetricsRegistry()
    reg.gauge("data_reader_lag_s", unit="s").set(0.25)
    reg.counter("data_reader_respawns").inc(2)
    reg.histogram("step_s", unit="s").observe(0.5)
    text = prometheus_text(reg)
    assert "# TYPE data_reader_lag_s gauge" in text
    assert "data_reader_lag_s 0.25" in text
    assert "# TYPE data_reader_respawns counter" in text
    assert 'step_s{quantile="0.5"}' in text
    assert "step_s_count 1" in text
    srv = MetricsServer(0, registry_fn=lambda: reg)
    try:
        url = f"http://127.0.0.1:{srv.port}"
        body = urllib.request.urlopen(f"{url}/metrics").read().decode()
        assert "data_reader_lag_s 0.25" in body
        reg.gauge("data_reader_lag_s", unit="s").set(0.5)  # live, not frozen
        body = urllib.request.urlopen(f"{url}/metrics").read().decode()
        assert "data_reader_lag_s 0.5" in body
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(f"{url}/nope")
    finally:
        srv.shutdown()


# ---------------------------------------------------------------------------
# metadata preemption poller
# ---------------------------------------------------------------------------

def _fake_metadata_server(state):
    from http.server import BaseHTTPRequestHandler, HTTPServer

    class Handler(BaseHTTPRequestHandler):
        def do_GET(self):
            assert self.headers.get("Metadata-Flavor") == "Google"
            self.send_response(200)
            self.end_headers()
            self.wfile.write(state["body"])

        def log_message(self, *a):
            pass

    httpd = HTTPServer(("127.0.0.1", 0), Handler)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    return httpd


def test_metadata_poller_latches_preemption():
    from dtf_tpu.train import preemption
    state = {"body": b"FALSE"}
    httpd = _fake_metadata_server(state)
    url = f"http://127.0.0.1:{httpd.server_address[1]}/"
    guard = preemption.install()
    poller = preemption.MetadataPoller(0.05, url=url).start()
    try:
        time.sleep(0.2)
        assert preemption.triggered() is None
        state["body"] = b"TRUE"
        deadline = time.time() + 5.0
        while preemption.triggered() is None and time.time() < deadline:
            time.sleep(0.05)
        assert preemption.triggered() is not None
        assert poller.preempted
    finally:
        poller.stop()
        preemption.restore()
        httpd.shutdown()


def test_metadata_poller_unreachable_is_quiet():
    from dtf_tpu.train import preemption
    poller = preemption.MetadataPoller(0.05, url="http://127.0.0.1:9/x")
    assert poller.poll_once() is False  # connection refused != preempted
    with pytest.raises(ValueError):
        preemption.MetadataPoller(0.0)


# ---------------------------------------------------------------------------
# flags + legacy pipeline refusal
# ---------------------------------------------------------------------------

def test_config_validates_service_flags():
    from dtf_tpu.config import Config
    Config(input_num_shards=4, input_workers=2,
           input_cache_dir="/tmp/x", input_cache_limit_mb=64,
           metrics_port=9000, preemption_poll_s=5.0)
    with pytest.raises(ValueError, match="input_num_shards"):
        Config(input_num_shards=0)
    Config(input_workers=-1)  # -1 = auto-size to the host
    with pytest.raises(ValueError, match="input_workers"):
        Config(input_workers=-2)
    with pytest.raises(ValueError, match="input_cache_limit_mb"):
        Config(input_cache_limit_mb=64)  # limit without a cache dir
    with pytest.raises(ValueError, match="metrics_port"):
        Config(metrics_port=70000)
    with pytest.raises(ValueError, match="preemption_poll_s"):
        Config(preemption_poll_s=-1.0)


def test_legacy_imagenet_resume_refused(shards_dir):
    """The old re-key-best-effort path is GONE: the threaded pipeline
    refuses a mid-stream train resume loudly (the data service is the
    position-exact path)."""
    from dtf_tpu.data.imagenet import imagenet_input_fn
    with pytest.raises(ValueError, match="input_service"):
        imagenet_input_fn(shards_dir, True, 4, process_id=0,
                          process_count=1, start_step=3)


# ---------------------------------------------------------------------------
# e2e: killed-at-K imagenet resume (the CI smoke, driven as a test)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_e2e_imagenet_killed_at_k_bit_identical():
    """Synthetic-shard imagenet run killed at step 4 under the
    supervisor, resumed with a different worker count: per-step loss
    trajectory bit-identical to uninterrupted (closing the PR-4
    imagenet leftover).  Full contract in tools/data_service_smoke.py
    — also wired as a tools/ci_check.sh stage."""
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools",
                                      "data_service_smoke.py")],
        capture_output=True, timeout=600)
    assert r.returncode == 0, (r.stdout.decode()[-2000:]
                               + r.stderr.decode()[-2000:])
