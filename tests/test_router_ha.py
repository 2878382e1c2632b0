"""Router high availability: request journal, fenced leader lease,
and crash-exact takeover with in-flight re-adoption.

All tier-1: real ReplicaServer instances over the deterministic fake
engine (test_router.py harness), with the router "crash" simulated
in-process by freezing the dying router exactly the way a SIGKILL
leaves it — loops stopped, sockets dropped, nothing resolved, journal
unsynced tail intact.  The real-subprocess path (leader SIGKILLed
mid-burst, standby process takes over) is pinned by
tools/router_ha_smoke.py (ci_check stage 16) and its slow-marked
wrapper below.
"""

import json
import os
import socket
import subprocess
import sys
import threading
import time

import pytest

from dtf_tpu import chaos
from dtf_tpu.serve import ha
from dtf_tpu.serve import journal as journal_mod
from dtf_tpu.serve.router import Router
from test_router import FakeReplica, oracle

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _no_chaos_leak():
    yield
    chaos.disable()


# ---------------------------------------------------------------------------
# journal: replay semantics under the failure modes appends create
# ---------------------------------------------------------------------------

def _jpath(tmp_path):
    return journal_mod.journal_path(str(tmp_path))


def test_journal_roundtrip_and_unresolved(tmp_path):
    j = journal_mod.RequestJournal(_jpath(tmp_path))
    j.submit("1", prompt=[5, 6], max_new_tokens=8, temperature=0.0,
             eos_id=None, rng_seed=42, trace="t1")
    j.dispatch("1", 0, 1)
    j.first_token("1")
    j.watermark("1", 4)
    j.complete("1", ok=True)
    j.submit("2", prompt=[7], max_new_tokens=8, temperature=0.5,
             eos_id=3, rng_seed=7, trace="t2")
    j.dispatch("2", 0, 0)
    j.dispatch("2", 1, 1)          # failover re-dispatch
    j.close()
    state = journal_mod.replay(_jpath(tmp_path))
    assert state["1"]["complete"]["ok"] is True
    assert state["1"]["first_token"] and state["1"]["watermark"] == 4
    left = journal_mod.unresolved(state)
    assert list(left) == ["2"]
    # everything a successor needs to re-dispatch bit-identically
    sub = left["2"]["submit"]
    assert sub["prompt"] == [7] and sub["rng_seed"] == 7
    assert sub["eos_id"] == 3 and sub["temperature"] == 0.5
    # last dispatch wins as the reattach target
    assert left["2"]["dispatches"][-1]["replica"] == 1


def test_journal_torn_tail_dropped(tmp_path):
    j = journal_mod.RequestJournal(_jpath(tmp_path))
    j.submit("1", prompt=[5], max_new_tokens=4, temperature=0.0,
             eos_id=None, rng_seed=1, trace="t")
    j.dispatch("1", 0, 0)
    j.close()
    # the signature of a router killed mid-append: a final line with
    # no newline and truncated JSON
    with open(_jpath(tmp_path), "a", encoding="utf-8") as f:
        f.write('{"t":"complete","id":"1","ok":tr')
    state = journal_mod.replay(_jpath(tmp_path))
    # the torn complete is DROPPED — request 1 is still unresolved,
    # which is the safe direction (a successor finishes it; finishing
    # a finished request is dedupe's job, losing one is forever)
    assert state["1"]["complete"] is None
    assert "1" in journal_mod.unresolved(state)


def test_journal_duplicates_idempotent(tmp_path):
    p = _jpath(tmp_path)
    with open(p, "w", encoding="utf-8") as f:
        for rec in [
            {"t": "submit", "id": "1", "prompt": [5], "max_new_tokens": 4,
             "temperature": 0.0, "eos_id": None, "rng_seed": 1,
             "trace": "a", "ts": 0},
            {"t": "submit", "id": "1", "prompt": [9], "max_new_tokens": 4,
             "temperature": 0.0, "eos_id": None, "rng_seed": 2,
             "trace": "b", "ts": 1},            # duplicate: first wins
            {"t": "watermark", "id": "1", "n": 8, "ts": 2},
            {"t": "watermark", "id": "1", "n": 3, "ts": 3},  # max wins
            {"t": "complete", "id": "1", "ok": True, "ts": 4},
            {"t": "complete", "id": "1", "ok": False, "ts": 5},  # dup
            {"t": "dispatch", "id": "1", "attempt": 9, "replica": 0,
             "ts": 6},                          # post-complete: ignored
            {"t": "complete", "id": "ghost", "ok": True, "ts": 7},
        ]:
            f.write(json.dumps(rec) + "\n")
    state = journal_mod.replay(p)
    st = state["1"]
    assert st["submit"]["prompt"] == [5] and st["submit"]["rng_seed"] == 1
    assert st["watermark"] == 8
    assert st["complete"]["ok"] is True        # first complete wins
    assert st["dispatches"] == []              # none before completion
    assert "ghost" not in state                # complete without submit
    assert journal_mod.unresolved(state) == {}


# ---------------------------------------------------------------------------
# leader lease: mutual exclusion, fencing, stalls
# ---------------------------------------------------------------------------

def test_lease_mutual_exclusion_and_fencing(tmp_path):
    rdir = str(tmp_path)
    a = ha.LeaderLease(rdir, ttl_s=0.3, holder="a")
    b = ha.LeaderLease(rdir, ttl_s=0.3, holder="b")
    assert a.acquire() == 1
    assert b.acquire() is None          # live holder protects the lease
    assert a.renew() is True
    time.sleep(0.45)                    # a stops renewing: lease ages out
    assert b.acquire() == 2             # monotonic epoch bump
    assert a.renew() is False           # the FENCED verdict, latched
    assert a.fenced
    assert a.renew() is False
    b.release()
    assert ha.read_lease(rdir) is None  # clean release frees the lease


def test_lease_stall_chaos_lets_standby_take_over(tmp_path):
    """lease_stall@2 drops exactly two renewal writes — the
    deterministic GC-pause/storage-brownout stand-in — so the lease
    ages out under a perfectly live leader and the standby fences it."""
    rdir = str(tmp_path)
    a = ha.LeaderLease(rdir, ttl_s=0.3, holder="a")
    assert a.acquire() == 1
    ts0 = ha.read_lease(rdir)["ts"]
    chaos.configure("lease_stall@2", rank=0)
    assert a.renew() is True            # tick happens, write doesn't
    assert a.renew() is True
    assert ha.read_lease(rdir)["ts"] == ts0
    time.sleep(0.35)
    b = ha.LeaderLease(rdir, ttl_s=0.3, holder="b")
    epoch = ha.wait_for_takeover(b, poll_s=0.02, timeout_s=5.0)
    assert epoch == 2
    assert a.renew() is False and a.fenced


def test_lease_keeper_fences_router(tmp_path):
    """LeaseKeeper renews in the background and fences its router the
    moment a usurper's epoch appears — /healthz flips out of ok."""
    rdir = str(tmp_path / "rdv")
    rep = FakeReplica(0, rdir).start()
    lease = ha.LeaderLease(rdir, ttl_s=0.2, holder="a")
    assert lease.acquire() == 1
    router = Router(1, rdir, probe_interval_s=0.05, health_timeout_s=0.5,
                    epoch=1)
    router.start(wait_s=10)
    keeper = ha.LeaseKeeper(lease, on_fenced=router.fence).start()
    try:
        h = router.health()
        assert h["ok"] and h["role"] == "leader" and h["epoch"] == 1
        assert h["fenced"] is False
        # a usurper takes the lease by force (operator override path)
        ha.LeaderLease(rdir, ttl_s=0.2, holder="b").acquire(force=True)
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline and not router.health()["fenced"]:
            time.sleep(0.02)
        h = router.health()
        assert h["fenced"] and not h["ok"]
        with pytest.raises(RuntimeError, match="fenced"):
            router.submit([5, 6, 7])
    finally:
        keeper.stop()
        router.stop(drain=False)
        rep.kill()


def test_standby_health_payload(tmp_path):
    lease = ha.LeaderLease(str(tmp_path), ttl_s=0.5, holder="s")
    h = ha.standby_health(lease)
    assert h["ok"] and h["role"] == "standby" and h["epoch"] == 0
    assert h["lease_expired"] is True
    ha.LeaderLease(str(tmp_path), ttl_s=0.5, holder="l").acquire()
    h = ha.standby_health(lease)
    assert h["epoch"] == 1 and h["lease_expired"] is False


# ---------------------------------------------------------------------------
# chaos grammar
# ---------------------------------------------------------------------------

def test_chaos_grammar_router_ha_kinds():
    specs = chaos.parse_spec("router_kill@req:2, lease_stall@3")
    assert [str(s) for s in specs] == ["router_kill@req:2",
                                      "lease_stall@ticks:3"]
    with pytest.raises(ValueError, match="lease_stall"):
        chaos.parse_spec("lease_stall@ticks:0")
    with pytest.raises(ValueError, match="router_kill"):
        chaos.parse_spec("router_kill@latest")


def test_chaos_router_kill_fires_crash_hook(tmp_path):
    """router_kill@req:N crashes the router at its Nth dispatch — in
    process, via the crash hook (the smoke uses the real os._exit)."""
    rdir = str(tmp_path / "rdv")
    rep = FakeReplica(0, rdir, tok_delay=0.001).start()
    crashed = threading.Event()
    router = Router(1, rdir, probe_interval_s=0.05, health_timeout_s=0.5,
                    crash_hook=crashed.set)
    router.start(wait_s=10)
    try:
        chaos.configure("router_kill@req:1", rank=0)
        assert router.generate(
            [5, 6], max_new_tokens=4).tokens == oracle([5, 6], 4)
        router.submit([7, 8], max_new_tokens=4)
        assert crashed.wait(5.0)
    finally:
        router.stop(drain=False)
        rep.kill()


# ---------------------------------------------------------------------------
# takeover: crash-exact re-adoption of in-flight requests
# ---------------------------------------------------------------------------

def _freeze(router):
    """Simulate router death in-process: loops stop, sockets drop,
    NOTHING resolves — the successor recovers from exactly what a
    SIGKILL leaves behind (the replicas keep decoding into their
    retained tails; the journal keeps its unsynced-but-flushed tail)."""
    with router._mu:
        router._stopping = True
        router._mu.notify_all()
    for rep in router._replicas:
        conn = rep.conn
        if conn is not None:
            try:
                # shutdown, not just close: the reader thread holds the
                # socket open through its makefile() wrapper — a real
                # SIGKILL severs the TCP stream, so must this
                conn.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
        router._close_conn(rep)


def _ha_tier(tmp_path, n=2, tok_delay=0.01):
    rdir = str(tmp_path / "rdv")
    os.makedirs(rdir, exist_ok=True)
    reps = [FakeReplica(i, rdir, tok_delay=tok_delay).start()
            for i in range(n)]
    router = Router(n, rdir, probe_interval_s=0.05, health_timeout_s=0.5,
                    deadline_s=30.0, page_size=8,
                    journal_path=journal_mod.journal_path(rdir), epoch=1)
    router.start(wait_s=10)
    return router, reps, rdir


def _collect(handle, out, timeout=0.8):
    """Client-side stream consumer: drains tokens until the request
    resolves or the stream goes silent (= the router died)."""

    def run():
        try:
            for t in handle.stream(timeout=timeout):
                out.append(t)
        except (TimeoutError, RuntimeError):
            pass

    t = threading.Thread(target=run, daemon=True)
    t.start()
    return t


def test_takeover_reattach_exactly_once(tmp_path):
    """Leader dies mid-stream with live replicas: the successor replays
    the journal, REATTACHES each request where its engine kept decoding,
    and with the client-echoed delivered prefix every stream sees each
    token exactly once — full sequence token-exact vs the oracle."""
    router1, reps, rdir = _ha_tier(tmp_path)
    prompts = [[5, 6, 7], [11, 12], [3, 1, 4, 1, 5]]
    n_tok = 48
    try:
        handles = [router1.submit(p, max_new_tokens=n_tok)
                   for p in prompts]
        got = [[] for _ in prompts]
        threads = [_collect(h, g) for h, g in zip(handles, got)]
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline \
                and not all(len(g) >= 4 for g in got):
            time.sleep(0.01)
        assert all(len(g) >= 4 for g in got), "streams never started"
        _freeze(router1)
        for t in threads:
            t.join(timeout=5.0)        # drain everything pre-crash
        delivered = {h.request.id: list(g)
                     for h, g in zip(handles, got)}
        assert all(len(v) < n_tok for v in delivered.values())

        router2 = Router(len(reps), rdir, probe_interval_s=0.05,
                         health_timeout_s=0.5, deadline_s=30.0,
                         page_size=8,
                         journal_path=journal_mod.journal_path(rdir),
                         epoch=2, role="leader")
        router2.start(wait_s=10, adopt=True)
        try:
            summary = ha.take_over(router2, delivered=delivered,
                                   resume_rollout=False)
            # every request found its engine still decoding
            assert summary["readopted"] == len(prompts)
            assert summary["redispatched"] == 0
            for h, p, pre in zip(handles, prompts, got):
                nh = summary["handles"][h.request.id]
                tail = list(nh.stream(timeout=10.0))
                want = oracle(p, n_tok)
                # exactly-once across the death: the resumed stream
                # starts right after the acknowledged prefix
                assert list(pre) + tail == want
                res = nh.result(timeout=10)
                assert res.tokens == want and not res.diverged
        finally:
            router2.stop(drain=False)
    finally:
        router1.stop(drain=False)
        for r in reps:
            r.kill()


def test_takeover_watermark_sentinels_without_client_echo(tmp_path):
    """No client echo on reconnect: the journal's delivery watermark
    seeds -1 sentinels, the reattach replay FILLS them (verify, not
    re-emit), and at most one watermark-cadence of tail re-emits —
    the final token sequence is still exact and undiverged."""
    router1, reps, rdir = _ha_tier(tmp_path)
    prompt, n_tok = [9, 9, 8], 40
    try:
        h = router1.submit(prompt, max_new_tokens=n_tok)
        got = []
        th = _collect(h, got)
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and len(got) < 20:
            time.sleep(0.01)
        assert len(got) >= 20
        _freeze(router1)
        th.join(timeout=5.0)
        # the journal recorded a watermark at the 16-token cadence
        state = journal_mod.replay(journal_mod.journal_path(rdir))
        assert state[str(h.request.id)]["watermark"] >= 16

        router2 = Router(len(reps), rdir, probe_interval_s=0.05,
                         health_timeout_s=0.5, deadline_s=30.0,
                         page_size=8,
                         journal_path=journal_mod.journal_path(rdir),
                         epoch=2)
        router2.start(wait_s=10, adopt=True)
        try:
            summary = ha.take_over(router2, resume_rollout=False)
            assert summary["readopted"] == 1
            nh = summary["handles"][h.request.id]
            res = nh.result(timeout=10)
            assert res.tokens == oracle(prompt, n_tok)
            assert not res.diverged
        finally:
            router2.stop(drain=False)
    finally:
        router1.stop(drain=False)
        for r in reps:
            r.kill()


def test_takeover_dead_replica_falls_to_redispatch(tmp_path):
    """The replica died DURING the router outage: no reattach target,
    so the successor re-dispatches through ordinary budgeted failover —
    the journaled rng_seed replays the stream token-exactly and the
    client-echoed prefix keeps it exactly-once."""
    router1, reps, rdir = _ha_tier(tmp_path)
    prompt, n_tok = [2, 7, 1, 8], 32
    try:
        h = router1.submit(prompt, max_new_tokens=n_tok)
        got = []
        th = _collect(h, got)
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and len(got) < 4:
            time.sleep(0.01)
        assert len(got) >= 4
        _freeze(router1)
        th.join(timeout=5.0)
        # the replica that held it dies during the outage
        state = journal_mod.replay(journal_mod.journal_path(rdir))
        holder = state[str(h.request.id)]["dispatches"][-1]["replica"]
        reps[holder].kill()

        router2 = Router(len(reps), rdir, probe_interval_s=0.05,
                         health_timeout_s=0.5, deadline_s=30.0,
                         page_size=8,
                         journal_path=journal_mod.journal_path(rdir),
                         epoch=2)
        router2.start(wait_s=0, adopt=True)   # can't wait: one is dead
        try:
            survivor = 1 - holder
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline \
                    and not router2.replica_healthy(survivor):
                time.sleep(0.02)
            assert router2.replica_healthy(survivor)
            summary = ha.take_over(
                router2, delivered={h.request.id: list(got)},
                resume_rollout=False)
            assert summary["redispatched"] == 1
            nh = summary["handles"][h.request.id]
            tail = list(nh.stream(timeout=15.0))
            want = oracle(prompt, n_tok)
            assert list(got) + tail == want
            res = nh.result(timeout=10)
            assert res.tokens == want and not res.diverged
            assert res.replica == survivor
        finally:
            router2.stop(drain=False)
    finally:
        router1.stop(drain=False)
        for r in reps:
            try:
                r.kill()
            except Exception:
                pass


def test_takeover_respawned_replica_nacks_then_redispatches(tmp_path):
    """The replica RESTARTED during the outage (healthy, but its
    retained tails died with the old process): reattach gets a nack
    and the request falls to budgeted failover re-dispatch."""
    router1, reps, rdir = _ha_tier(tmp_path)
    prompt, n_tok = [6, 6, 6], 32
    try:
        h = router1.submit(prompt, max_new_tokens=n_tok)
        got = []
        th = _collect(h, got)
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and len(got) < 4:
            time.sleep(0.01)
        assert len(got) >= 4
        _freeze(router1)
        th.join(timeout=5.0)
        state = journal_mod.replay(journal_mod.journal_path(rdir))
        holder = state[str(h.request.id)]["dispatches"][-1]["replica"]
        reps[holder].kill()
        # a fresh process takes the same slot: announces anew, retains
        # nothing
        reps[holder] = FakeReplica(holder, rdir,
                                   tok_delay=0.01).start()

        router2 = Router(len(reps), rdir, probe_interval_s=0.05,
                         health_timeout_s=0.5, deadline_s=30.0,
                         page_size=8,
                         journal_path=journal_mod.journal_path(rdir),
                         epoch=2)
        router2.start(wait_s=10, adopt=True)
        try:
            summary = ha.take_over(
                router2, delivered={h.request.id: list(got)},
                resume_rollout=False)
            # the reattach was SENT (replica looks alive) — the nack
            # converts it to a re-dispatch asynchronously
            nh = summary["handles"][h.request.id]
            tail = list(nh.stream(timeout=15.0))
            want = oracle(prompt, n_tok)
            assert list(got) + tail == want
            res = nh.result(timeout=10)
            assert res.tokens == want and not res.diverged
        finally:
            router2.stop(drain=False)
    finally:
        router1.stop(drain=False)
        for r in reps:
            try:
                r.kill()
            except Exception:
                pass


# ---------------------------------------------------------------------------
# split-brain: the deposed leader is fenced out at the replicas
# ---------------------------------------------------------------------------

def test_stale_epoch_fences_deposed_router(tmp_path):
    """A deposed leader that never noticed (GC pause) keeps driving the
    tier — every replica rejects its epoch-1 ops the moment epoch 2
    appears, the old router latches fenced, and its clients get a
    RuntimeError instead of a possibly-doubled stream."""
    rdir = str(tmp_path / "rdv")
    rep = FakeReplica(0, rdir, tok_delay=0.002).start()
    router1 = Router(1, rdir, probe_interval_s=0.05,
                     health_timeout_s=0.5, epoch=1)
    router1.start(wait_s=10)
    router2 = None
    try:
        assert router1.generate(
            [4, 2], max_new_tokens=4).tokens == oracle([4, 2], 4)
        router2 = Router(1, rdir, probe_interval_s=0.05,
                         health_timeout_s=0.5, epoch=2)
        router2.start(wait_s=10, adopt=True)
        # the successor's first op teaches the replica epoch 2
        assert router2.generate(
            [4, 3], max_new_tokens=4).tokens == oracle([4, 3], 4)
        # the deposed router's next op is rejected → fenced, latched
        with pytest.raises(RuntimeError):
            router1.submit([4, 4], max_new_tokens=4).result(timeout=10)
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline \
                and not router1.health()["fenced"]:
            time.sleep(0.02)
        h = router1.health()
        assert h["fenced"] and not h["ok"]
        with pytest.raises(RuntimeError, match="fenced"):
            router1.submit([4, 5], max_new_tokens=4)
        # the real leader is untouched by the split-brain attempt
        assert router2.generate(
            [4, 6], max_new_tokens=4).tokens == oracle([4, 6], 4)
        assert router2.health()["ok"]
    finally:
        if router2 is not None:
            router2.stop(drain=False)
        router1.stop(drain=False)
        rep.kill()


def test_takeover_resumes_mid_rollout(tmp_path):
    """The leader dies mid-ROLLING with requests in flight: takeover
    re-adopts the streams AND drives the persisted rollout state
    machine forward to DONE (serve/rollout.py resume semantics) —
    deterministically, from the durable state alone."""
    from dtf_tpu.serve import rollout as rollout_mod
    router1, reps, rdir = _ha_tier(tmp_path)
    n_tok = 48

    def hook(rid, ckpt):
        hook_calls.append((rid, ckpt))
        try:
            reps[rid].kill()
        except Exception:
            pass
        # both checkpoints answer identically (salt 0): a re-exported
        # identical model — the token-exact rollout
        reps[rid] = FakeReplica(rid, rdir, tok_delay=0.01).start()

    hook_calls = []
    router2 = None
    try:
        # replica 0 already rolled, as the persisted state claims
        hook(0, "ckpt_new")
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline \
                and not router1.replica_healthy(0):
            time.sleep(0.02)
        state_path = rollout_mod.default_state_path(rdir)
        state = rollout_mod.RolloutState(
            phase="ROLLING", new_checkpoint="ckpt_new",
            old_checkpoint="ckpt_old", canary=0, order=[0, 1],
            rolled=[0])
        with open(state_path, "w") as f:
            json.dump({k: getattr(state, k)
                       for k in state.__dataclass_fields__}, f)

        prompts = [[9, 8, 7], [2, 4, 6]]
        handles = [router1.submit(p, max_new_tokens=n_tok)
                   for p in prompts]
        got = [[] for _ in prompts]
        threads = [_collect(h, g) for h, g in zip(handles, got)]
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline \
                and not all(len(g) >= 4 for g in got):
            time.sleep(0.01)
        assert all(len(g) >= 4 for g in got), "streams never started"
        _freeze(router1)
        for t in threads:
            t.join(timeout=5.0)
        delivered = {h.request.id: list(g)
                     for h, g in zip(handles, got)}

        router2 = Router(len(reps), rdir, probe_interval_s=0.05,
                         health_timeout_s=0.5, deadline_s=30.0,
                         page_size=8,
                         journal_path=journal_mod.journal_path(rdir),
                         epoch=2, role="leader")
        router2.start(wait_s=10, adopt=True)
        summary = ha.take_over(router2, delivered=delivered,
                               restart_hook=hook)
        # the rollout finished forward: replica 1 rolled, phase DONE
        assert summary["rollout_resumed"] == "DONE"
        assert (1, "ckpt_new") in hook_calls, "replica 1 never rolled"
        final = rollout_mod.RolloutState.load(state_path)
        assert final.phase == "DONE" and sorted(final.rolled) == [0, 1]
        # ... and the adopted streams stayed exactly-once token-exact
        assert summary["readopted"] + summary["redispatched"] \
            == len(prompts)
        for h, p, pre in zip(handles, prompts, got):
            nh = summary["handles"][h.request.id]
            tail = list(nh.stream(timeout=20.0))
            assert list(pre) + tail == oracle(p, n_tok)
            res = nh.result(timeout=10)
            assert res.tokens == oracle(p, n_tok) and not res.diverged
    finally:
        if router2 is not None:
            router2.stop(drain=False)
        router1.stop(drain=False)
        for r in reps:
            r.kill()


# ---------------------------------------------------------------------------
# the real-subprocess contract (ci_check stage 16)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_router_ha_smoke_tool_end_to_end():
    """Full smoke: real subprocess tier, leader SIGKILLed mid-burst,
    standby takes over — zero lost requests, zero replica respawns,
    exactly-once token-exact streams, trace check green."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools",
                                      "router_ha_smoke.py")],
        capture_output=True, text=True, timeout=900, cwd=REPO)
    assert proc.returncode == 0, \
        f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
