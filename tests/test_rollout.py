"""Zero-downtime rollout: state machine, canary divergence gate,
drain/replace mechanics, resume-from-persisted-state, and rollout
chaos — all tier-1 over the jax-free fake replica tier from
test_router (the real-checkpoint, real-subprocess path is pinned by
tools/rollout_smoke.py, ci_check stage 11).

The fake models checkpoints as an oracle SALT: ``ckpt_old`` and
``ckpt_new_same`` answer identically (a re-exported identical
checkpoint — the token-exact rollout), ``ckpt_new_div`` answers
differently (a genuinely different model — the canary gate must catch
it), ``ckpt_bad`` cannot start at all (a truncated/corrupt artifact).
"""

import json
import os
import threading
import time

import numpy as np
import pytest

from test_router import FakeReplica, oracle, stop_tier

from dtf_tpu import chaos
from dtf_tpu.serve.rollout import (RolloutController, RolloutError,
                                   RolloutState, _truncate_checkpoint)
from dtf_tpu.serve.router import Router

OLD = "ckpt_old"
NEW_SAME = "ckpt_new_same"
NEW_DIV = "ckpt_new_div"
BAD = "ckpt_bad"
SALTS = {OLD: 0, NEW_SAME: 0, NEW_DIV: 7, "": 0}


@pytest.fixture(autouse=True)
def _no_chaos_leak():
    yield
    chaos.disable()


def make_rollout_tier(tmp_path, n=2):
    """Fake tier + a restart_hook that swaps a replica's engine for
    one serving the named checkpoint's salt (BAD starts nothing —
    the unserveable-artifact case)."""
    rdir = str(tmp_path / "rdv")
    os.makedirs(rdir, exist_ok=True)
    reps = [FakeReplica(i, rdir, tok_delay=0.004).start()
            for i in range(n)]
    router = Router(n, rdir, probe_interval_s=0.05,
                    health_timeout_s=0.4, deadline_s=30.0,
                    replica_inflight=32, page_size=8,
                    kill_hook=lambda rid: reps[rid].kill())
    router.start(wait_s=10)
    hook_calls = []

    def hook(rid, ckpt):
        hook_calls.append((rid, ckpt))
        try:
            reps[rid].kill()
        except Exception:
            pass
        if ckpt == BAD:
            return          # the new checkpoint cannot even start
        reps[rid] = FakeReplica(rid, rdir, tok_delay=0.004,
                                salt=SALTS[ckpt]).start()

    return router, reps, hook, hook_calls


def controller(router, hook, ckpt, tmp_path, **kw):
    args = dict(old_checkpoint=OLD, canary_requests=2,
                mirror_fraction=1.0, warm_timeout_s=8.0,
                drain_timeout_s=15.0, gate_timeout_s=20.0,
                restart_hook=hook, poll_s=0.02,
                state_path=str(tmp_path / "rollout_state.json"))
    args.update(kw)
    return RolloutController(router, ckpt, **args)


class Pump:
    """Continuous greedy traffic during a rollout: submits on a
    cadence, resolves everything at stop — the zero-lost ledger."""

    def __init__(self, router, interval=0.03, budget=6):
        self.router = router
        self.interval = interval
        self.budget = budget
        rng = np.random.default_rng(17)
        self.prompts = [rng.integers(0, 97, (5 + i % 4,))
                        .astype(np.int32) for i in range(6)]
        self._handles = []
        self._shed = 0
        self._attempted = threading.Event()
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)

    def _run(self):
        from dtf_tpu.serve.engine import Backpressure
        i = 0
        while not self._stop.wait(self.interval):
            p = self.prompts[i % len(self.prompts)]
            try:
                self._handles.append(
                    (p, self.router.submit(p,
                                           max_new_tokens=self.budget)))
            except Backpressure:
                self._shed += 1
            self._attempted.set()
            i += 1

    def __enter__(self):
        self._thread.start()
        return self

    def __exit__(self, *exc):
        # a rollout can be over before the thread's first cadence tick
        # (a resumed one takes ~60 ms; on a loaded host the tick comes
        # later): stop on a condition, not on the clock
        self._attempted.wait(timeout=5)
        self._stop.set()
        self._thread.join(timeout=5)

    def assert_zero_lost_token_exact(self, salt=0):
        assert self._shed == 0, f"{self._shed} requests shed mid-rollout"
        assert self._handles, "the pump never submitted"
        for p, h in self._handles:
            r = h.result(timeout=60)   # lost = the one forbidden outcome
            assert r.tokens == oracle(p, self.budget, salt=salt), (
                f"request diverged from the salt-{salt} model "
                f"(replica {r.replica}, version {r.version})")


# ---------------------------------------------------------------------------
# state machine
# ---------------------------------------------------------------------------

def test_state_machine_legal_transitions(tmp_path):
    s = RolloutState()
    assert s.phase == "IDLE"
    s.advance("CANARY")
    s.advance("ROLLING")
    s.advance("DONE")
    s2 = RolloutState()
    s2.advance("CANARY")
    s2.advance("ROLLED_BACK", reason="canary_divergence")
    assert s2.reason == "canary_divergence"
    s3 = RolloutState()
    s3.advance("CANARY")
    s3.advance("ROLLING")
    s3.advance("ROLLED_BACK", reason="replica_lost")
    assert s3.phase == "ROLLED_BACK"


@pytest.mark.parametrize("chain,bad", [
    ((), "ROLLING"),                      # IDLE cannot skip the canary
    ((), "DONE"),
    ((), "ROLLED_BACK"),
    (("CANARY",), "DONE"),                # the gate cannot be skipped
    (("CANARY", "ROLLING"), "CANARY"),    # no going back
    (("CANARY", "ROLLED_BACK"), "ROLLING"),   # terminal
    (("CANARY", "ROLLING", "DONE"), "ROLLED_BACK"),  # terminal
])
def test_state_machine_illegal_transitions(chain, bad):
    s = RolloutState()
    for phase in chain:
        s.advance(phase)
    with pytest.raises(RolloutError):
        s.advance(bad)


def test_state_persist_roundtrip(tmp_path):
    path = str(tmp_path / "state.json")
    s = RolloutState(new_checkpoint="/n", old_checkpoint="/o",
                     canary=0, order=[0, 1, 2], rolled=[0, 1],
                     compared=5, diverged=1, first_divergence_pos=3)
    s.advance("CANARY")
    s.save(path)
    back = RolloutState.load(path)
    assert back == s
    # atomic write: no tmp litter
    assert [f for f in os.listdir(tmp_path)] == ["state.json"]


def test_truncate_checkpoint_halves_largest_file(tmp_path):
    big = tmp_path / "ckpt" / "payload.bin"
    small = tmp_path / "ckpt" / "meta.json"
    os.makedirs(tmp_path / "ckpt")
    big.write_bytes(b"x" * 1000)
    small.write_bytes(b"y" * 10)
    _truncate_checkpoint(str(tmp_path / "ckpt"))
    assert big.stat().st_size == 500
    assert small.stat().st_size == 10


# ---------------------------------------------------------------------------
# the rollout itself (fake tier)
# ---------------------------------------------------------------------------

def test_rollout_identical_checkpoint_completes_zero_lost(tmp_path):
    """A mid-traffic rollout to an identical checkpoint: DONE, zero
    shed/lost, every request token-exact, no mixed-model streams,
    whole fleet on the new version."""
    router, reps, hook, _ = make_rollout_tier(tmp_path)
    try:
        with Pump(router) as pump:
            time.sleep(0.2)     # traffic flowing before the rollout
            state = controller(router, hook, NEW_SAME, tmp_path).run()
            time.sleep(0.2)     # and after it
        assert state.phase == "DONE"
        assert state.compared >= 2 and state.diverged == 0
        pump.assert_zero_lost_token_exact(salt=0)
        assert router.metrics.get("router_mixed_model_total").value == 0
        for rid in range(2):
            assert router.replica_version(rid) == NEW_SAME
            assert router.replica_healthy(rid)
        # durable state says DONE too (the resume contract's ground)
        persisted = RolloutState.load(str(tmp_path /
                                          "rollout_state.json"))
        assert persisted.phase == "DONE"
        assert sorted(persisted.rolled) == [0, 1]
    finally:
        stop_tier(router, reps)


def test_rollout_divergent_checkpoint_gated_rollback(tmp_path):
    """A genuinely different model: the token-exact canary gate fires
    on live mirrored traffic and the rollout auto-rolls-back — fleet
    token-exact on the OLD model, zero lost."""
    router, reps, hook, _ = make_rollout_tier(tmp_path)
    try:
        with Pump(router) as pump:
            time.sleep(0.2)
            state = controller(router, hook, NEW_DIV, tmp_path).run()
            time.sleep(0.2)
        assert state.phase == "ROLLED_BACK"
        assert state.reason.startswith("canary_divergence")
        assert state.diverged >= 1
        assert state.first_divergence_pos >= 0
        assert state.rolled == [], "rollback left replicas on the new model"
        pump.assert_zero_lost_token_exact(salt=0)
        for rid in range(2):
            assert router.replica_version(rid) == OLD
            assert router.replica_healthy(rid)
        # the canary's divergent tokens were SHADOWS — never delivered
        assert router.metrics.get("router_mixed_model_total").value == 0
    finally:
        stop_tier(router, reps)


def test_rollout_unserveable_checkpoint_rolls_back(tmp_path):
    """A new checkpoint that cannot even start a replica (truncated /
    corrupt artifact): the canary never re-registers, the rollout
    rolls back, the fleet stands on the old model."""
    router, reps, hook, hook_calls = make_rollout_tier(tmp_path)
    try:
        with Pump(router) as pump:
            state = controller(router, hook, BAD, tmp_path,
                               warm_timeout_s=1.5).run()
        assert state.phase == "ROLLED_BACK"
        assert state.reason == "canary_start_failed"
        pump.assert_zero_lost_token_exact(salt=0)
        # the rollback re-ran the hook with the OLD checkpoint
        assert (0, OLD) in hook_calls
        for rid in range(2):
            assert router.replica_healthy(rid)
            assert router.replica_version(rid) == OLD
    finally:
        stop_tier(router, reps)


def test_rollout_gate_timeout_rolls_back(tmp_path):
    """No traffic → no comparisons → the gate cannot pass; it times
    out into a rollback rather than promoting an unproven model."""
    router, reps, hook, _ = make_rollout_tier(tmp_path)
    try:
        state = controller(router, hook, NEW_SAME, tmp_path,
                           gate_timeout_s=0.8).run()
        assert state.phase == "ROLLED_BACK"
        assert state.reason.startswith("canary_timeout")
        for rid in range(2):
            assert router.replica_version(rid) == OLD
    finally:
        stop_tier(router, reps)


def test_rollout_kill_canary_phase_rolls_back(tmp_path):
    """rollout_kill@phase:canary: the canary dies mid-gate; the
    rollout detects the instability and rolls back — zero lost,
    fleet on the old model."""
    chaos.configure("rollout_kill@phase:canary", rank=0)
    router, reps, hook, _ = make_rollout_tier(tmp_path)
    try:
        with Pump(router) as pump:
            state = controller(router, hook, NEW_SAME, tmp_path).run()
        assert state.phase == "ROLLED_BACK"
        pump.assert_zero_lost_token_exact(salt=0)
        for rid in range(2):
            assert router.replica_healthy(rid)
            assert router.replica_version(rid) == OLD
    finally:
        stop_tier(router, reps)


def test_rollout_kill_rolling_phase_rolls_back(tmp_path):
    """rollout_kill@phase:rolling: a serving replica dies after the
    gate passed; policy is abort — the canary (already on the new
    model) re-drains back onto the old checkpoint."""
    chaos.configure("rollout_kill@phase:rolling", rank=0)
    router, reps, hook, _ = make_rollout_tier(tmp_path)
    try:
        with Pump(router) as pump:
            state = controller(router, hook, NEW_SAME, tmp_path).run()
        assert state.phase == "ROLLED_BACK"
        assert state.compared >= 2 and state.diverged == 0, (
            "the gate should have PASSED before the rolling kill")
        pump.assert_zero_lost_token_exact(salt=0)
        for rid in range(2):
            assert router.replica_healthy(rid)
            assert router.replica_version(rid) == OLD
        assert router.metrics.get("router_mixed_model_total").value == 0
    finally:
        stop_tier(router, reps)


# ---------------------------------------------------------------------------
# resume from persisted state (the router-restart-mid-rollout case)
# ---------------------------------------------------------------------------

def _write_state(tmp_path, **kw):
    path = str(tmp_path / "rollout_state.json")
    state = RolloutState(**kw)
    with open(path, "w") as f:
        json.dump(
            {k: getattr(state, k) for k in state.__dataclass_fields__},
            f)
    return path


def test_resume_mid_rolling_finishes_forward(tmp_path):
    """Persisted ROLLING + a rolled canary: a fresh router resumes
    FORWARD — the remaining replica rolls, phase reaches DONE."""
    router, reps, hook, hook_calls = make_rollout_tier(tmp_path)
    try:
        # replica 0 already on the new checkpoint, as the state claims.
        # Swapped the way the dead router had swapped it (held, closed,
        # re-announced): a bare hook() kills it under the router's feet,
        # replica_healthy(0) stays True until the dead socket is noticed,
        # and whether that falls inside the resume (-> "replica0_lost",
        # ROLLED_BACK) is the host's load, not the code under test
        router.hold_replica(0)
        router.terminate_replica(0)
        hook(0, NEW_SAME)
        router.allow_reconnect(0)
        path = _write_state(tmp_path, phase="ROLLING",
                            new_checkpoint=NEW_SAME, old_checkpoint=OLD,
                            canary=0, order=[0, 1], rolled=[0])
        t0 = time.monotonic()
        while not router.replica_healthy(0) and time.monotonic() - t0 < 5:
            time.sleep(0.02)
        assert router.replica_healthy(0)
        router.release_replica(0)
        with Pump(router) as pump:
            state = RolloutController.resume(
                router, path, restart_hook=hook, warm_timeout_s=8.0,
                drain_timeout_s=15.0, poll_s=0.02)
        assert state.phase == "DONE"
        assert sorted(state.rolled) == [0, 1]
        assert (1, NEW_SAME) in hook_calls, "replica 1 never rolled"
        pump.assert_zero_lost_token_exact(salt=0)
        for rid in range(2):
            assert router.replica_version(rid) == NEW_SAME
    finally:
        stop_tier(router, reps)


def test_resume_mid_canary_rolls_back(tmp_path):
    """Persisted CANARY: an interrupted canary proved nothing — the
    deterministic resume verdict is ROLLBACK, canary restored onto
    the old checkpoint."""
    router, reps, hook, hook_calls = make_rollout_tier(tmp_path)
    try:
        hook(0, NEW_SAME)   # the canary the dead router had replaced
        path = _write_state(tmp_path, phase="CANARY",
                            new_checkpoint=NEW_SAME, old_checkpoint=OLD,
                            canary=0, order=[0, 1], rolled=[0])
        t0 = time.monotonic()
        while not router.replica_healthy(0) and time.monotonic() - t0 < 5:
            time.sleep(0.02)
        state = RolloutController.resume(
            router, path, restart_hook=hook, warm_timeout_s=8.0,
            drain_timeout_s=15.0, poll_s=0.02)
        assert state.phase == "ROLLED_BACK"
        assert state.reason == "resumed_mid_canary"
        assert state.rolled == []
        assert (0, OLD) in hook_calls
        for rid in range(2):
            assert router.replica_version(rid) == OLD
    finally:
        stop_tier(router, reps)


def test_resume_rolled_back_finishes_rollback(tmp_path):
    """Persisted ROLLED_BACK with a replica still on the new model
    (the controller died mid-rollback): resume finishes the rollback."""
    router, reps, hook, hook_calls = make_rollout_tier(tmp_path)
    try:
        hook(1, NEW_SAME)
        path = _write_state(tmp_path, phase="ROLLED_BACK",
                            new_checkpoint=NEW_SAME, old_checkpoint=OLD,
                            canary=0, order=[0, 1], rolled=[1],
                            reason="canary_divergence")
        t0 = time.monotonic()
        while not router.replica_healthy(1) and time.monotonic() - t0 < 5:
            time.sleep(0.02)
        state = RolloutController.resume(
            router, path, restart_hook=hook, warm_timeout_s=8.0,
            drain_timeout_s=15.0, poll_s=0.02)
        assert state.phase == "ROLLED_BACK"
        assert state.rolled == []
        assert (1, OLD) in hook_calls
        for rid in range(2):
            assert router.replica_version(rid) == OLD
    finally:
        stop_tier(router, reps)


def test_resume_done_is_noop(tmp_path):
    router, reps, hook, hook_calls = make_rollout_tier(tmp_path)
    try:
        path = _write_state(tmp_path, phase="DONE",
                            new_checkpoint=NEW_SAME, old_checkpoint=OLD,
                            canary=0, order=[0, 1], rolled=[0, 1])
        state = RolloutController.resume(router, path,
                                         restart_hook=hook)
        assert state.phase == "DONE"
        assert hook_calls == []
    finally:
        stop_tier(router, reps)


# ---------------------------------------------------------------------------
# the real-subprocess + real-checkpoint matrix (ci_check stage 11)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_rollout_smoke_tool_end_to_end():
    """tools/rollout_smoke.py: real replica subprocesses serving real
    exported checkpoints — identical rollout DONE token-exact, gated
    rollback on a divergent checkpoint, rollout_kill + ckpt_truncate
    chaos both ROLLED_BACK, zero shed/lost/mixed throughout."""
    import subprocess
    import sys as _sys
    proc = subprocess.run(
        [_sys.executable,
         os.path.join(os.path.dirname(os.path.dirname(
             os.path.abspath(__file__))), "tools", "rollout_smoke.py")],
        capture_output=True, text=True, timeout=1500,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, (
        f"rollout smoke failed\nstdout:\n{proc.stdout[-4000:]}\n"
        f"stderr:\n{proc.stderr[-4000:]}")


def test_rollout_refuses_wrong_old_checkpoint(tmp_path):
    """The old_checkpoint contract is enforced: a second rollout that
    names an old checkpoint the fleet does not actually serve is
    refused up front — 'rolling back' to it would split the tier
    across two models while reporting success."""
    router, reps, hook, _ = make_rollout_tier(tmp_path)
    try:
        with Pump(router):
            state = controller(router, hook, NEW_SAME, tmp_path).run()
        assert state.phase == "DONE"
        with pytest.raises(RolloutError, match="old checkpoint"):
            # fleet serves NEW_SAME now; declaring OLD is a lie
            controller(router, hook, NEW_DIV, tmp_path,
                       old_checkpoint=OLD).run()
        # the honest declaration is accepted (and gets gated normally)
        with Pump(router) as pump:
            state = controller(router, hook, NEW_DIV, tmp_path,
                               old_checkpoint=NEW_SAME).run()
        assert state.phase == "ROLLED_BACK"
        pump.assert_zero_lost_token_exact(salt=0)
    finally:
        stop_tier(router, reps)


def test_rollout_refuses_single_replica_tier(tmp_path):
    """A 1-replica tier cannot roll: the shadow-only canary would be
    the only replica — every request would queue into its deadline
    and the traffic-fed gate could never complete.  Refused up
    front."""
    router, reps, hook, _ = make_rollout_tier(tmp_path, n=1)
    try:
        with pytest.raises(RolloutError, match="1-replica"):
            controller(router, hook, NEW_SAME, tmp_path).run()
    finally:
        stop_tier(router, reps)


def test_rollout_refuses_unstable_fleet(tmp_path):
    """A rollout is a planned maneuver: it refuses to START on a fleet
    with a dead replica (recover first, then roll)."""
    router, reps, hook, _ = make_rollout_tier(tmp_path)
    try:
        reps[1].kill()
        t0 = time.monotonic()
        while router.replica_healthy(1) and time.monotonic() - t0 < 5:
            time.sleep(0.02)
        with pytest.raises(RolloutError, match="unhealthy"):
            controller(router, hook, NEW_SAME, tmp_path).run()
    finally:
        stop_tier(router, reps)
