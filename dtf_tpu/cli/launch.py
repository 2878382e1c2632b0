"""Multi-process launcher — the one-command replacement for the
reference's deployment story.

The reference needed 16 near-identical per-rank script copies plus an
ssh fan-out loop (`ps_server/run.sh`: ssh root@host python …_ps_$i.py
2>log$i.log &, 1 s stagger) and a pkill teardown (`kill.sh`), because
each rank's TF_CONFIG had to be hardcoded (SURVEY §3.4, §7.9).  Here
per-process identity is env config, so one parameterized command does
it all:

Local fan-out (all processes on this host — CPU mesh testing; on a
TPU host the fan-out is refused, because nothing here assigns chips and
one process already drives every local chip):

    python -m dtf_tpu.cli.launch --num_processes 4 -- \
        python -m dtf_tpu.cli.cifar_main --distribution_strategy \
        multi_worker_mirrored ...

Cluster fan-out (prints — or runs with --execute via ssh — one command
per host; horovodrun -H parity):

    python -m dtf_tpu.cli.launch --hosts h1,h2,h3,h4 -- \
        python -m dtf_tpu.cli.imagenet_main ...

Per-rank stderr/stdout goes to <log_dir>/log{rank}.log (run.sh parity).
On any rank failing, all ranks are torn down (kill.sh parity) and the
launcher exits non-zero.
"""

from __future__ import annotations

import collections
import glob
import json
import os
import shlex
import signal
import subprocess
import sys
import time
from typing import List, Optional

# Heartbeat-file contract, duplicated from dtf_tpu/obs/watchdog.py ON
# PURPOSE: the supervisor's own logic stays stdlib-only — the process
# that kills and restarts broken ML ranks should not depend on the obs
# package it supervises (`import dtf_tpu` itself imports nothing).
# tests/test_obs.py asserts the two sides agree on the contract.
HEARTBEAT_DIR_ENV = "DTF_HEARTBEAT_DIR"

# Exit-code contract with dtf_tpu/train/preemption.py, dtf_tpu/chaos
# and dtf_tpu/train/elastic.py — duplicated here for the same
# stdlib-only reason (parity is pinned by tests/test_chaos.py and
# tests/test_elastic.py).  A rank exiting EXIT_PREEMPTED performed a
# graceful preemption checkpoint: the supervisor restarts it WITHOUT
# consuming the crash-restart budget and without backoff (the work is
# durable; waiting helps nobody).  A rank exiting EXIT_DEVICE_LOST saw
# its accelerators vanish while the host survived: under --elastic the
# supervisor RESHARDS (relaunch on the surviving topology) instead of
# burning the crash budget on a fault no restart-at-size can fix.  Any
# other nonzero exit (including death by signal — negative Popen
# returncodes) is a crash: budgeted, with exponential backoff — except
# an UNPROMPTED SIGKILL (one this supervisor did not send), which is
# the host-loss rank-exit pattern: the OOM-killer or the host going
# away, never a python crash.
EXIT_PREEMPTED = 75
EXIT_DEVICE_LOST = 76

# Env var + rendezvous-file contract with dtf_tpu/train/elastic.py
# (canonical constants live there; parity test-pinned).  The supervisor
# exports the surviving device total so a relaunched rank can verify
# the topology it actually attached matches the supervisor's
# accounting; a healed host's agent (or the elastic smoke) re-announces
# capacity by writing {"devices": N} into <log_dir>/elastic_rejoin.json
# — the grow-back probe consumes it at the next checkpoint boundary.
ELASTIC_DEVICES_ENV = "DTF_ELASTIC_DEVICES"
REJOIN_FILE = "elastic_rejoin.json"


def local_tpu_chips() -> int:
    """TPU chips this host exposes, counted from their device nodes
    (``/dev/accel*``, or one numbered ``/dev/vfio`` group each) —
    without importing JAX: a supervisor that initialised a backend
    would hold the very chips its children need."""
    return len(glob.glob("/dev/accel*")) + sum(
        os.path.basename(p).isdigit() for p in glob.glob("/dev/vfio/*"))


def refuse_shared_chips(children: int, env: dict, what: str) -> None:
    """A chip belongs to one process.  Nothing here assigns chips to
    children: each of ``children`` local processes started with ``env``
    would initialise every chip the host exposes, and all but the
    first would fail or hang.  Refuse that before spawning anything.
    Children held to the CPU (``JAX_PLATFORMS`` without ``tpu`` — the
    virtual-device mesh) share nothing and pass."""
    if children <= 1:
        return
    platforms = env.get("JAX_PLATFORMS", "")
    if platforms and "tpu" not in platforms.split(","):
        return
    chips = local_tpu_chips()
    if chips:
        raise RuntimeError(
            f"{what}: {children} local processes on a host with {chips} "
            f"TPU chip(s) would each claim the same chips (no chip "
            f"assignment is implemented; one process must own each "
            f"chip).  Run ONE process per host — it drives every local "
            f"chip — or set JAX_PLATFORMS=cpu for a virtual-device CPU "
            f"mesh")


def classify_exit(rc: int) -> str:
    if rc == 0:
        return "ok"
    if rc == EXIT_PREEMPTED:
        return "preempted"
    if rc == EXIT_DEVICE_LOST:
        return "device_loss"
    return "crash"


def read_rejoin(log_dir: str):
    """Announced rejoin capacity (device count), or None when absent,
    torn, or malformed — ANY unreadable announce reads as 'not yet',
    never as a grow (and never as a supervisor crash: this runs inside
    the monitor loop)."""
    try:
        with open(os.path.join(log_dir, REJOIN_FILE)) as f:
            doc = json.load(f)
        if not isinstance(doc, dict):
            return None
        return int(doc.get("devices", 0))
    except (OSError, ValueError, TypeError):
        return None


class SupervisorEventLog:
    """Append-only ``supervisor_events.jsonl`` in the log dir: one JSON
    record per supervision decision (rank exits with classification,
    heartbeat kills, restarts with backoff + budget state, give-ups) —
    post-mortems read this instead of scraping log{N}.retry{M}.log
    filenames.  Best-effort: a full disk must not take down the
    supervisor with the job."""

    def __init__(self, log_dir: str):
        self.path = os.path.join(log_dir, "supervisor_events.jsonl")

    def emit(self, event: str, **attrs) -> None:
        rec = {"ts": time.time(), "event": event}
        rec.update(attrs)
        try:
            with open(self.path, "a") as f:
                f.write(json.dumps(rec) + "\n")
        except OSError:
            pass


def heartbeat_path(directory: str, rank: int) -> str:
    return os.path.join(directory, f"heartbeat_rank{rank}.json")


def read_heartbeat(path: str):
    """Parse a heartbeat file; None when missing/torn (treated as 'no
    heartbeat signal', not as death — log growth still counts)."""
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, json.JSONDecodeError):
        return None


def build_env(rank: int, world: int, coordinator: str,
              devices_per_process: Optional[int] = None,
              heartbeat_dir: Optional[str] = None,
              generation: int = 0,
              trace_id: Optional[str] = None,
              elastic_devices: Optional[int] = None) -> dict:
    env = dict(os.environ)
    env["DTF_COORDINATOR"] = coordinator
    env["DTF_PROCESS_ID"] = str(rank)
    env["DTF_PROCESS_COUNT"] = str(world)
    if trace_id:
        # run-scoped trace id: every rank (and every restart attempt)
        # of one supervised job shares it, so their trace records join
        # one timeline (`trace_main --request <id>`).  The runner
        # installs it as the process default trace
        # (obs/trace.set_default_trace).  Unconditional: the per-job id
        # is authoritative here — operator intent (an exported
        # DTF_TRACE_ID) was already folded in when the job minted it,
        # and a stale var lingering in os.environ must not fuse two
        # jobs' timelines.
        env["DTF_TRACE_ID"] = trace_id
    # restart generation (= supervisor attempt): the async-PS snapshot
    # tags its done_count with this, so a whole-job restart discards
    # the stale generation's DONE tally instead of double-counting it
    # (dtf_tpu/parallel/ps.py GENERATION_ENV — duplicated string for
    # the same stdlib-only reason as the contracts above; parity is
    # pinned by tests/test_ps.py)
    env["DTF_RESTART_GENERATION"] = str(generation)
    if heartbeat_dir:
        # ranks running dtf_tpu mains rewrite
        # <log_dir>/heartbeat_rank{N}.json at a bounded interval
        # (obs/watchdog.Heartbeat) — the supervisor's structured
        # liveness signal, replacing stdout-size scraping
        env[HEARTBEAT_DIR_ENV] = os.path.abspath(heartbeat_dir)
    if elastic_devices:
        # elastic supervision: the surviving device TOTAL this attempt
        # was sized for — the runner verifies its attached topology
        # against it (train/elastic.note_elastic_resume) so a relaunch
        # that silently got a different mesh than the supervisor
        # accounted for fails loudly instead of training mis-sharded
        env[ELASTIC_DEVICES_ENV] = str(elastic_devices)
    if devices_per_process:
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                            f" --xla_force_host_platform_device_count="
                            f"{devices_per_process}")
    return env


def _run_once(cmd: List[str], num_processes: int, coordinator: str,
              log_dir: str, devices_per_process: Optional[int],
              stagger_s: float = 0.0,
              heartbeat_timeout: Optional[float] = None,
              attempt: int = 0, startup_grace: float = 300.0,
              events: Optional[SupervisorEventLog] = None,
              teardown_grace: float = 60.0,
              trace_id: Optional[str] = None,
              grow_check=None,
              elastic_devices: Optional[int] = None):
    """One supervised attempt.  Returns ``(rc, classification, grew)``:
    the first failing rank's exit code and REFINED classification
    (heartbeat-lost kills and unprompted SIGKILLs read as host loss,
    EXIT_DEVICE_LOST as device loss), and whether ``grow_check`` fired
    — in which case the attempt was deliberately drained (SIGTERM ⇒
    emergency checkpoints ⇒ the preempted exit) so the caller can
    relaunch at the restored topology."""
    os.makedirs(log_dir, exist_ok=True)
    if events is None:
        events = SupervisorEventLog(log_dir)
    events.emit("attempt_start", attempt=attempt, ranks=num_processes,
                devices_per_process=devices_per_process)
    # teardown escalation state: once a failure SIGTERMs the survivors,
    # they get `teardown_grace` seconds to emergency-checkpoint and
    # exit; a rank wedged in a dead collective (or ignoring SIGTERM)
    # is then hard-killed — without this the monitor loop would wait
    # on it forever (the finally's kill only runs after the loop ends)
    term_at: Optional[float] = None
    procs = []  # (rank, Popen)
    logs = []
    rc = 0
    first_cls = "ok"
    grew = False
    # kill attribution for host-loss classification: ranks THIS
    # supervisor SIGKILLed (heartbeat loss, teardown escalation) vs an
    # unprompted SIGKILL from outside (OOM-killer, the host vanishing)
    hb_killed: set = set()
    td_killed: set = set()
    # hang watchdog state: last time each rank showed life — via its
    # heartbeat file (structured, preferred) or its log growing
    # (fallback ONLY for ranks that have never emitted a heartbeat: once
    # a rank has beaten, log growth stops counting, so a rank whose log
    # grows from a side thread while its training thread is deadlocked
    # is still caught)
    sizes = [0] * num_processes
    hb_ts = [None] * num_processes   # last heartbeat payload ts seen
    hb_mtime = [None] * num_processes  # stat gate: parse only on change
    last_beat = [0.0] * num_processes
    spawned = [0.0] * num_processes
    # restart attempts keep earlier logs (the first failure is usually
    # the informative one): log0.log, then log0.retry1.log, ...
    suffix = f".retry{attempt}" if attempt else ""
    log_path = lambda rank: os.path.join(log_dir, f"log{rank}{suffix}.log")
    try:
        for rank in range(num_processes):
            # a heartbeat file surviving a previous attempt must not
            # masquerade as this attempt's first beat
            try:
                os.unlink(heartbeat_path(log_dir, rank))
            except OSError:
                pass
            f = open(log_path(rank), "wb")
            logs.append(f)
            p = subprocess.Popen(
                cmd, env=build_env(rank, num_processes, coordinator,
                                   devices_per_process,
                                   heartbeat_dir=log_dir,
                                   generation=attempt,
                                   trace_id=trace_id,
                                   elastic_devices=elastic_devices),
                stdout=f, stderr=subprocess.STDOUT)
            procs.append((rank, p))
            last_beat[rank] = spawned[rank] = time.monotonic()
            if stagger_s:
                time.sleep(stagger_s)  # run.sh's 1 s stagger, now optional
        while procs:
            for rank, p in list(procs):
                ret = p.poll()
                if ret is None:
                    if heartbeat_timeout:
                        # liveness: the rank's heartbeat file advanced
                        # (obs/watchdog beats at a bounded interval even
                        # when nothing logs — e.g. mid-epoch with a long
                        # --log_steps); ranks that never beat fall back
                        # to log growth.  Quiet past the timeout means a
                        # hung collective or deadlock — the failure mode
                        # the reference could only resolve by hand with
                        # kill.sh
                        now = time.monotonic()
                        # mtime gate: beats land every heartbeat_secs at
                        # most, so one stat per poll replaces an
                        # open+parse per poll
                        try:
                            mt = os.stat(
                                heartbeat_path(log_dir, rank)).st_mtime
                        except OSError:
                            mt = hb_mtime[rank]
                        if mt != hb_mtime[rank]:
                            hb_mtime[rank] = mt
                            hb = read_heartbeat(
                                heartbeat_path(log_dir, rank))
                            if (hb is not None
                                    and hb.get("ts") != hb_ts[rank]):
                                hb_ts[rank] = hb.get("ts")
                                last_beat[rank] = now
                        try:
                            sz = os.path.getsize(log_path(rank))
                        except OSError:
                            sz = sizes[rank]
                        if sz != sizes[rank]:
                            sizes[rank] = sz
                            # log growth is liveness only until the
                            # first heartbeat: after that, a growing log
                            # with a stale heartbeat is the deadlocked-
                            # but-chatty signature, not life
                            if hb_ts[rank] is None:
                                last_beat[rank] = now
                        if (now - last_beat[rank] > heartbeat_timeout
                                # a rank in first XLA compile /
                                # checkpoint restore legitimately logs
                                # nothing for minutes — give every rank
                                # a startup grace before the heartbeat
                                # rule applies
                                and now - spawned[rank] > startup_grace):
                            print(f"rank {rank} heartbeat lost "
                                  f"({heartbeat_timeout:.0f}s without "
                                  f"{'a heartbeat' if hb_ts[rank] is not None else 'log output'}"
                                  f"); killing", file=sys.stderr)
                            events.emit("heartbeat_lost", attempt=attempt,
                                        rank=rank,
                                        timeout_s=heartbeat_timeout)
                            # heartbeat silence is the host-loss
                            # signature (a dead host stops beating long
                            # before any exit code arrives) — remember
                            # the kill so the exit classifies as
                            # host_loss, not as our own SIGKILL
                            hb_killed.add(rank)
                            p.kill()
                    continue
                procs.remove((rank, p))
                cls = classify_exit(ret)
                if rank in hb_killed:
                    cls = "host_loss"
                elif (ret < 0 and -ret == signal.SIGKILL
                        and rank not in td_killed):
                    # an unprompted SIGKILL: this supervisor did not
                    # send it, and a python crash cannot exit via
                    # SIGKILL on its own — the OOM-killer or the host
                    # going away, i.e. host loss
                    cls = "host_loss"
                events.emit("rank_exit", attempt=attempt, rank=rank,
                            code=ret, classification=cls,
                            log=log_path(rank))
                if ret != 0:
                    if rc == 0:  # keep the FIRST failure's code + class
                        rc = ret
                        first_cls = cls
                    print(f"rank {rank} exited {ret} "
                          f"({cls}; see "
                          f"{log_path(rank)}); tearing down",
                          file=sys.stderr)
                    for _, q in procs:  # kill.sh parity — SIGTERM first
                        # so dtf mains can emergency-checkpoint (the
                        # preemption path); hard kill after
                        # teardown_grace below
                        q.send_signal(signal.SIGTERM)
                    if term_at is None:
                        term_at = time.monotonic()
            if (term_at is not None and procs
                    and time.monotonic() - term_at > teardown_grace):
                for r2, q in procs:
                    print(f"rank {r2} still alive {teardown_grace:.0f}s "
                          f"after teardown SIGTERM; killing",
                          file=sys.stderr)
                    events.emit("teardown_kill", attempt=attempt, rank=r2,
                                grace_s=teardown_grace)
                    td_killed.add(r2)
                    q.kill()
                term_at = None  # killed; the loop reaps their exits
            if (grow_check is not None and not grew and term_at is None
                    and procs and grow_check()):
                # capacity re-announced while running shrunken: drain
                # the job at a CHECKPOINT BOUNDARY (SIGTERM ⇒ the
                # preemption path's emergency sealed checkpoint at the
                # next step boundary ⇒ exit 75) and let the caller
                # relaunch at the restored topology
                grew = True
                events.emit("grow_triggered", attempt=attempt)
                print("elastic: capacity re-announced — draining for a "
                      "grow-back relaunch at the next checkpoint "
                      "boundary", file=sys.stderr)
                for _, q in procs:
                    q.send_signal(signal.SIGTERM)
                term_at = time.monotonic()
            time.sleep(0.2)
    finally:
        for _, q in procs:
            q.kill()
        for f in logs:
            f.close()
    return rc, first_cls, grew


def launch_local(cmd: List[str], num_processes: int, coordinator: str,
                 log_dir: str, devices_per_process: Optional[int],
                 stagger_s: float = 0.0, max_restarts: int = 0,
                 heartbeat_timeout: Optional[float] = None,
                 startup_grace: float = 300.0,
                 restart_window_s: float = 3600.0,
                 restart_backoff_s: float = 1.0,
                 max_preemptions: int = 100,
                 teardown_grace: float = 60.0,
                 elastic: bool = False, min_devices: int = 1,
                 max_elastic: int = 16) -> int:
    """Run the job, supervising it.

    On any rank failing (or hanging, with ``heartbeat_timeout``), tear
    down and relaunch ALL ranks — the sync-SPMD recovery unit is the
    whole job, with progress carried by checkpoints (pair the training
    command with ``--resume``).  The reference's recovery story was
    manual: per-epoch checkpoints plus an operator running kill.sh and
    re-running run.sh (SURVEY §5.3).

    Exit-code classification drives the restart policy:

      preempted (EXIT_PREEMPTED, 75) — the rank wrote a durable
          emergency checkpoint before exiting: relaunch immediately,
          WITHOUT consuming the crash budget (capped only by
          ``max_preemptions``, a runaway-loop backstop).  Only when
          supervision was actually requested (``max_restarts`` > 0 or a
          ``heartbeat_timeout``): an unsupervised launch whose operator
          SIGTERMs it must STOP, not resurrect itself 100 times.
      device_loss (EXIT_DEVICE_LOST, 76) / host_loss (heartbeat-lost
          kill, or an UNPROMPTED SIGKILL — the OOM-killer / the host
          vanishing) — with ``elastic`` set, these are TOPOLOGY losses,
          not crashes: restarting at the same size would fail the same
          way, so the supervisor SHRINKS instead (host loss drops the
          lost host's worth of ranks; device loss halves the local
          device count — the finest granularity an emulated topology
          can report), relaunches on the surviving mesh at the last
          checkpoint, and refuses LOUDLY when the result would fall
          below ``min_devices``.  The training command resolves its own
          parallelization against whatever it attaches (``--plan auto``
          re-plans; mirrored re-meshes), so the GLOBAL batch and step
          semantics are invariant across the shrink.  Capped by
          ``max_elastic`` (a flapping-fabric backstop), never by the
          crash budget.  While shrunken, the supervisor probes
          ``<log_dir>/elastic_rejoin.json`` (a healed host's agent — or
          an operator — re-announces capacity there): once the
          announced device count covers the full topology again, the
          job is DRAINED at a checkpoint boundary (SIGTERM ⇒ emergency
          sealed checkpoint ⇒ exit 75) and relaunched at full size —
          preemption becomes a throughput dip, not an outage.  Without
          ``elastic`` both classifications fall back to the budgeted
          crash policy (the label still lands in the event log).
      crash (any other nonzero, incl. death by signal) — budgeted:
          ``max_restarts`` crashes per sliding ``restart_window_s``
          window (a long healthy run earns its budget back — unlike
          the old lifetime counter, where a week of uptime and a
          crash-loop looked the same), with exponential backoff
          ``restart_backoff_s × 2^(n-1)`` between relaunches.

    Every decision lands in ``<log_dir>/supervisor_events.jsonl``.
    """
    refuse_shared_chips(num_processes, os.environ, "launch")
    os.makedirs(log_dir, exist_ok=True)
    # run-scoped trace id, minted ONCE for the whole supervised job and
    # handed to every rank (and every restart attempt) through
    # build_env — all ranks' trace records share it, so `trace_main
    # --request <id>` joins the cross-rank timeline.  An
    # operator-exported DTF_TRACE_ID wins (correlate with an outer
    # orchestrator); otherwise a local variable, not os.environ — an
    # in-process caller launching several jobs (tests) must not have
    # them share one id.  Stdlib-only (os.urandom), matching
    # obs/trace.new_trace_id().
    run_trace_id = os.environ.get("DTF_TRACE_ID") or os.urandom(8).hex()
    events = SupervisorEventLog(log_dir)
    supervising = (bool(max_restarts) or heartbeat_timeout is not None
                   or elastic)
    if elastic and not devices_per_process and num_processes <= 1:
        raise ValueError(
            "--elastic needs a topology the supervisor can shrink: "
            "--devices_per_process (local/virtual device count) or "
            "--num_processes > 1")
    # elastic topology state: the full (launch-time) topology and the
    # current surviving one.  dpp=None means "whatever is attached" —
    # it counts as 1 for totals so the multi-process host-loss lever
    # still works without a device count.
    dpp1 = lambda d: d if d else 1
    full_procs, full_dpp = num_processes, devices_per_process
    cur_procs, cur_dpp = num_processes, devices_per_process
    full_total = full_procs * dpp1(full_dpp)
    losses = 0
    if elastic:
        # a rejoin announce surviving a PREVIOUS job must not trigger
        # an instant spurious grow
        try:
            os.unlink(os.path.join(log_dir, REJOIN_FILE))
        except OSError:
            pass
    attempt = 0
    preemptions = 0
    crash_times: collections.deque = collections.deque()
    while True:
        cur_total = cur_procs * dpp1(cur_dpp)
        grow_check = None
        if elastic and cur_total < full_total:
            grow_check = (lambda need=full_total:
                          (read_rejoin(log_dir) or 0) >= need)
        rc, cls, grew = _run_once(
            cmd, cur_procs, coordinator, log_dir,
            cur_dpp, stagger_s, heartbeat_timeout,
            attempt=attempt, startup_grace=startup_grace,
            events=events, teardown_grace=teardown_grace,
            trace_id=run_trace_id, grow_check=grow_check,
            # only exported when the supervisor actually KNOWS the
            # device total (devices_per_process set): in multi-process
            # mode without it, cur_total counts ranks, not devices,
            # and the runner's topology verification against it would
            # wrongly refuse any rank attaching more than one device
            elastic_devices=(cur_total if elastic and cur_dpp
                             else None))
        if grew and rc != 0:
            # deliberately drained for growth (the expected exits are
            # 75 after the emergency checkpoint): restore the full
            # topology, consume the announce, relaunch outside the
            # crash budget.  A rank that died DIRTY during the drain
            # (anything but preempted) is recorded honestly — the
            # relaunch still resumes from the last SEALED checkpoint,
            # losing at most the boundary save, and the loop is
            # bounded because each grow needs a fresh shrink, which
            # max_elastic caps.
            try:
                os.unlink(os.path.join(log_dir, REJOIN_FILE))
            except OSError:
                pass
            cur_procs, cur_dpp = full_procs, full_dpp
            attempt += 1
            events.emit("elastic_grow", restart=attempt, procs=cur_procs,
                        devices_per_process=cur_dpp,
                        total_devices=full_total,
                        drain_classification=cls)
            if cls != "preempted":
                print(f"elastic: grow-back drain exited DIRTY "
                      f"({cls}, rc {rc}) — the boundary checkpoint may "
                      f"be missing; resuming from the last sealed one",
                      file=sys.stderr)
            print(f"elastic: growing back to {full_total} device(s) "
                  f"({cur_procs} rank(s)) — restart {attempt}",
                  file=sys.stderr)
            continue
        if cls == "ok" or rc == 0:
            events.emit("job_done", attempts=attempt)
            return 0
        if elastic and cls in ("device_loss", "host_loss"):
            losses += 1
            if losses > max_elastic:
                events.emit("give_up", code=rc, classification=cls,
                            losses=losses, max_elastic=max_elastic)
                print(f"giving up: {losses} topology losses exceed "
                      f"--max_elastic {max_elastic} (flapping fabric?)",
                      file=sys.stderr)
                return rc
            if cls == "host_loss" and cur_procs > 1:
                # the lost host's ranks are gone; its devices with it
                new_procs, new_dpp = cur_procs - 1, cur_dpp
            elif dpp1(cur_dpp) > 1:
                # device loss (or a single-process host emulation):
                # halve the local device count — the finest surviving-
                # capacity granularity an exit code can report
                new_procs, new_dpp = cur_procs, dpp1(cur_dpp) // 2
            else:
                new_procs, new_dpp = cur_procs - 1, cur_dpp
            new_total = new_procs * dpp1(new_dpp)
            if new_procs < 1 or new_total < min_devices:
                events.emit("give_up", code=rc, classification=cls,
                            reason="min_devices",
                            surviving_devices=new_total,
                            min_devices=min_devices)
                print(f"giving up: {cls} would shrink the job to "
                      f"{new_total} device(s), below the --min_devices "
                      f"floor ({min_devices}) — refusing to resume "
                      f"that small; waiting for capacity is the "
                      f"operator's call", file=sys.stderr)
                return rc
            cur_procs, cur_dpp = new_procs, new_dpp
            attempt += 1
            events.emit("elastic_shrink", classification=cls,
                        restart=attempt, procs=cur_procs,
                        devices_per_process=cur_dpp,
                        total_devices=new_total, losses=losses,
                        max_elastic=max_elastic)
            print(f"elastic: {cls} — resuming smaller on {new_total} "
                  f"device(s) ({cur_procs} rank(s)) at the last "
                  f"checkpoint (restart {attempt}; crash budget "
                  f"untouched)", file=sys.stderr)
            continue
        if cls == "preempted":
            if not supervising:
                events.emit("give_up", code=rc, classification=cls,
                            reason="unsupervised")
                print("job preempted; not supervising (no --max_restarts/"
                      "--heartbeat_timeout) — exiting", file=sys.stderr)
                return rc
            preemptions += 1
            if preemptions > max_preemptions:
                events.emit("give_up", code=rc, classification=cls,
                            preemptions=preemptions,
                            max_preemptions=max_preemptions)
                print(f"giving up: {preemptions} preemptions exceed "
                      f"--max_preemptions {max_preemptions}",
                      file=sys.stderr)
                return rc
            attempt += 1
            events.emit("restart", classification=cls, restart=attempt,
                        backoff_s=0.0, preemptions=preemptions,
                        crashes_in_window=len(crash_times),
                        budget=max_restarts)
            print(f"relaunching all {cur_procs} ranks after "
                  f"preemption (restart {attempt}; crash budget "
                  f"untouched)", file=sys.stderr)
            continue
        # crash — including device/host loss WITHOUT --elastic (the
        # honest label still landed in the event log, but the policy
        # without an elastic mandate is the plain budgeted restart):
        # sliding-window budget + exponential backoff
        now = time.monotonic()
        while crash_times and now - crash_times[0] > restart_window_s:
            crash_times.popleft()
        if len(crash_times) >= max_restarts:
            events.emit("give_up", code=rc, classification=cls,
                        crashes_in_window=len(crash_times),
                        window_s=restart_window_s, budget=max_restarts)
            return rc
        crash_times.append(now)
        backoff = restart_backoff_s * (2.0 ** (len(crash_times) - 1))
        attempt += 1
        events.emit("restart", classification=cls, restart=attempt,
                    backoff_s=backoff, crashes_in_window=len(crash_times),
                    window_s=restart_window_s, budget=max_restarts)
        print(f"relaunching all {cur_procs} ranks (crash "
              f"{len(crash_times)}/{max_restarts} in window; backoff "
              f"{backoff:.1f}s)", file=sys.stderr)
        if backoff > 0:
            time.sleep(backoff)


def cluster_commands(cmd: List[str], hosts: List[str], coordinator: str,
                     log_dir: str, background: bool = True) -> List[str]:
    """One ssh line per host — the run.sh loop, generated.

    `background` appends `&` for manual copy-paste use; --execute mode
    passes False so ssh blocks until the remote rank exits and its
    status is observable."""
    world = len(hosts)
    quoted = " ".join(shlex.quote(c) for c in cmd)
    # one run-scoped trace id for the WHOLE cluster job (same contract
    # as launch_local): every host's rank inherits it, so their trace
    # records join one timeline.  An operator-exported DTF_TRACE_ID
    # wins — correlate with an outer orchestrator by exporting it.
    trace_id = os.environ.get("DTF_TRACE_ID") or os.urandom(8).hex()
    lines = []
    for rank, host in enumerate(hosts):
        envs = (f"DTF_COORDINATOR={coordinator} DTF_PROCESS_ID={rank} "
                f"DTF_PROCESS_COUNT={world} DTF_TRACE_ID={trace_id}")
        logfile = shlex.quote(f"{log_dir}/log{rank}.log")
        remote = (f"mkdir -p {shlex.quote(log_dir)} && {envs} {quoted} "
                  f"> {logfile} 2>&1")
        if background:
            remote += " &"
        lines.append(f"ssh {host} {shlex.quote(remote)}")
    return lines


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if "--" not in argv:
        print(__doc__)
        return 2
    split = argv.index("--")
    opts, cmd = argv[:split], argv[split + 1:]

    num_processes, coordinator = 1, "localhost:12346"
    hosts: List[str] = []
    log_dir = "./ranklogs"
    devices_per_process: Optional[int] = None
    execute = False
    max_restarts = 0
    heartbeat_timeout: Optional[float] = None
    startup_grace: Optional[float] = None  # None → default 300 (local mode)
    restart_window_s = 3600.0
    restart_backoff_s = 1.0
    max_preemptions = 100
    teardown_grace = 60.0
    elastic = False
    min_devices = 1
    max_elastic = 16
    supervise_flags_set = False
    i = 0
    while i < len(opts):
        o = opts[i]
        if o == "--num_processes":
            num_processes = int(opts[i + 1]); i += 2
        elif o == "--coordinator":
            coordinator = opts[i + 1]; i += 2
        elif o == "--hosts":
            hosts = [h.strip() for h in opts[i + 1].split(",") if h.strip()]
            i += 2
        elif o == "--log_dir":
            log_dir = opts[i + 1]; i += 2
        elif o == "--devices_per_process":
            devices_per_process = int(opts[i + 1]); i += 2
        elif o == "--execute":
            execute = True; i += 1
        elif o == "--max_restarts":
            max_restarts = int(opts[i + 1]); i += 2
        elif o == "--heartbeat_timeout":
            heartbeat_timeout = float(opts[i + 1]); i += 2
        elif o == "--startup_grace":
            startup_grace = float(opts[i + 1]); i += 2
        elif o == "--restart_window":
            restart_window_s = float(opts[i + 1])
            supervise_flags_set = True; i += 2
        elif o == "--restart_backoff":
            restart_backoff_s = float(opts[i + 1])
            supervise_flags_set = True; i += 2
        elif o == "--max_preemptions":
            max_preemptions = int(opts[i + 1])
            supervise_flags_set = True; i += 2
        elif o == "--teardown_grace":
            teardown_grace = float(opts[i + 1])
            supervise_flags_set = True; i += 2
        elif o == "--elastic":
            elastic = True
            supervise_flags_set = True; i += 1
        elif o == "--min_devices":
            min_devices = int(opts[i + 1])
            supervise_flags_set = True; i += 2
        elif o == "--max_elastic":
            max_elastic = int(opts[i + 1])
            supervise_flags_set = True; i += 2
        else:
            raise ValueError(f"unknown launcher option {o}")

    if hosts:
        if num_processes != 1 or devices_per_process:
            raise ValueError(
                "--hosts runs one rank per host; --num_processes/"
                "--devices_per_process are not supported with it")
        if (max_restarts or heartbeat_timeout or startup_grace is not None
                or supervise_flags_set):
            raise ValueError(
                "--max_restarts/--heartbeat_timeout/--startup_grace/"
                "--restart_window/--restart_backoff/--max_preemptions/"
                "--teardown_grace/--elastic/--min_devices/--max_elastic "
                "supervise local fan-out; for --hosts runs, supervise "
                "on each host")
        if coordinator == "localhost:12346":
            coordinator = f"{hosts[0]}:12346"
        lines = cluster_commands(cmd, hosts, coordinator, log_dir,
                                 background=not execute)
        if not execute:
            print("\n".join(lines))
            return 0
        # blocking ssh per rank: failures are observable and propagated
        running = [subprocess.Popen(line, shell=True) for line in lines]
        rc = 0
        for rank, p in enumerate(running):
            ret = p.wait()
            if ret:
                print(f"host rank {rank} exited {ret}", file=sys.stderr)
                if rc == 0:
                    rc = ret
        return rc
    # startup_grace default: 300 s covers first-compile stalls, but an
    # operator who explicitly set a SHORTER --heartbeat_timeout wants
    # hangs caught on that clock from the start — so the unset-grace
    # default follows the explicit timeout downward (never upward: a
    # long steady-state timeout must not weaken startup detection).
    if startup_grace is None:
        startup_grace = (min(heartbeat_timeout, 300.0)
                         if heartbeat_timeout else 300.0)
    return launch_local(cmd, num_processes, coordinator, log_dir,
                        devices_per_process, max_restarts=max_restarts,
                        heartbeat_timeout=heartbeat_timeout,
                        startup_grace=startup_grace,
                        restart_window_s=restart_window_s,
                        restart_backoff_s=restart_backoff_s,
                        max_preemptions=max_preemptions,
                        teardown_grace=teardown_grace,
                        elastic=elastic, min_devices=min_devices,
                        max_elastic=max_elastic)


if __name__ == "__main__":
    sys.exit(main())
