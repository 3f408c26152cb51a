"""Launcher implementation.

Parity target: ``python/paddle/distributed/launch/main.py`` +
``controllers/collective.py`` in the reference (process spawn, env plumbing,
workerlog.N files, failure watch, elastic restarts). TPU redesign: the unit
of launch is one process per HOST (single-controller JAX sees every local
chip), so ``--nproc_per_node`` defaults to 1; values > 1 are the multi-
process CPU simulation (each child gets a ``jax.distributed`` process id and
a localhost coordinator — the reference's Gloo-on-localhost testing trick,
SURVEY §4) and are refused unless the caller's environment says
``JAX_PLATFORMS=cpu``, which the children inherit.

Env contract exported to children (reference names + their JAX equivalents):
  PADDLE_TRAINER_ID / PADDLE_TRAINERS_NUM / PADDLE_MASTER
  PADDLE_DIST_COORDINATOR (host:port for jax.distributed.initialize)
  PADDLE_DIST_PROCESS_ID / PADDLE_DIST_NUM_PROCESSES
"""

from __future__ import annotations

import argparse
import os
import signal
import socket
import subprocess
import sys
import time
from typing import List, Optional

from ...core.place import cpu_requested

__all__ = ["main", "launch_procs", "write_rejoin_file",
           "read_rejoin_count", "consume_rejoin_file"]


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _parse(argv: Optional[List[str]] = None):
    p = argparse.ArgumentParser(
        prog="paddle_tpu.distributed.launch",
        description="launch a (multi-process) training job")
    p.add_argument("--master", default=None,
                   help="coordinator host:port (default: auto on localhost)")
    p.add_argument("--nnodes", type=int, default=1)
    p.add_argument("--node_rank", "--rank", type=int, default=0)
    p.add_argument("--nproc_per_node", type=int, default=1,
                   help="1 = single-controller TPU (default); >1 = "
                        "multi-process CPU simulation (needs "
                        "JAX_PLATFORMS=cpu in the environment)")
    p.add_argument("--devices", "--gpus", default=None,
                   help="visible chip ids (exported as TPU_VISIBLE_DEVICES "
                        "with the subset's bounds; several ids need "
                        "TPU_CHIPS_PER_PROCESS_BOUNDS in the environment)")
    p.add_argument("--log_dir", default="log")
    p.add_argument("--job_id", default="default")
    p.add_argument("--max_restart", "--elastic_level", type=int, default=0,
                   dest="max_restart",
                   help="elastic level: 0 = fail fast (no restarts); N > 0 "
                        "= restart the whole job up to N times on a crash "
                        "OR a hung worker (see --elastic_timeout); each "
                        "round gets a fresh rendezvous and the script is "
                        "expected to resume from its own checkpoints")
    p.add_argument("--elastic_timeout", type=float, default=60.0,
                   help="seconds without a worker heartbeat before the rank "
                        "is declared HUNG and the job restarts. Active only "
                        "when --max_restart/--elastic_level > 0; 0 disables "
                        "liveness detection. Workers stamp heartbeats "
                        "automatically from init_parallel_env/fleet.init. "
                        "Note: a native call holding the GIL longer than "
                        "the timeout starves the stamping thread — size the "
                        "timeout above your longest compile")
    p.add_argument("--elastic_rejoin_file", default=None,
                   help="path the infrastructure touches (optionally "
                        "writing a worker count) when capacity RETURNS; "
                        "the watcher notices mid-round, gracefully "
                        "restarts, and the next round re-rendezvouses "
                        "LARGER (scale-out; ref: fleet/elastic/manager.py "
                        "watching etcd for rejoined nodes)")
    p.add_argument("--elastic_max_nprocs", type=int, default=0,
                   help="upper bound for elastic scale-out (0 = the "
                        "original --nproc_per_node)")
    p.add_argument("--ckpt_dir", default=None,
                   help="checkpoint-series root exported to workers as "
                        "PADDLE_CHECKPOINT_DIR (AsyncCheckpointer's "
                        "default root). When set, each restart round first "
                        "prunes torn (uncommitted) step dirs so every "
                        "resume — even a naive pick-the-newest — lands on "
                        "the last-known-good commit")
    p.add_argument("--preempt_grace", type=float, default=15.0,
                   help="seconds between forwarding SIGTERM to the workers "
                        "(their emergency-checkpoint window; exported as "
                        "PADDLE_PREEMPT_GRACE) and SIGKILL, when the "
                        "LAUNCHER itself is preempted with SIGTERM")
    p.add_argument("--elastic_min_nprocs", type=int, default=0,
                   help="scale-in floor: when > 0, a restart after a crash "
                        "or hang RE-RENDEZVOUSES WITH THE SURVIVING WORLD "
                        "SIZE (failed ranks are dropped, down to this "
                        "minimum) instead of respawning the full world — "
                        "the reference's elastic scale-in event (fleet/"
                        "elastic/manager.py). The script must derive its "
                        "parallel degrees from PADDLE_TRAINERS_NUM and "
                        "resume via the distributed checkpoint's "
                        "reshard-on-load. 0 (default) = fixed world")
    p.add_argument("training_script")
    p.add_argument("training_script_args", nargs=argparse.REMAINDER)
    return p.parse_args(argv)


def _visible_chips_env(devices: str) -> dict:
    """Env that hands a child a SUBSET of the host's chips. Measured on a
    2x2 v5e host with libtpu 0.0.34: ``TPU_VISIBLE_DEVICES`` (like
    ``TPU_VISIBLE_CHIPS``) is honoured, but alone it fails TPU start-up
    ("the number of devices found in the host does not match the
    topology"); the subset's own bounds must ride along. One chip is
    always ``1,1,1``; the shape of a larger subset depends on which chips
    they are (``1,2,1`` for chips 0,1), so it comes from the caller."""
    env = {"TPU_VISIBLE_DEVICES": devices, "TPU_PROCESS_BOUNDS": "1,1,1"}
    if len(devices.split(",")) == 1:
        env["TPU_CHIPS_PER_PROCESS_BOUNDS"] = "1,1,1"
    elif "TPU_CHIPS_PER_PROCESS_BOUNDS" not in os.environ:
        raise RuntimeError(
            f"--devices {devices}: libtpu refuses a subset of a host's "
            f"chips without its bounds; export "
            f"TPU_CHIPS_PER_PROCESS_BOUNDS for these chips (e.g. 1,2,1 "
            f"for chips 0,1 of a 2x2 host)")
    return env


class _Proc:
    def __init__(self, rank: int, popen: subprocess.Popen, log_path: str):
        self.rank = rank
        self.popen = popen
        self.log_path = log_path


def _spawn(args, restart_round: int,
           elastic_store: Optional[str] = None,
           nproc_override: Optional[int] = None) -> List[_Proc]:
    os.makedirs(args.log_dir, exist_ok=True)
    nproc = nproc_override if nproc_override is not None \
        else args.nproc_per_node
    if nproc > 1 and not cpu_requested():
        # several processes per node is the CPU SIMULATION of a multi-host
        # job; a chip belongs to one process, so on a TPU host the children
        # would fight over it — and steering them to the CPU unasked is
        # training on the CPU without saying so
        raise RuntimeError(
            f"--nproc_per_node {nproc}: more than one process per node is "
            f"the multi-process CPU simulation and runs only where the "
            f"caller's environment says JAX_PLATFORMS=cpu. On a TPU host "
            f"one process drives every local chip (--nproc_per_node 1)")
    world = args.nnodes * nproc
    # fresh rendezvous every round: a restarted job must not collide with
    # stale state from the previous coordinator (SURVEY §5 elastic)
    master = args.master or f"127.0.0.1:{_free_port()}"
    procs = []
    for local_rank in range(nproc):
        rank = args.node_rank * nproc + local_rank
        env = dict(os.environ)
        env.update({
            "PADDLE_TRAINER_ID": str(rank),
            "PADDLE_TRAINERS_NUM": str(world),
            "PADDLE_MASTER": master,
            "PADDLE_DIST_COORDINATOR": master,
            "PADDLE_DIST_PROCESS_ID": str(rank),
            "PADDLE_DIST_NUM_PROCESSES": str(world),
            "PADDLE_LOCAL_RANK": str(local_rank),
            "PADDLE_RESTART_ROUND": str(restart_round),
            "PADDLE_JOB_ID": args.job_id,
        })
        if elastic_store:
            env["PADDLE_ELASTIC_STORE"] = elastic_store
        if getattr(args, "ckpt_dir", None):
            env["PADDLE_CHECKPOINT_DIR"] = args.ckpt_dir
        env["PADDLE_PREEMPT_GRACE"] = str(
            getattr(args, "preempt_grace", 15.0))
        if args.devices is not None:
            env.update(_visible_chips_env(args.devices))
        log_path = os.path.join(args.log_dir, f"workerlog.{rank}")
        logf = open(log_path, "ab", buffering=0)
        logf.write(f"==== launch rank {rank} round {restart_round} "
                   f"{time.strftime('%F %T')} ====\n".encode())
        popen = subprocess.Popen(
            [sys.executable, args.training_script, *args.training_script_args],
            env=env, stdout=logf, stderr=subprocess.STDOUT)
        procs.append(_Proc(rank, popen, log_path))
    return procs


HUNG_RC = 98     # job rc when a rank was killed for missing heartbeats
RESCALE_RC = 97  # internal rc: healthy round interrupted to scale OUT
PREEMPT_RC = 96  # the launcher was SIGTERMed (preemption): workers were
#                  given --preempt_grace to emergency-checkpoint, then the
#                  job exited WITHOUT burning a restart round (the host is
#                  going away; the rescheduled job resumes from last-good)

# a worker that exits with elastic.EMERGENCY_EXIT_RC ran its preemption
# handler (the infrastructure SIGTERMed the WORKERS directly, bypassing the
# launcher): treat it as a preemption, not a crash — restarting on a host
# being reclaimed would just burn every restart round
from ..elastic import EMERGENCY_EXIT_RC  # noqa: E402 (lightweight module)

# set by the launcher's SIGTERM handler, polled by the watch loop (a signal
# can land while _watch is mid-poll; a bare flag is async-signal-safe)
_preempt_flag = {"v": False}


def _kill_all(procs: List[_Proc], grace: float = 10.0,
              force_first: Optional[List[int]] = None):
    force_first = force_first or []
    for q in procs:
        if q.popen.poll() is None:
            # a STOPPED/hung process won't act on SIGTERM — SIGKILL it
            if q.rank in force_first:
                q.popen.kill()
            else:
                q.popen.send_signal(signal.SIGTERM)
    deadline = time.time() + grace
    for q in procs:
        timeout = max(0.1, deadline - time.time())
        try:
            q.popen.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            q.popen.kill()


def _check_rejoin(path) -> int:
    """Worker count offered by a rejoin signal file (0 = no signal). The
    file may be empty (means "capacity is back, take what you need") or
    hold an integer count."""
    if not path or not os.path.exists(path):
        return 0
    try:
        txt = open(path).read().strip()
        return int(txt) if txt else 10 ** 9
    except (OSError, ValueError):
        return 10 ** 9


# the launcher owns the rejoin-file format; these are the public spellings
# other layers use — the serving supervisor's autoscale_signal() writes a
# scale-up through write_rejoin_file so a watching launcher scales out
read_rejoin_count = _check_rejoin


def write_rejoin_file(path: str, workers: Optional[int] = None) -> str:
    """Write the ``--elastic_rejoin_file`` signal: an empty file means
    "capacity is back, take what you need"; an integer is the offered
    worker count. Written atomically (tmp + rename) so the watcher's
    poll never reads a torn count."""
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        if workers is not None:
            f.write(str(int(workers)))
    os.replace(tmp, path)
    return path


def consume_rejoin_file(path: Optional[str]) -> int:
    """Read-and-consume one rejoin signal: returns the offered worker
    count (0 = no signal) and removes the file — even a zero-count one
    (``write_rejoin_file(path, 0)`` is legal), or the next poll would
    re-read the stale signal forever — so the handshake both the elastic
    launcher (between rounds) and the serving router's ``poll_rejoin``
    use always starts the next round clean."""
    offered = _check_rejoin(path)
    if path:
        try:
            os.remove(path)
        except OSError:
            pass
    return offered


def _watch(procs: List[_Proc], monitor=None, ttl: float = 0.0,
           rejoin_file=None, want_more: bool = False,
           preempt_grace: float = 15.0) -> int:
    """Wait for all children; on any nonzero exit kill the rest (the
    reference's kill-all-on-one-failure policy). With a heartbeat
    ``monitor``, a rank whose liveness stamp goes stale for ``ttl`` seconds
    is declared HUNG — killed with the rest, job rc = HUNG_RC (a hung
    worker never produces an exit code on its own). Returns the job rc."""
    try:
        last_hb_check = 0.0
        while True:
            if _preempt_flag["v"]:
                # preemption: forward SIGTERM (the workers' emergency-
                # checkpoint trigger — see elastic.install_preemption_
                # handler), give them the bounded grace window to commit,
                # then make sure nothing survives the host going away
                print(f"launch: SIGTERM received — forwarding to workers "
                      f"with {preempt_grace}s emergency-checkpoint grace",
                      file=sys.stderr)
                _kill_all(procs, grace=preempt_grace)
                return PREEMPT_RC, []
            alive = 0
            for p in procs:
                rc = p.popen.poll()
                if rc is None:
                    alive += 1
                elif rc == EMERGENCY_EXIT_RC:
                    # the infrastructure preempted the WORKERS directly:
                    # this rank already committed its emergency checkpoint
                    # and exited; give its peers the same grace window
                    print(f"rank {p.rank} exited after an emergency "
                          f"checkpoint (preempted); forwarding SIGTERM to "
                          f"peers with {preempt_grace}s grace",
                          file=sys.stderr)
                    _kill_all(procs, grace=preempt_grace)
                    return PREEMPT_RC, []
                elif rc != 0:
                    # Collect every rank already dead BEFORE killing peers
                    # (post-kill, terminated peers also report nonzero) so a
                    # scale-in round sheds all lost ranks at once.
                    dead = [q.rank for q in procs
                            if q.popen.poll() not in (None, 0)]
                    _kill_all(procs)
                    print(f"rank(s) {dead} exited nonzero (first: rank "
                          f"{p.rank} rc {rc}, log: {p.log_path}); peers "
                          f"terminated", file=sys.stderr)
                    return rc, dead
            if alive == 0:
                return 0, []
            if want_more and _check_rejoin(rejoin_file):
                # capacity returned: gracefully interrupt the (healthy)
                # round; the caller re-rendezvouses with a larger world and
                # every script resumes from its checkpoint (the same
                # reshard-on-load contract scale-in uses)
                print("elastic: rejoin signal observed — interrupting the "
                      "round to scale out", file=sys.stderr)
                _kill_all(procs, grace=5.0)
                return RESCALE_RC, []
            if monitor is not None and ttl > 0 and \
                    time.time() - last_hb_check > min(1.0, ttl / 3):
                last_hb_check = time.time()
                live = [p.rank for p in procs if p.popen.poll() is None]
                hung = monitor.hung_ranks(live, ttl)
                if hung:
                    print(f"elastic: rank(s) {hung} missed heartbeats for "
                          f"> {ttl}s — declaring hung, terminating the job",
                          file=sys.stderr)
                    _kill_all(procs, grace=3.0, force_first=hung)
                    return HUNG_RC, list(hung)
            time.sleep(0.2)
    except KeyboardInterrupt:
        for q in procs:
            if q.popen.poll() is None:
                q.popen.terminate()
        return 130, []


def launch_procs(args) -> int:
    """Run the job with elastic restarts (checkpoint-resume contract: the
    script must resume from its own checkpoints; the launcher supplies a
    fresh rendezvous each round and the heartbeat-based hung-worker
    detection — SURVEY §5 failure-detection stance)."""
    rounds = args.max_restart + 1
    # liveness detection only at elastic levels > 0: without restarts a
    # hung-kill would just turn a stall into a failure with no recovery
    ttl = float(getattr(args, "elastic_timeout", 0.0) or 0.0) \
        if args.max_restart > 0 else 0.0
    monitor = None
    if ttl > 0:
        try:
            from ..elastic import HeartbeatMonitor
            monitor = HeartbeatMonitor(args.job_id)
        except Exception as e:  # native lib unavailable: degrade gracefully
            print(f"elastic: heartbeat monitor unavailable ({e}); "
                  f"exit-code watching only", file=sys.stderr)
    min_nprocs = int(getattr(args, "elastic_min_nprocs", 0) or 0)
    max_nprocs = int(getattr(args, "elastic_max_nprocs", 0) or 0) \
        or args.nproc_per_node
    rejoin_file = getattr(args, "elastic_rejoin_file", None)
    ckpt_dir = getattr(args, "ckpt_dir", None)
    preempt_grace = float(getattr(args, "preempt_grace", 15.0) or 15.0)
    cur_nproc = args.nproc_per_node
    rc = 1

    # Preemption watch: SIGTERM to the LAUNCHER (the infrastructure's
    # eviction notice) must become an emergency-checkpoint window for the
    # workers, not an instant job kill. Handler only flips a flag; the
    # watch loop does the forwarding (async-signal-safe).
    _preempt_flag["v"] = False
    prev_term = None
    try:
        prev_term = signal.signal(
            signal.SIGTERM, lambda s, f: _preempt_flag.__setitem__("v", True))
    except ValueError:
        pass  # not the main thread (embedded use): no preemption watch
    try:
        for attempt in range(rounds):
            if attempt > 0 and ckpt_dir:
                # resume-from-last-good contract: physically drop torn
                # (uncommitted) step dirs before the next round so ANY
                # resume policy in the script lands on a committed save
                try:
                    from ..checkpoint.manifest import prune_uncommitted
                    removed = prune_uncommitted(ckpt_dir)
                    if removed:
                        print(f"elastic: pruned {len(removed)} torn "
                              f"checkpoint dir(s) under {ckpt_dir}",
                              file=sys.stderr)
                except Exception as e:   # pruning is best-effort
                    print(f"elastic: checkpoint prune skipped ({e})",
                          file=sys.stderr)
            if monitor is not None:
                monitor.clear(args.nnodes * cur_nproc)  # stale stamps
            procs = _spawn(args, attempt,
                           elastic_store=monitor.addr if monitor else None,
                           nproc_override=cur_nproc)
            # only interrupt a healthy round for scale-out when a
            # restart round remains to actually perform it
            rc, bad = _watch(procs, monitor=monitor, ttl=ttl,
                             rejoin_file=rejoin_file,
                             want_more=(cur_nproc < max_nprocs
                                        and attempt < rounds - 1),
                             preempt_grace=preempt_grace)
            if rc == 0 or rc == 130:
                return rc
            if rc == PREEMPT_RC:
                # the host is being reclaimed: no restart round could run
                # here — the RESCHEDULED job resumes from the emergency
                # commit (or last-good) in ckpt_dir
                return rc
            if attempt < rounds - 1:
                if rc == RESCALE_RC or (rejoin_file and
                                        _check_rejoin(rejoin_file)):
                    # scale-out: capacity is back — re-rendezvous with the
                    # larger world (mirror of scale-in; ref:
                    # fleet/elastic/manager.py rejoin handling)
                    offered = consume_rejoin_file(rejoin_file)
                    new_nproc = min(max_nprocs,
                                    max(cur_nproc, min(offered,
                                                       max_nprocs)))
                    if new_nproc != cur_nproc:
                        print(f"elastic: scale-out {cur_nproc} -> "
                              f"{new_nproc} procs (rejoin signal)",
                              file=sys.stderr)
                        cur_nproc = new_nproc
                elif min_nprocs > 0 and bad:
                    # scale-in: drop the failed/hung ranks from the world
                    # (ref: elastic manager's scale event -> rendezvous
                    # re-init with the surviving node set); the script
                    # resumes at the NEW topology via the distributed
                    # checkpoint's reshard-on-load
                    new_nproc = max(min_nprocs, cur_nproc - len(bad))
                    if new_nproc != cur_nproc:
                        print(f"elastic: scale-in {cur_nproc} -> "
                              f"{new_nproc} procs (lost ranks {bad})",
                              file=sys.stderr)
                    cur_nproc = new_nproc
                print(f"elastic: restarting job "
                      f"(attempt {attempt + 2}/{rounds})", file=sys.stderr)
    finally:
        if prev_term is not None:
            try:
                signal.signal(signal.SIGTERM, prev_term)
            except ValueError:
                pass
        if monitor is not None:
            monitor.close()
    return rc


def main(argv: Optional[List[str]] = None) -> int:
    args = _parse(argv)
    rc = launch_procs(args)
    if rc != 0:
        sys.exit(rc)
    return 0


if __name__ == "__main__":
    main()
