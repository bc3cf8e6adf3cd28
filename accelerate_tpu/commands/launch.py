"""``accelerate-tpu launch`` — run a training script with the right env.

TPU-native analogue of the reference's launcher (commands/launch.py:986-1193).
The reference fans out one process per GPU (torchrun/deepspeed/xmp.spawn);
JAX runs ONE process per host addressing all local devices, so:

* single host → set env, exec the script (reference ``simple_launcher``);
* multi-host (``--num_processes N --coordinator_address host:port
  --process_id i``) → same, plus jax.distributed bootstrap env consumed by
  PartialState (state.py);
* TPU pod (``--pod``) → fan the SAME command out to every worker over
  ``gcloud compute tpus tpu-vm ssh --worker=all`` (the reference's
  ``tpu_pod_launcher``/``tpu-config``, commands/launch.py:1117 + tpu.py).

Fault tolerance (the reference forwards ``--max_restarts``/
``--monitor_interval`` to torchrun's elastic agent, commands/launch.py:
589-620,998): each host runs a local supervisor. ``--max_restarts N``
relaunches the script when it dies; ``--monitor_interval``/
``--watchdog_timeout`` add a heartbeat hang detector (the Accelerator
touches ``ACCELERATE_HEARTBEAT_FILE`` every optimizer step). On a
multi-host SPMD job a single dead host makes every other host's
collectives fail, so all supervisors restart their worker together and
``jax.distributed`` re-forms — recovery is whole-job restart + resume from
the latest checkpoint (``Accelerator.resume_from_latest`` +
``skip_first_batches``), which is the only sound recovery on a TPU pod (no
per-rank elasticity). With ``--elastic`` the whole-job restart may re-form
at a DIFFERENT world size (``ACCELERATE_ELASTIC_TOPOLOGY_FILE`` updated by
an external orchestrator between restarts): workers resume from the
cluster-consensus checkpoint with ``elastic=True``, resharding state onto
the new mesh, and ``--replicate_to`` gives hosts that lost their local
checkpoint tree a durable replica to restore from
(docs/fault_tolerance.md "Replication & elastic resume").
"""

from __future__ import annotations

import json
import os
import shlex
import signal
import subprocess
import sys
import tempfile
import threading
import time

from .config import ClusterConfig, default_config_file

# utils.fault is import-light by design so the launcher can use it
from ..utils.fault import PREEMPTION_EXIT_CODE

# exponential-backoff cap between crash-loop restarts
_MAX_BACKOFF = 60.0


def _supervise(cmd, env, max_restarts: int, monitor_interval: float,
               watchdog_timeout: float, min_uptime: float = 10.0,
               crash_loop_limit: int = 3) -> int:
    """Run ``cmd`` under a restart supervisor; returns the final exit code.

    The child is polled every ``monitor_interval`` seconds. With
    ``watchdog_timeout > 0`` a heartbeat file is exported as
    ``ACCELERATE_HEARTBEAT_FILE``; if the child stops touching it for longer
    than the timeout (hung collective, lost host) it is killed and counted
    as a failure.

    Signals: SIGTERM/SIGINT sent to the supervisor (TPU preemption targets
    the whole process tree's leader) are forwarded to the worker so it can
    run its preemption handler (emergency checkpoint); the worker then
    exiting 0 or :data:`PREEMPTION_EXIT_CODE` counts as a clean shutdown
    (supervisor returns 0, no restart).

    Crash-loop breaker: a worker that dies within ``min_uptime`` seconds of
    launch is a *fast failure* (bad config, import error, poisoned
    checkpoint) — after ``crash_loop_limit`` CONSECUTIVE fast failures the
    supervisor aborts even with restart budget left, instead of hammering
    the job forever. Consecutive fast failures also back off exponentially
    (``ACCELERATE_RESTART_BACKOFF`` base seconds, default 1.0, doubling per
    fast failure, capped at 60s); a worker that survived past ``min_uptime``
    resets both the counter and the backoff."""
    hb_file = None
    if watchdog_timeout > 0:
        fd, hb_file = tempfile.mkstemp(prefix="accelerate_hb_")
        os.close(fd)
        env["ACCELERATE_HEARTBEAT_FILE"] = hb_file
    attempt = 0
    fast_fails = 0
    backoff_base = float(os.environ.get("ACCELERATE_RESTART_BACKOFF", "1.0"))
    child: dict = {"proc": None, "terminating": False}
    prev_handlers = {}

    def _forward(signum, frame):
        child["terminating"] = True
        proc = child["proc"]
        if proc is not None and proc.poll() is None:
            print(
                f"[launch] forwarding signal {signum} to worker for a "
                "preemption checkpoint",
                file=sys.stderr,
            )
            try:
                proc.send_signal(signum)
            except OSError:
                pass

    # handler installation is main-thread-only in Python; in a test harness
    # driving _supervise from a worker thread the forwarding is simply off
    if threading.current_thread() is threading.main_thread():
        for sig in (signal.SIGTERM, signal.SIGINT):
            prev_handlers[sig] = signal.signal(sig, _forward)
    try:
        while True:
            env["ACCELERATE_RESTART_COUNT"] = str(attempt)
            _apply_elastic_topology(env, attempt)
            if hb_file:
                os.utime(hb_file, None)
            started = time.time()
            proc = subprocess.Popen(cmd, env=env)
            child["proc"] = proc
            rc = None
            while rc is None:
                try:
                    rc = proc.wait(timeout=monitor_interval)
                except subprocess.TimeoutExpired:
                    if hb_file and (
                        time.time() - os.path.getmtime(hb_file) > watchdog_timeout
                    ):
                        print(
                            f"[launch] heartbeat stale >{watchdog_timeout}s; "
                            "killing hung worker",
                            file=sys.stderr,
                        )
                        proc.kill()
                        proc.wait()  # graft: wait-ok — reaping a just-SIGKILLed child
                        rc = 1
            uptime = time.time() - started
            if child["terminating"]:
                # forwarded preemption: the worker checkpointing and exiting
                # 143 (or 0) is the PLANNED outcome, not a crash
                if rc in (0, PREEMPTION_EXIT_CODE, -signal.SIGTERM, -signal.SIGINT):
                    print(
                        "[launch] worker shut down cleanly after preemption "
                        "signal",
                        file=sys.stderr,
                    )
                    return 0
                return rc
            if rc == 0:
                return 0
            if uptime < min_uptime:
                fast_fails += 1
            else:
                fast_fails = 0
            if fast_fails >= crash_loop_limit:
                print(
                    f"[launch] crash loop: worker died within {min_uptime}s "
                    f"of launch {fast_fails} times in a row; aborting "
                    f"(rc={rc})",
                    file=sys.stderr,
                )
                return rc
            if attempt >= max_restarts:
                return rc
            attempt += 1
            print(
                f"[launch] worker exited rc={rc}; restart {attempt}/{max_restarts}",
                file=sys.stderr,
            )
            # Whole-job restart alignment: on a multi-host job one rank's
            # crash leaves the OTHERS failing or hung at different times —
            # error-exits within seconds, hung workers only when their
            # watchdog fires, up to watchdog_timeout later. Relaunching
            # per-host on its OWN death time splits the restarts by that
            # spread: early rejoiners attach to the half-dead old cluster
            # (split-brain) or give up before the new coordinator exists,
            # burning the restart budget. The heartbeat file is a per-host
            # clock that ticks with the GLOBAL step cadence, so
            # "last beat + watchdog horizon + margin" is (to within a step)
            # the same ABSOLUTE instant on every host — each supervisor
            # sleeps until that deadline and the whole job relaunches
            # together, with every old worker provably dead (any hung one
            # was killed at last beat + watchdog).
            multi_host = int(env.get("ACCELERATE_NUM_PROCESSES", "1") or 1) > 1
            backoff = (
                min(backoff_base * (2 ** (fast_fails - 1)), _MAX_BACKOFF)
                if fast_fails > 0
                else 0.0
            )
            if "ACCELERATE_RESTART_DELAY" in os.environ:
                delay = float(os.environ["ACCELERATE_RESTART_DELAY"])
            elif multi_host and hb_file and watchdog_timeout > 0:
                deadline = (
                    os.path.getmtime(hb_file)
                    + watchdog_timeout
                    + 2 * monitor_interval
                    + 2
                )
                # both constraints hold: the whole job must be down AND a
                # fast-failing worker must not be hammered back up instantly
                delay = max(0.0, deadline - time.time(), backoff)
            else:
                delay = backoff
            if delay:
                print(
                    f"[launch] waiting {delay:.1f}s before relaunching"
                    + (f" (backoff after {fast_fails} fast failures)" if backoff and backoff >= delay else
                       " for the whole job to come down"),
                    file=sys.stderr,
                )
                time.sleep(delay)
    finally:
        for sig, handler in prev_handlers.items():
            try:
                signal.signal(sig, handler)
            except (OSError, ValueError):
                pass
        if hb_file:
            try:
                os.unlink(hb_file)
            except OSError:
                pass


def _apply_elastic_topology(env: dict, attempt: int) -> None:
    """Gang restart with a NEW topology: before every (re)launch the
    supervisor re-reads ``ACCELERATE_ELASTIC_TOPOLOGY_FILE`` (JSON with any
    of ``num_processes`` / ``process_id`` / ``coordinator_address``) and
    exports the values to the worker. An external orchestrator that lost a
    host updates the file on every surviving host; at the next whole-job
    restart the gang re-forms at the new world size and
    ``resume_from_latest(elastic=True)`` reshards from the consensus
    checkpoint. Without the env var (or the file) this is a no-op — the
    restart keeps the original fixed topology."""
    topo_file = env.get("ACCELERATE_ELASTIC_TOPOLOGY_FILE") or os.environ.get(
        "ACCELERATE_ELASTIC_TOPOLOGY_FILE"
    )
    if not topo_file or not os.path.exists(topo_file):
        return
    try:
        with open(topo_file) as f:
            topo = json.load(f)
    except (json.JSONDecodeError, OSError) as exc:
        print(f"[launch] unreadable elastic topology file {topo_file}: {exc}",
              file=sys.stderr)
        return
    changed = []
    for key in ("num_processes", "process_id", "coordinator_address"):
        if key in topo:
            var = f"ACCELERATE_{key.upper()}"
            val = str(topo[key])
            if env.get(var) != val:
                changed.append(f"{var}={val}")
            env[var] = val
    if changed and attempt:
        print(
            f"[launch] elastic relaunch with {' '.join(changed)}",
            file=sys.stderr,
        )


def _supervision_settings(args, cfg) -> tuple[int, float]:
    """CLI flags override the config file; an EXPLICIT --max_restarts 0 /
    --watchdog_timeout 0 disables supervision (flags default to None so
    unset and explicit-zero are distinguishable)."""
    max_restarts = args.max_restarts if args.max_restarts is not None else cfg.max_restarts
    watchdog = args.watchdog_timeout if args.watchdog_timeout is not None else cfg.watchdog_timeout
    return int(max_restarts or 0), float(watchdog or 0.0)


def launch_command(args, script_args) -> int:
    cfg = None
    config_file = args.config_file or default_config_file()
    if os.path.exists(config_file):
        cfg = ClusterConfig.load(config_file)
    else:
        cfg = ClusterConfig()

    # CLI flags override the config file (reference _validate_launch_command)
    for name in (
        "mixed_precision",
        "num_processes",
        "coordinator_address",
        "gradient_accumulation_steps",
    ):
        val = getattr(args, name, None)
        if val is not None:
            setattr(cfg, name, val)
    for axis in ("dp_replicate", "dp_shard", "pp", "cp", "sp", "tp", "ep"):
        val = getattr(args, f"{axis}_size", None)
        if val is not None:
            setattr(cfg, f"{axis}_size", val)
    if args.debug:
        cfg.debug = True

    flag_env: dict = {}
    if args.process_id is not None:
        flag_env["ACCELERATE_PROCESS_ID"] = str(args.process_id)
    if args.handle_preemption:
        # every worker's Accelerator installs the SIGTERM/SIGINT
        # checkpoint-then-exit handler (utils/fault.py)
        flag_env["ACCELERATE_HANDLE_PREEMPTION"] = "1"
    if args.elastic:
        # workers resume with elastic=True: a restart at a different world
        # size reshards from the cluster-consensus checkpoint instead of
        # failing the topology gate (docs/fault_tolerance.md)
        flag_env["ACCELERATE_ELASTIC"] = "1"
    if args.replicate_to:
        flag_env["ACCELERATE_REPLICATION_TARGET"] = args.replicate_to
        if args.replicate_copies is not None:
            flag_env["ACCELERATE_REPLICATION_COPIES"] = str(args.replicate_copies)
    env = dict(os.environ)
    env.update(cfg.to_env())
    env.update(flag_env)

    if not args.training_script:
        print("error: no training script given", file=sys.stderr)
        return 2
    cmd = [sys.executable, args.training_script, *script_args]

    if args.pod:
        # each pod worker runs its OWN local supervisor: forward the restart/
        # watchdog flags through the inner launch command rather than bare
        # `python script` (a crash on one host then restarts everywhere, and
        # jax.distributed re-forms — the whole-job restart recovery model)
        pod_restarts, pod_watchdog = _supervision_settings(args, cfg)
        supervisor_flags: list[str] = []
        if pod_restarts:
            supervisor_flags += ["--max_restarts", str(pod_restarts)]
            supervisor_flags += ["--monitor_interval", str(args.monitor_interval)]
            if pod_watchdog:
                supervisor_flags += ["--watchdog_timeout", str(pod_watchdog)]
            supervisor_flags += ["--min_uptime", str(args.min_uptime)]
            supervisor_flags += ["--crash_loop_limit", str(args.crash_loop_limit)]
        if args.handle_preemption:
            supervisor_flags += ["--handle_preemption"]
        if args.elastic:
            supervisor_flags += ["--elastic"]
        if args.replicate_to:
            supervisor_flags += ["--replicate_to", args.replicate_to]
            if args.replicate_copies is not None:
                supervisor_flags += ["--replicate_copies", str(args.replicate_copies)]
        inner = " ".join(
            [f"{k}={shlex.quote(v)}" for k, v in cfg.to_env().items()]
            + ["python", "-m", "accelerate_tpu.commands.accelerate_cli", "launch"]
            + supervisor_flags
            + [shlex.quote(args.training_script)]
            + [shlex.quote(a) for a in script_args]
        )
        pod_cmd = [
            "gcloud", "compute", "tpus", "tpu-vm", "ssh", args.pod,
            "--worker=all", f"--command={inner}",
        ]
        if args.dry_run:
            print(" ".join(shlex.quote(c) for c in pod_cmd))
            return 0
        return subprocess.call(pod_cmd)

    if args.dry_run:
        print(" ".join(shlex.quote(c) for c in cmd))
        for k, v in sorted({**cfg.to_env(), **flag_env}.items()):
            print(f"  {k}={v}")
        return 0
    max_restarts, watchdog = _supervision_settings(args, cfg)
    # even with zero restarts the child runs under _supervise so preemption
    # signals are forwarded for a checkpoint-then-exit shutdown
    return _supervise(
        cmd, env, max_restarts, args.monitor_interval, watchdog,
        min_uptime=args.min_uptime, crash_loop_limit=args.crash_loop_limit,
    )


def add_parser(subparsers) -> None:
    p = subparsers.add_parser("launch", help="launch a training script")
    p.add_argument("--config_file", default=None)
    p.add_argument("--mixed_precision", default=None, choices=["no", "bf16", "fp16", "fp8"])
    p.add_argument("--num_processes", type=int, default=None, help="number of host processes")
    p.add_argument("--coordinator_address", default=None, help="host:port of process 0")
    p.add_argument("--process_id", type=int, default=None, help="this host's process index")
    p.add_argument("--gradient_accumulation_steps", type=int, default=None)
    for axis in ("dp_replicate", "dp_shard", "pp", "cp", "sp", "tp", "ep"):
        p.add_argument(f"--{axis}_size", type=int, default=None)
    p.add_argument("--pod", default=None, help="TPU pod name: fan out over gcloud ssh --worker=all")
    p.add_argument("--max_restarts", type=int, default=None,
                   help="relaunch the script up to N times when it dies (per-host supervisor)")
    p.add_argument("--monitor_interval", type=float, default=5.0,
                   help="seconds between child liveness polls")
    p.add_argument("--watchdog_timeout", type=float, default=None,
                   help=">0: kill the worker if it stops heartbeating for this many "
                        "seconds. The heartbeat ticks per optimizer step and around "
                        "checkpoint save/load — set this comfortably above the first-"
                        "step XLA compile time or the watchdog will kill a healthy "
                        "worker mid-compile")
    p.add_argument("--min_uptime", type=float, default=10.0,
                   help="a worker dying within this many seconds of launch counts as a "
                        "fast failure for the crash-loop breaker")
    p.add_argument("--crash_loop_limit", type=int, default=3,
                   help="abort after this many consecutive fast failures even with "
                        "restart budget left (exponential backoff applies in between; "
                        "base seconds via ACCELERATE_RESTART_BACKOFF, default 1.0)")
    p.add_argument("--handle_preemption", action="store_true",
                   help="workers checkpoint and exit cleanly on SIGTERM/SIGINT "
                        "(TPU preemption); the supervisor forwards the signal and "
                        "treats the shutdown as planned")
    p.add_argument("--elastic", action="store_true",
                   help="exports ACCELERATE_ELASTIC=1: resume_from_latest loads "
                        "the cluster-consensus checkpoint with elastic=True, so a "
                        "gang restart at a DIFFERENT world size (see "
                        "ACCELERATE_ELASTIC_TOPOLOGY_FILE) reshards instead of "
                        "failing the topology gate")
    p.add_argument("--replicate_to", default=None,
                   help="exports ACCELERATE_REPLICATION_TARGET: every committed "
                        "checkpoint is mirrored (manifest-verified, background) "
                        "under this durable path; a host that lost its local tree "
                        "restores from the replica on resume")
    p.add_argument("--replicate_copies", type=int, default=None,
                   help="number of replica copies under --replicate_to (default 1)")
    p.add_argument("--debug", action="store_true", help="enable collective shape verification")
    p.add_argument("--dry_run", action="store_true", help="print the command and env, don't run")
    p.add_argument("training_script", nargs="?")
    p.set_defaults(func=launch_command)
