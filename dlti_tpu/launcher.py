"""Multi-process / multi-host launcher — the torchrun / `deepspeed` analog.

The reference never launches processes itself; it leans on ``torchrun``
(``train_deepspeed_zero1.py:10-12``: sets LOCAL_RANK/WORLD_SIZE) and the
``deepspeed`` CLI (``train.ipynb:640-653``: spawns N ranks with
``--master_addr=127.0.0.1 --master_port=29500``), with SLURM claimed but
absent (``README.md:18``). This module is the in-tree replacement:

* :func:`launch_local` — spawn N local worker processes, each with the
  ``DLTI_*`` rendezvous env (coordinator address, world size, process id);
  on the first failure the rest are terminated and the worst return code is
  returned (the semantics of torchrun's sigkill_handler, visible in the
  reference's recorded crash, ``train.ipynb:826-838``).
* :func:`slurm_env` — derive the same rendezvous env from ``SLURM_*``
  variables so one ``srun`` task per host self-configures.
* :func:`maybe_initialize_from_env` — called by entry points
  (``scripts/train.py``); a no-op unless the launcher env is present, in
  which case it runs :func:`jax.distributed.initialize` before backend use.

Rendezvous env contract (the LOCAL_RANK/WORLD_SIZE/MASTER_ADDR analog):

==========================  =================================================
``DLTI_COORDINATOR``        ``host:port`` of process 0
``DLTI_NUM_PROCESSES``      world size
``DLTI_PROCESS_ID``         this process's id (0-based)
==========================  =================================================

Elastic supervision (``--elastic``) hands off to
:class:`dlti_tpu.training.elastic.ElasticLauncher`, which extends the
contract with ``DLTI_GENERATION`` (the rendezvous generation),
``DLTI_ELASTIC_DIR`` (heartbeat/event dir), and
``DLTI_ELASTIC_NUM_SLOTS`` (the full-size world the batch schedule is
defined against) — see that module for the recovery loop.
"""

from __future__ import annotations

import os
import re
import signal
import subprocess
import sys
from typing import Dict, List, Optional, Sequence

ENV_COORDINATOR = "DLTI_COORDINATOR"
ENV_NUM_PROCESSES = "DLTI_NUM_PROCESSES"
ENV_PROCESS_ID = "DLTI_PROCESS_ID"

DEFAULT_PORT = 29400


def worker_env(coordinator: str, num_processes: int, process_id: int,
               base: Optional[Dict[str, str]] = None) -> Dict[str, str]:
    env = dict(os.environ if base is None else base)
    env[ENV_COORDINATOR] = coordinator
    env[ENV_NUM_PROCESSES] = str(num_processes)
    env[ENV_PROCESS_ID] = str(process_id)
    return env


def launch_local(command: Sequence[str], num_processes: int,
                 port: int = DEFAULT_PORT,
                 log_dir: Optional[str] = None) -> int:
    """Spawn ``num_processes`` copies of ``command`` on this host.

    Process i gets ``DLTI_PROCESS_ID=i``; all share a localhost coordinator.
    Output is interleaved to our stdout/stderr unless ``log_dir`` is given
    (then ``rank{i}.out``/``.err`` per process — the ``logs/*.out``/``.err``
    layout the reference's ``.gitignore:36-37`` implies).

    Returns the worst return code; terminates stragglers once any worker
    fails so a crashed rank can't hang the job.
    """
    coordinator = f"127.0.0.1:{port}"
    procs: List[subprocess.Popen] = []
    files = []
    try:
        for i in range(num_processes):
            env = worker_env(coordinator, num_processes, i)
            stdout = stderr = None
            if log_dir:
                os.makedirs(log_dir, exist_ok=True)
                stdout = open(os.path.join(log_dir, f"rank{i}.out"), "wb")
                stderr = open(os.path.join(log_dir, f"rank{i}.err"), "wb")
                files += [stdout, stderr]
            procs.append(subprocess.Popen(list(command), env=env,
                                          stdout=stdout, stderr=stderr))
        rcs = [None] * num_processes
        first_bad_rc = None
        while any(rc is None for rc in rcs) and first_bad_rc is None:
            for i, p in enumerate(procs):
                if rcs[i] is None:
                    try:
                        rcs[i] = p.wait(timeout=0.25)
                    except subprocess.TimeoutExpired:
                        continue
                    if rcs[i] != 0 and first_bad_rc is None:
                        first_bad_rc = rcs[i]
        if first_bad_rc is not None:
            for i, p in enumerate(procs):
                if rcs[i] is None:
                    p.send_signal(signal.SIGTERM)
            for i, p in enumerate(procs):
                if rcs[i] is None:
                    try:
                        rcs[i] = p.wait(timeout=10)
                    except subprocess.TimeoutExpired:
                        p.kill()
                        rcs[i] = p.wait()
            # The rc that *triggered* teardown, not the -15s from our own
            # SIGTERMs — and never max(), which masks signal codes (-11)
            # behind a clean 0 from an already-finished rank.
            return first_bad_rc
        return next((rc for rc in rcs if rc), 0)
    finally:
        for p in procs:
            if p.poll() is None:  # spawn-loop exception / interrupt: no orphans
                p.kill()
        for f in files:
            f.close()


def first_slurm_node(nodelist: str) -> str:
    """First hostname of a SLURM nodelist, without needing ``scontrol``.

    Handles plain lists (``a,b``), compressed ranges
    (``tpu-host[003-006,009]`` -> ``tpu-host003``), and mixes of both
    (``alpha,tpu[01-04]`` -> ``alpha``): the first entry ends at the first
    top-level comma (commas inside ``[...]`` don't split entries).
    """
    depth = 0
    head = nodelist
    for i, ch in enumerate(nodelist):
        if ch == "[":
            depth += 1
        elif ch == "]":
            depth -= 1
        elif ch == "," and depth == 0:
            head = nodelist[:i]
            break
    m = re.match(r"^([^\[]+)\[([^\]\-,]+)", head)
    if m:
        return m.group(1) + m.group(2)
    return head


def slurm_env(environ: Optional[Dict[str, str]] = None,
              port: int = DEFAULT_PORT) -> Dict[str, str]:
    """Map ``SLURM_*`` vars to the ``DLTI_*`` rendezvous contract.

    Raises KeyError outside a SLURM allocation.
    """
    e = os.environ if environ is None else environ
    nodelist = e.get("SLURM_JOB_NODELIST") or e["SLURM_NODELIST"]
    coordinator = f"{first_slurm_node(nodelist)}:{port}"
    num = int(e.get("SLURM_NTASKS") or e["SLURM_NNODES"])
    pid = int(e.get("SLURM_PROCID") or e["SLURM_NODEID"])
    return worker_env(coordinator, num, pid, base=dict(e))


def maybe_initialize_from_env() -> bool:
    """Initialize jax.distributed from the launcher env; no-op without it.

    Entry points call this exactly once, before any jax backend use. Returns
    True if multi-process init ran.

    The connect retries with capped exponential backoff
    (``DLTI_CONNECT_RETRIES`` / ``DLTI_CONNECT_BACKOFF_S``, defaults 3 /
    1.0s, cap 10s): workers race rank-0 to the rendezvous and a cold
    coordinator — rank 0 still importing jax, or an elastic relaunch
    whose previous generation's port is mid-teardown — must read as
    "not up yet", not as a fatal error.
    """
    num = int(os.environ.get(ENV_NUM_PROCESSES, "1"))
    if num <= 1:
        return False
    from dlti_tpu.parallel.mesh import initialize_multihost

    coordinator = os.environ[ENV_COORDINATOR]
    process_id = int(os.environ[ENV_PROCESS_ID])
    retries = int(os.environ.get("DLTI_CONNECT_RETRIES", "3"))
    backoff = float(os.environ.get("DLTI_CONNECT_BACKOFF_S", "1.0"))
    attempt = 0
    while True:
        try:
            initialize_multihost(
                coordinator_address=coordinator,
                num_processes=num,
                process_id=process_id,
            )
            return True
        except Exception:
            attempt += 1
            if attempt > retries:
                raise
            import logging
            import time

            # A failed connect can leave the client half-initialized;
            # shut it down so the retry starts clean.
            try:
                import jax

                jax.distributed.shutdown()
            except Exception:
                pass
            delay = min(backoff * (2 ** (attempt - 1)), 10.0)
            logging.getLogger("dlti").warning(
                "jax.distributed.initialize(%s) failed (attempt %d/%d); "
                "retrying in %.1fs", coordinator, attempt, retries + 1,
                delay)
            time.sleep(delay)


def main(argv: Optional[Sequence[str]] = None) -> int:
    """CLI: ``launch.py [--num-processes N | --coordinator-from-slurm] -- cmd...``"""
    import argparse

    p = argparse.ArgumentParser(
        description="Process launcher (torchrun/deepspeed-CLI analog)")
    p.add_argument("--num-processes", type=int, default=0,
                   help="spawn N local worker processes")
    p.add_argument("--coordinator-from-slurm", action="store_true",
                   help="derive rendezvous from SLURM_* env and exec the "
                        "command in-place (one srun task per host)")
    p.add_argument("--port", type=int, default=DEFAULT_PORT)
    p.add_argument("--log-dir", default=None)
    # Elastic supervision (dlti_tpu.training.elastic.ElasticLauncher):
    # instead of kill-all-on-first-failure, recover worker death with a
    # restart budget, exponential backoff, and generation-numbered
    # rendezvous — shrink the world to the survivors, resume from the
    # last verified checkpoint, and rejoin at the next checkpoint
    # boundary.
    p.add_argument("--elastic", action="store_true",
                   help="supervise workers elastically (restart budget + "
                        "backoff + reshape-on-failure + rejoin) instead "
                        "of kill-all-on-first-failure")
    p.add_argument("--restart-budget", type=int, default=3,
                   help="worker-failure recoveries before giving up")
    p.add_argument("--backoff", type=float, default=1.0,
                   help="initial restart backoff seconds (doubles per "
                        "restart, capped at --backoff-max)")
    p.add_argument("--backoff-max", type=float, default=30.0)
    p.add_argument("--heartbeat-stale-s", type=float, default=0.0,
                   help="supervisor-side staleness deadline for per-rank "
                        "heartbeat files (0 = exits only)")
    p.add_argument("--startup-grace", type=float, default=60.0,
                   help="seconds before a never-beaten worker can be "
                        "declared stale (covers cold jax compiles)")
    p.add_argument("--no-rejoin", action="store_true",
                   help="do not grow back to full size at the next "
                        "checkpoint boundary after a shrink")
    p.add_argument("--ckpt-dir", default=None,
                   help="checkpoint dir to watch for rejoin boundaries "
                        "(the trainer's --output-dir)")
    p.add_argument("--min-world", type=int, default=1,
                   help="smallest world the supervisor may shrink to")
    p.add_argument("--term-grace", type=float, default=10.0,
                   help="SIGTERM->SIGKILL grace seconds in teardown")
    p.add_argument("--elastic-dir", default=None,
                   help="rendezvous/heartbeat dir (default: under "
                        "--log-dir, else a temp dir)")
    p.add_argument("command", nargs=argparse.REMAINDER,
                   help="-- command to run")
    args = p.parse_args(argv)
    cmd = args.command
    if cmd and cmd[0] == "--":
        cmd = cmd[1:]
    if not cmd:
        p.error("no command given (use: launch.py ... -- python scripts/train.py ...)")
    if args.coordinator_from_slurm:
        env = slurm_env(port=args.port)
        os.execvpe(cmd[0], list(cmd), env)  # never returns
    if args.num_processes <= 0:
        p.error("--num-processes N or --coordinator-from-slurm required")
    if args.num_processes > 1:
        # Several local JAX processes share a host only on the CPU
        # backend; on a TPU host this exits here, before any spawn.
        from dlti_tpu.utils.platform import refuse_multiprocess_on_tpu

        refuse_multiprocess_on_tpu(
            f"scripts/launch.py --num-processes {args.num_processes}"
            + (" --elastic" if args.elastic else ""))
    if args.elastic:
        from dlti_tpu.training.elastic import ElasticLauncher

        return ElasticLauncher(
            cmd, args.num_processes, port=args.port, log_dir=args.log_dir,
            restart_budget=args.restart_budget, backoff_s=args.backoff,
            backoff_max_s=args.backoff_max,
            heartbeat_stale_s=args.heartbeat_stale_s,
            startup_grace_s=args.startup_grace,
            rejoin=not args.no_rejoin, ckpt_dir=args.ckpt_dir,
            min_world=args.min_world, term_grace_s=args.term_grace,
            elastic_dir=args.elastic_dir,
        ).run()
    return launch_local(cmd, args.num_processes, port=args.port,
                        log_dir=args.log_dir)


if __name__ == "__main__":
    sys.exit(main())
