# Copyright 2026. Licensed under the Apache License, Version 2.0.
"""``bfrun-tpu``: launch a bluefog_tpu program.

Reference counterpart: ``bfrun`` (reference ``run/run.py:58-203``), which
parses np/hosts/hostfile/ssh/timeline args, discovers NICs and exec's
``mpirun``. On TPU the transport is fixed (ICI within a slice, DCN across
hosts) and process bring-up is one process per host handing control to
``jax.distributed.initialize`` — so this launcher:

- single host, ``-np N``: prepares an environment in which exactly N
  worker devices are used (the first N real chips, or with ``--platform
  cpu`` a forced N-device virtual CPU platform for development) and
  execs the command;
- multi host (``-H``/``--hostfile``): starts one process per host over
  ssh, each with ``BLUEFOG_COORDINATOR/NUM_PROCESSES/PROCESS_ID`` set;
  :func:`bluefog_tpu.context.init` picks these up and calls
  ``jax.distributed.initialize`` before building the mesh.

Environment contract consumed by :mod:`bluefog_tpu.context`:

==========================  =================================================
``BLUEFOG_NUM_WORKERS``     total worker-device count the mesh must have
``BLUEFOG_COORDINATOR``     ``host:port`` of the jax.distributed coordinator
``BLUEFOG_NUM_PROCESSES``   number of controller processes (hosts)
``BLUEFOG_PROCESS_ID``      this process's index
``BLUEFOG_TIMELINE``        timeline file prefix (reference parity)
==========================  =================================================
"""

import argparse
import os
import shlex
import subprocess
import sys
import time
from typing import Callable, Dict, List, Sequence, Tuple

from bluefog_tpu.run import network_util
from bluefog_tpu.platforms import (
    with_cpu_device_count,
    with_exact_cpu_device_count,
)

__all__ = [
    "parse_args",
    "build_child_env",
    "build_host_commands",
    "resolve_max_restarts",
    "backoff_seconds",
    "run_with_restarts",
    "flight_artifacts",
    "report_flight_artifacts",
    "main",
]

DEFAULT_COORDINATOR_PORT = 9781

# Env prefixes forwarded to remote hosts (the reference forwards every
# exportable env over mpirun -x, run/run.py:196; ssh does not inherit the
# caller's environment so the launcher re-exports these explicitly).
_FORWARD_PREFIXES = ("BLUEFOG_", "JAX_", "XLA_", "LIBTPU_", "TPU_")


def parse_args(argv: Sequence[str] = None) -> argparse.Namespace:
    parser = argparse.ArgumentParser(
        prog="bfrun-tpu", description="Bluefog TPU Runner"
    )
    parser.add_argument(
        "-v", "--version", action="store_true", dest="version",
        help="Shows bluefog_tpu version.",
    )
    parser.add_argument(
        "-np", "--num-proc", action="store", dest="np", type=int,
        help="Total number of workers (mesh devices).",
    )
    parser.add_argument(
        "--platform", action="store", dest="platform", default="auto",
        choices=("auto", "cpu", "tpu"),
        help="Backend for the workers. 'cpu' forces an -np-device virtual "
        "CPU platform (development mode) and 'tpu' the chips; 'auto' "
        "leaves JAX's own choice (JAX_PLATFORMS) alone. Fewer than -np "
        "devices on the chosen backend is an error.",
    )

    group_hosts = parser.add_mutually_exclusive_group()
    group_hosts.add_argument(
        "-H", "--hosts", action="store", dest="hosts",
        help="Comma-separated <hostname>:<slots> list (slots = worker "
        "devices on that host), e.g. host1:4,host2:4.",
    )
    group_hosts.add_argument(
        "-hostfile", "--hostfile", action="store", dest="hostfile",
        help="Path to a host file of '<hostname> slots=<n>' lines.",
    )
    parser.add_argument(
        "-p", "--ssh-port", action="store", dest="ssh_port", type=int,
        help="SSH port on all the hosts.",
    )
    parser.add_argument(
        "--coordinator", action="store", dest="coordinator",
        help="host:port of the jax.distributed coordinator. Set "
        "automatically in -H/--hostfile mode; pass explicitly when each "
        "host process is started by an external scheduler.",
    )
    parser.add_argument(
        "--num-processes", action="store", dest="num_processes", type=int,
        help="Total controller processes (with --coordinator).",
    )
    parser.add_argument(
        "--process-id", action="store", dest="process_id", type=int,
        help="This process's index (with --coordinator).",
    )
    parser.add_argument(
        "--timeline-filename", action="store", dest="timeline_filename",
        help="Prefix for per-process Chrome-trace timeline files "
        "(sets BLUEFOG_TIMELINE).",
    )
    parser.add_argument(
        "--flight-dir", action="store", dest="flight_dir",
        help="Directory for flight-recorder dumps (sets "
        "BLUEFOG_FLIGHT_DIR): each process writes "
        "flight_<process_id>.json there on stall/verdict/crash/SIGTERM, "
        "and the launcher lists the collected artifacts after a failed "
        "run — fuse them with tools/trace_merge.py (docs/flight.md).",
    )
    parser.add_argument(
        "--remote-python", action="store", dest="remote_python",
        default="python3",
        help="Interpreter used to run bare .py commands on REMOTE hosts "
        "(default python3). Locally the launcher's own sys.executable is "
        "used; its absolute path may not exist on other machines.",
    )
    parser.add_argument(
        "--max-restarts", action="store", dest="max_restarts", type=int,
        default=None,
        help="Restart a worker process that exits nonzero up to this many "
        "times, with exponential backoff (default from "
        "BLUEFOG_MAX_RESTARTS, else 0 = fail fast). The elastic subsystem "
        "(docs/elastic.md) handles in-run repair; this handles the process "
        "layer.",
    )
    parser.add_argument(
        "--extra-env", action="append", dest="extra_env", default=[],
        metavar="KEY=VALUE",
        help="Extra environment variable for the launched processes "
        "(repeatable).",
    )
    parser.add_argument(
        "--verbose", action="store_true", dest="verbose",
        help="Print the launch plan before executing.",
    )
    parser.add_argument(
        "command", nargs=argparse.REMAINDER, help="Command to be executed."
    )

    args = parser.parse_args(argv)
    if args.command and args.command[0] == "--":
        args.command = args.command[1:]  # argparse REMAINDER keeps the sep
    if not args.version and not args.np:
        parser.error("argument -np/--num-proc is required")
    if (args.coordinator is None) != (args.num_processes is None):
        parser.error("--coordinator and --num-processes must be given together")
    return args


def _parse_extra_env(pairs: Sequence[str]) -> Dict[str, str]:
    out = {}
    for pair in pairs:
        if "=" not in pair:
            raise ValueError(f"--extra-env expects KEY=VALUE, got {pair!r}")
        k, v = pair.split("=", 1)
        out[k] = v
    return out


def _cpu_platform(env: Dict[str, str]) -> bool:
    return env.get("JAX_PLATFORMS", "").strip().lower() == "cpu"


def build_child_env(
    args, base_env: Dict[str, str], cpu_count: int = None
) -> Dict[str, str]:
    """The environment for a launched worker process (pure; unit tested).

    ``cpu_count`` is how many virtual CPU devices THIS process should be
    able to expose — the pod-wide ``-np`` on a single host, the host's
    slot count in multi-host mode (each controller owns only its local
    devices). ``None`` defaults to ``args.np``.
    """
    env = dict(base_env)
    env["BLUEFOG_NUM_WORKERS"] = str(args.np)
    if args.platform != "auto":
        env["JAX_PLATFORMS"] = args.platform
    if _cpu_platform(env):
        # The virtual CPU devices exist only where CPU was chosen — by
        # --platform cpu or by an inherited JAX_PLATFORMS=cpu. 0 means
        # the caller sets a per-host count itself.
        count = args.np if cpu_count is None else cpu_count
        if count > 0:
            env["XLA_FLAGS"] = with_cpu_device_count(
                env.get("XLA_FLAGS", ""), count
            )
    if args.timeline_filename:
        env["BLUEFOG_TIMELINE"] = args.timeline_filename
    if getattr(args, "flight_dir", None):
        env["BLUEFOG_FLIGHT_DIR"] = args.flight_dir
    if args.coordinator:
        env["BLUEFOG_COORDINATOR"] = args.coordinator
        env["BLUEFOG_NUM_PROCESSES"] = str(args.num_processes)
        env["BLUEFOG_PROCESS_ID"] = str(args.process_id or 0)
    env.update(_parse_extra_env(args.extra_env))
    return env


def resolve_max_restarts(args, env: Dict[str, str] = None) -> int:
    """The effective restart budget (pure; unit tested): the CLI flag
    wins, then ``BLUEFOG_MAX_RESTARTS``, then 0 (fail fast). Negative
    values are rejected — an unbounded restart loop hides a crash-looping
    job from its operator."""
    env = os.environ if env is None else env
    value = getattr(args, "max_restarts", None)
    if value is None:
        raw = env.get("BLUEFOG_MAX_RESTARTS", "0")
        try:
            value = int(raw)
        except ValueError:
            raise ValueError(
                f"BLUEFOG_MAX_RESTARTS must be an integer, got {raw!r}"
            )
    if value < 0:
        raise ValueError(f"max restarts must be >= 0, got {value}")
    return value


def backoff_seconds(attempt: int, base: float = 1.0, cap: float = 30.0) -> float:
    """Exponential backoff before restart ``attempt`` (0-based): ``base *
    2**attempt`` capped at ``cap`` (pure; unit tested)."""
    assert attempt >= 0
    return min(float(cap), float(base) * (2.0 ** attempt))


def run_with_restarts(
    start: Callable[[], int],
    max_restarts: int,
    sleep: Callable[[float], None] = time.sleep,
    base: float = 1.0,
    log=None,
) -> int:
    """Run ``start()`` (returning an exit code), restarting on nonzero
    exit up to ``max_restarts`` times with exponential backoff. Returns
    the final exit code. Pure given injected ``start``/``sleep`` — the
    unit-testable core of ``--max-restarts``."""
    attempt = 0
    while True:
        rc = start()
        if rc == 0 or attempt >= max_restarts:
            return rc
        delay = backoff_seconds(attempt, base=base)
        if log is not None:
            log(
                f"[bfrun-tpu] worker exited with {rc}; restart "
                f"{attempt + 1}/{max_restarts} in {delay:g}s"
            )
        sleep(delay)
        attempt += 1


def flight_artifacts(flight_dir: str) -> List[str]:
    """The postmortem files a failed run left behind (pure; unit
    tested): flight dumps and per-process timeline JSONs under
    ``--flight-dir``, sorted. Empty when the directory is missing —
    a failure before any dump trigger is not a launcher error."""
    if not flight_dir or not os.path.isdir(flight_dir):
        return []
    return sorted(
        os.path.join(flight_dir, f)
        for f in os.listdir(flight_dir)
        if f.endswith(".json")
    )


def report_flight_artifacts(flight_dir: str, out=None) -> List[str]:
    """After a nonzero exit: list the collected per-rank dumps/traces
    and print the one command that fuses them into a postmortem. The
    launcher is the only place that knows the run failed AND where
    every process was told to dump — this closes the loop so the
    operator is never left grepping hosts for evidence."""
    out = out or sys.stderr
    files = flight_artifacts(flight_dir)
    if not files:
        return files
    print(
        f"[bfrun-tpu] flight artifacts in {flight_dir}:", file=out
    )
    for f in files:
        print(f"[bfrun-tpu]   {f}", file=out)
    print(
        "[bfrun-tpu] postmortem: python tools/trace_merge.py "
        f"{flight_dir}", file=out,
    )
    return files


def _command_argv(
    command: Sequence[str], interpreter: str = None
) -> List[str]:
    """Run bare ``script.py`` through an interpreter: the launcher's own
    ``sys.executable`` locally, a configurable command name for remote
    hosts (the local absolute path — e.g. a venv — may not exist there)."""
    command = list(command)
    if command and command[0].endswith(".py"):
        return [interpreter or sys.executable] + command
    return command


def build_host_commands(
    args, hosts: Sequence[network_util.HostSlots]
) -> List[Tuple[str, List[str]]]:
    """(host, argv) per controller process for multi-host launch (pure).

    Process i runs on hosts[i] with the coordinator on hosts[0]. Worker
    count per host comes from the host's slot count; BLUEFOG_NUM_WORKERS
    is the pod-wide total so every controller builds the same mesh.
    """
    total_slots = sum(h.slots for h in hosts)
    if args.np != total_slots:
        raise ValueError(
            f"-np {args.np} does not match the {total_slots} total host "
            f"slots in {[tuple(h) for h in hosts]}"
        )
    coordinator = args.coordinator
    if coordinator is None:
        # A local alias ('localhost') would resolve to the WRONG machine on
        # the remote hosts; substitute a name they can route to.
        coord_host = hosts[0].host
        if network_util.is_local_address(coord_host):
            coord_host = network_util.reachable_local_name()
        coordinator = f"{coord_host}:{DEFAULT_COORDINATOR_PORT}"
    # Forward ambient BLUEFOG_/JAX_/XLA_/TPU_ vars the way the reference
    # forwards exportable envs through mpirun -x (ssh starts a fresh env).
    forwarded = {
        key: val
        for key, val in os.environ.items()
        if key.startswith(_FORWARD_PREFIXES)
    }
    env = build_child_env(args, base_env=forwarded, cpu_count=0)
    env["BLUEFOG_COORDINATOR"] = coordinator
    env["BLUEFOG_NUM_PROCESSES"] = str(len(hosts))

    commands = []
    for i, hs in enumerate(hosts):
        proc_env = dict(env)
        if _cpu_platform(proc_env):
            # Each controller exposes EXACTLY its own host's worker
            # devices; an inherited larger count would break the pod-wide
            # device-count invariant checked by context._resolve_devices.
            proc_env["XLA_FLAGS"] = with_exact_cpu_device_count(
                proc_env.get("XLA_FLAGS", ""), hs.slots
            )
        proc_env["BLUEFOG_PROCESS_ID"] = str(i)
        env_prefix = ["env"] + [
            f"{k}={v}" for k, v in sorted(proc_env.items())
        ]
        local = network_util.is_local_address(hs.host)
        argv = env_prefix + _command_argv(
            args.command,
            interpreter=None if local else getattr(
                args, "remote_python", "python3"
            ),
        )
        if local:
            commands.append((hs.host, argv))
        else:
            ssh = ["ssh", "-o", "BatchMode=yes"]
            if args.ssh_port:
                ssh += ["-p", str(args.ssh_port)]
            ssh.append(hs.host)
            ssh.append(" ".join(shlex.quote(a) for a in argv))
            commands.append((hs.host, ssh))
    return commands


def main(argv: Sequence[str] = None) -> int:
    args = parse_args(argv)

    if args.version:
        from bluefog_tpu.version import __version__

        print(__version__)
        return 0

    if not args.command:
        print("bfrun-tpu: no command to execute", file=sys.stderr)
        return 2

    if args.flight_dir:
        # the collection dir must exist before the workers' timeline /
        # flight writers try to open files inside it
        os.makedirs(args.flight_dir, exist_ok=True)

    if args.hosts or args.hostfile:
        hosts = (
            network_util.parse_hosts(args.hosts)
            if args.hosts
            else network_util.parse_hostfile(args.hostfile)
        )
        if len(hosts) == 1 and network_util.is_local_address(hosts[0].host):
            pass  # single local host: fall through to the exec path
        else:
            commands = build_host_commands(args, hosts)
            if args.verbose:
                for host, argv_ in commands:
                    print(f"[bfrun-tpu] {host}: {' '.join(argv_)}")
            max_restarts = resolve_max_restarts(args)

            def launch_pod() -> int:
                # jax.distributed is a static world: one host dying tears
                # down the coordinator, so the restart unit is the whole
                # pod launch (in-run rank survival is the elastic
                # subsystem's job, docs/elastic.md). POLL rather than
                # wait sequentially: a dead host leaves the survivors'
                # ranks blocked in collectives forever, so waiting on a
                # hung survivor would mean the failure is never observed
                # — on the first nonzero exit the remaining processes
                # are terminated so a relaunch can rebind the
                # coordinator port.
                procs = [
                    subprocess.Popen(argv_) for _host, argv_ in commands
                ]
                rc = 0
                try:
                    while any(p.poll() is None for p in procs):
                        for (host, _), proc in zip(commands, procs):
                            code = proc.poll()
                            if code is not None and code != 0:
                                print(
                                    f"[bfrun-tpu] process on {host} "
                                    f"exited with {code}; terminating "
                                    "the pod", file=sys.stderr,
                                )
                                return code
                        time.sleep(0.5)
                    for proc in procs:
                        if proc.returncode != 0 and rc == 0:
                            rc = proc.returncode
                    return rc
                finally:
                    for proc in procs:
                        if proc.poll() is None:
                            proc.terminate()
                    for proc in procs:
                        try:
                            proc.wait(timeout=10)
                        except subprocess.TimeoutExpired:
                            proc.kill()
                            proc.wait()

            rc = run_with_restarts(
                launch_pod, max_restarts,
                log=lambda msg: print(msg, file=sys.stderr),
            )
            if rc != 0:
                # SIGTERM from the pod teardown above triggered each
                # local process's flight dump; remote hosts dumped into
                # their own --flight-dir (same path, forwarded env)
                report_flight_artifacts(args.flight_dir)
            return rc

    env = build_child_env(args, base_env=dict(os.environ))
    argv_ = _command_argv(args.command)
    max_restarts = resolve_max_restarts(args)
    if args.verbose:
        print(f"[bfrun-tpu] exec: {' '.join(argv_)}")
    if max_restarts > 0 or args.flight_dir:
        # exec would forfeit the supervisor; keep a parent to restart
        # from — and, with --flight-dir, to list the postmortem
        # artifacts after a failed run
        rc = run_with_restarts(
            lambda: subprocess.run(argv_, env=env).returncode,
            max_restarts,
            log=lambda msg: print(msg, file=sys.stderr),
        )
        if rc != 0:
            report_flight_artifacts(args.flight_dir)
        return rc
    os.execvpe(argv_[0], argv_, env)
    raise AssertionError("unreachable")  # pragma: no cover


if __name__ == "__main__":
    sys.exit(main())
