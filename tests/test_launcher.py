# Copyright 2026. Licensed under the Apache License, Version 2.0.
"""Launcher layer: arg surface, env contract, host bring-up, multi-process
context branches (reference run/run.py:58-203 parity)."""

import os
import subprocess
import sys

import pytest

from bluefog_tpu.run import network_util
from bluefog_tpu.run.run import (
    build_child_env,
    build_host_commands,
    parse_args,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- network_util --------------------------------------------------------------


def test_parse_hosts():
    hosts = network_util.parse_hosts("host1:2,host2:4,host3")
    assert hosts == [("host1", 2), ("host2", 4), ("host3", 1)]


def test_parse_hosts_empty_raises():
    with pytest.raises(ValueError):
        network_util.parse_hosts(" , ")


def test_parse_hostfile(tmp_path):
    hf = tmp_path / "hosts"
    hf.write_text(
        "# pod hosts\nhost1 slots=4\n\nhost2 slots = 4  # trailing\nhost3\n"
    )
    assert network_util.parse_hostfile(str(hf)) == [
        ("host1", 4),
        ("host2", 4),
        ("host3", 1),
    ]


def test_parse_hostfile_malformed(tmp_path):
    hf = tmp_path / "hosts"
    hf.write_text("host1 slots=two\n")
    with pytest.raises(ValueError):
        network_util.parse_hostfile(str(hf))


def test_filter_local_addresses():
    remote = network_util.filter_local_addresses(
        ["localhost", "127.0.0.1", "farawayhost"]
    )
    assert remote == ["farawayhost"]


# -- arg surface (reference run/run.py:58-118) ---------------------------------


def test_parse_args_requires_np():
    with pytest.raises(SystemExit):
        parse_args(["train.py"])


def test_parse_args_surface():
    args = parse_args(
        [
            "-np", "8", "--platform", "cpu", "--timeline-filename", "/tmp/tl",
            "--extra-env", "FOO=1", "--verbose", "train.py", "--lr", "0.1",
        ]
    )
    assert args.np == 8
    assert args.platform == "cpu"
    assert args.command == ["train.py", "--lr", "0.1"]


def test_parse_args_coordinator_pair_required():
    with pytest.raises(SystemExit):
        parse_args(["-np", "8", "--coordinator", "h:1", "x.py"])


# -- env contract --------------------------------------------------------------


def test_child_env_cpu_mode():
    args = parse_args(["-np", "4", "--platform", "cpu", "x.py"])
    env = build_child_env(args, base_env={})
    assert env["JAX_PLATFORMS"] == "cpu"
    assert env["BLUEFOG_NUM_WORKERS"] == "4"
    assert "--xla_force_host_platform_device_count=4" in env["XLA_FLAGS"]


def test_child_env_auto_keeps_platform_and_ambient_env_intact():
    args = parse_args(["-np", "4", "x.py"])
    before = os.environ.get("XLA_FLAGS")
    env = build_child_env(args, base_env={"PATH": "/bin"})
    assert "JAX_PLATFORMS" not in env
    assert env["PATH"] == "/bin"
    # no virtual CPU devices are prepared behind the chosen backend's
    # back: a short chip count must surface as bf.init()'s error
    assert "XLA_FLAGS" not in env
    assert os.environ.get("XLA_FLAGS") == before  # launcher env untouched


def test_child_env_auto_inherits_cpu_choice_from_outside():
    args = parse_args(["-np", "4", "x.py"])
    env = build_child_env(args, base_env={"JAX_PLATFORMS": "cpu"})
    assert env["JAX_PLATFORMS"] == "cpu"
    assert "--xla_force_host_platform_device_count=4" in env["XLA_FLAGS"]


def test_child_env_tpu_mode_pins_the_backend():
    args = parse_args(["-np", "4", "--platform", "tpu", "x.py"])
    env = build_child_env(args, base_env={"JAX_PLATFORMS": "cpu"})
    assert env["JAX_PLATFORMS"] == "tpu"
    assert "XLA_FLAGS" not in env


def test_child_env_timeline_and_extra():
    args = parse_args(
        ["-np", "2", "--timeline-filename", "/tmp/tl_", "--extra-env",
         "A=b", "x.py"]
    )
    env = build_child_env(args, base_env={})
    assert env["BLUEFOG_TIMELINE"] == "/tmp/tl_"
    assert env["A"] == "b"


def test_child_env_coordinator():
    args = parse_args(
        ["-np", "8", "--coordinator", "h0:9781", "--num-processes", "2",
         "--process-id", "1", "x.py"]
    )
    env = build_child_env(args, base_env={})
    assert env["BLUEFOG_COORDINATOR"] == "h0:9781"
    assert env["BLUEFOG_NUM_PROCESSES"] == "2"
    assert env["BLUEFOG_PROCESS_ID"] == "1"


# -- multi-host bring-up -------------------------------------------------------


def test_host_commands_slots_mismatch():
    args = parse_args(["-np", "4", "-H", "h1:4,h2:4", "x.py"])
    hosts = network_util.parse_hosts(args.hosts)
    with pytest.raises(ValueError):
        build_host_commands(args, hosts)


def test_host_commands_shape():
    args = parse_args(["-np", "8", "-H", "localhost:4,far1:4", "x.py"])
    hosts = network_util.parse_hosts(args.hosts)
    cmds = build_host_commands(args, hosts)
    assert len(cmds) == 2
    # process 0 on the local host: plain env-wrapped python
    host0, argv0 = cmds[0]
    assert argv0[0] == "env"
    joined0 = " ".join(argv0)
    assert "BLUEFOG_PROCESS_ID=0" in joined0
    assert "BLUEFOG_NUM_PROCESSES=2" in joined0
    # 'localhost' would resolve to the remote machine itself; the
    # coordinator must be advertised under a routable name.
    assert "BLUEFOG_COORDINATOR=localhost:" not in joined0
    assert (
        f"BLUEFOG_COORDINATOR={network_util.reachable_local_name()}:"
        in joined0
    )
    # each controller exposes only its own host's worker devices
    assert "--xla_force_host_platform_device_count=4" in joined0
    assert sys.executable in argv0  # .py command runs under the interpreter
    # process 1 remote: ssh wrapper
    host1, argv1 = cmds[1]
    assert argv1[0] == "ssh" and "far1" in argv1
    assert "BLUEFOG_PROCESS_ID=1" in argv1[-1]


def test_host_commands_forward_ambient_xla_flags(monkeypatch):
    monkeypatch.setenv("XLA_FLAGS", "--xla_dump_to=/tmp/d")
    args = parse_args(["-np", "2", "-H", "far1:1,far2:1", "x.py"])
    cmds = build_host_commands(args, network_util.parse_hosts(args.hosts))
    for _h, argv in cmds:
        joined = argv[-1]  # remote: quoted command string
        assert "--xla_dump_to=/tmp/d" in joined
        assert "--xla_force_host_platform_device_count=1" in joined


def test_host_commands_ssh_port():
    args = parse_args(["-np", "2", "-H", "far1:1,far2:1", "-p", "2222", "x.py"])
    cmds = build_host_commands(args, network_util.parse_hosts(args.hosts))
    assert all("-p" in argv and "2222" in argv for _h, argv in cmds)


# -- multi-process context branches (mocked process topology) ------------------


class FakeDev:
    def __init__(self, process_index, ident):
        self.process_index = process_index
        self.ident = ident

    def __repr__(self):
        return f"d{self.ident}@p{self.process_index}"


def test_order_devices_for_mesh_groups_by_process():
    from bluefog_tpu.context import order_devices_for_mesh

    devs = [FakeDev(pi, i) for i, pi in enumerate([1, 0, 1, 0])]
    ordered = order_devices_for_mesh(devs, multi_process=True)
    assert [d.process_index for d in ordered] == [0, 0, 1, 1]
    # stable within each process group
    assert [d.ident for d in ordered] == [1, 3, 0, 2]


def test_default_nodes_per_machine():
    from bluefog_tpu.context import default_nodes_per_machine

    devs = [FakeDev(pi, i) for i, pi in enumerate([0, 0, 0, 1, 1, 1])]
    assert default_nodes_per_machine(devs, process_count=2) == 3
    assert default_nodes_per_machine(devs, process_count=1) is None


def test_resolve_devices_raises_instead_of_switching_backend():
    """A backend with fewer devices than BLUEFOG_NUM_WORKERS is an error:
    no other platform is looked at and no default device is re-pinned."""
    import jax

    from bluefog_tpu.context import _resolve_devices

    devices = jax.devices()
    assert _resolve_devices(None) == list(devices)
    assert _resolve_devices(2) == list(devices[:2])
    with pytest.raises(RuntimeError, match="backend has only"):
        _resolve_devices(len(devices) + 1)
    assert jax.config.jax_default_device is None


def test_compile_cache_obeys_env_else_fixed_path(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set: the cache is there and nothing is
    set in code; unset: one fixed, git-ignored path in the checkout."""
    import jax

    from bluefog_tpu import context as ctx

    before = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/outside")
        assert ctx.configure_compile_cache() == "/placed/outside"
        assert jax.config.jax_compilation_cache_dir is None
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert ctx.configure_compile_cache() == ctx.COMPILE_CACHE_DIR
        assert jax.config.jax_compilation_cache_dir == os.path.join(
            REPO, ".jax_cache"
        )
        with open(os.path.join(REPO, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_maybe_init_distributed(monkeypatch):
    """Argument-contract check only (env -> initialize kwargs); the real
    two-process bring-up is proven end-to-end in test_multiprocess.py."""
    import jax

    from bluefog_tpu import context as ctx

    calls = {}

    def fake_initialize(coordinator_address, num_processes, process_id):
        calls.update(
            addr=coordinator_address, n=num_processes, pid=process_id
        )

    monkeypatch.setattr(jax.distributed, "initialize", fake_initialize)
    monkeypatch.setattr(ctx, "_distributed_initialized", False)
    monkeypatch.setenv("BLUEFOG_COORDINATOR", "h0:9781")
    monkeypatch.setenv("BLUEFOG_NUM_PROCESSES", "4")
    monkeypatch.setenv("BLUEFOG_PROCESS_ID", "3")
    assert ctx.maybe_init_distributed() is True
    assert calls == {"addr": "h0:9781", "n": 4, "pid": 3}
    # second call is a no-op
    assert ctx.maybe_init_distributed() is False


def test_maybe_init_distributed_without_env(monkeypatch):
    from bluefog_tpu import context as ctx

    monkeypatch.delenv("BLUEFOG_COORDINATOR", raising=False)
    monkeypatch.setattr(ctx, "_distributed_initialized", False)
    assert ctx.maybe_init_distributed() is False


# -- end-to-end: bfrun-tpu launches a real program -----------------------------


E2E_SCRIPT = """
import bluefog_tpu as bf
import jax, numpy as np
bf.init()
assert bf.size() == 4, bf.size()
x = np.arange(4 * 3, dtype=np.float32).reshape(4, 3)
y = bf.neighbor_allreduce(jax.device_put(x, jax.sharding.NamedSharding(
    bf.get_context().mesh, jax.sharding.PartitionSpec("workers"))))
assert np.asarray(y).shape == (4, 3)
print("E2E_OK")
"""


def test_bfrun_end_to_end(tmp_path):
    script = tmp_path / "prog.py"
    script.write_text(E2E_SCRIPT)
    env = {
        k: v
        for k, v in os.environ.items()
        if k not in ("XLA_FLAGS", "JAX_PLATFORMS", "BLUEFOG_NUM_WORKERS")
    }
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [
            sys.executable, "-m", "bluefog_tpu.run.run", "-np", "4",
            "--platform", "cpu", str(script),
        ],
        capture_output=True,
        text=True,
        timeout=240,
        env=env,
        cwd=str(tmp_path),
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "E2E_OK" in out.stdout


def test_bfrun_version():
    out = subprocess.run(
        [sys.executable, "-m", "bluefog_tpu.run.run", "--version"],
        capture_output=True,
        text=True,
        timeout=120,
        env={**os.environ, "PYTHONPATH": REPO},
    )
    assert out.returncode == 0
    assert out.stdout.strip()


def test_ibfrun_start_executes_env_contract(tmp_path):
    """``ibfrun-tpu start -np 4 <cmd>`` must exec the child with the
    launcher env contract applied (worker count, dev platform) and the
    stall watchdog defaulted OFF for interactive think time."""
    import subprocess
    import sys

    probe = tmp_path / "probe.py"
    probe.write_text(
        "import os\n"
        "assert os.environ['BLUEFOG_NUM_WORKERS'] == '4', os.environ\n"
        "assert os.environ['BLUEFOG_STALL_TIMEOUT'] == '0', os.environ\n"
        "import jax\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "import bluefog_tpu as bf\n"
        "bf.init()\n"
        "assert bf.size() == 4, bf.size()\n"
        "import numpy as np\n"
        "x = bf.worker_values(lambda r: np.full((2,), float(r), np.float32))\n"
        "for _ in range(20):\n"
        "    x = bf.neighbor_allreduce(x)\n"
        "mse = float(np.mean((np.asarray(x) - 1.5) ** 2))\n"
        "assert mse < 1e-6, mse\n"
        "bf.suspend(); bf.resume(); bf.shutdown()\n"
        "print('IBFRUN_OK')\n"
    )
    env = {
        k: v for k, v in os.environ.items()
        if k not in ("XLA_FLAGS", "JAX_PLATFORMS")
    }
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-m", "bluefog_tpu.run.interactive_run",
         "start", "-np", "4", "--platform", "cpu",
         sys.executable, str(probe)],
        capture_output=True, text=True, timeout=240, env=env,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "IBFRUN_OK" in out.stdout


def test_interactive_notebook_cells_execute(tmp_path):
    """The committed notebook example (reference
    examples/interactive_bluefog_helloworld.ipynb analogue) must stay
    runnable: execute its code cells in order in a child interpreter
    under the ibfrun env contract."""
    import json
    import subprocess
    import sys

    nb_path = os.path.join(REPO, "examples", "interactive_helloworld.ipynb")
    with open(nb_path) as f:
        nb = json.load(f)
    cells = [
        "".join(c["source"]) for c in nb["cells"]
        if c["cell_type"] == "code"
    ]
    script = tmp_path / "nb.py"
    script.write_text("\n\n".join(cells))
    env = {
        k: v for k, v in os.environ.items()
        if k not in ("XLA_FLAGS", "JAX_PLATFORMS")
    }
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-m", "bluefog_tpu.run.interactive_run",
         "start", "-np", "8", "--platform", "cpu",
         sys.executable, str(script)],
        capture_output=True, text=True, timeout=240, env=env,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "done" in out.stdout


# -- restart policy (--max-restarts / BLUEFOG_MAX_RESTARTS) --------------------


def test_resolve_max_restarts_precedence():
    from bluefog_tpu.run.run import resolve_max_restarts

    flag = parse_args(["-np", "2", "--max-restarts", "3", "x.py"])
    assert resolve_max_restarts(flag, env={"BLUEFOG_MAX_RESTARTS": "9"}) == 3
    noflag = parse_args(["-np", "2", "x.py"])
    assert resolve_max_restarts(noflag, env={"BLUEFOG_MAX_RESTARTS": "5"}) == 5
    assert resolve_max_restarts(noflag, env={}) == 0
    with pytest.raises(ValueError):
        resolve_max_restarts(noflag, env={"BLUEFOG_MAX_RESTARTS": "many"})
    with pytest.raises(ValueError):
        resolve_max_restarts(
            parse_args(["-np", "2", "--max-restarts", "-1", "x.py"]), env={}
        )


def test_backoff_is_exponential_and_capped():
    from bluefog_tpu.run.run import backoff_seconds

    assert [backoff_seconds(a, base=1.0, cap=30.0) for a in range(6)] == [
        1.0, 2.0, 4.0, 8.0, 16.0, 30.0
    ]
    assert backoff_seconds(50, base=1.0, cap=30.0) == 30.0


def test_run_with_restarts_retries_then_succeeds():
    from bluefog_tpu.run.run import run_with_restarts

    codes = iter([1, 1, 0])
    sleeps, logs = [], []
    rc = run_with_restarts(
        lambda: next(codes), max_restarts=5, sleep=sleeps.append,
        log=logs.append,
    )
    assert rc == 0
    assert sleeps == [1.0, 2.0]  # exponential backoff between attempts
    assert len(logs) == 2 and "restart 1/5" in logs[0]


def test_run_with_restarts_exhausts_budget():
    from bluefog_tpu.run.run import run_with_restarts

    calls = []
    rc = run_with_restarts(
        lambda: calls.append(1) or 7, max_restarts=2,
        sleep=lambda s: None,
    )
    assert rc == 7
    assert len(calls) == 3  # initial + 2 restarts


def test_run_with_restarts_zero_budget_fails_fast():
    from bluefog_tpu.run.run import run_with_restarts

    calls = []
    rc = run_with_restarts(
        lambda: calls.append(1) or 3, max_restarts=0,
        sleep=lambda s: (_ for _ in ()).throw(AssertionError("no sleep")),
    )
    assert rc == 3 and len(calls) == 1
