"""Cluster launcher: ``ray_tpu up / down`` from a YAML spec.

Analog of the reference's ``ray up`` (``python/ray/autoscaler/_private/
commands.py`` + ``command_runner.py``): a cluster YAML names the head and
worker hosts; ``up`` starts the head there, reads its ``tcp://`` address,
and joins every worker host as a node agent; ``down`` tears everything
back down.  Command execution goes through a pluggable runner:

- ``SSHCommandRunner`` — real multi-host clusters over ``ssh`` (the
  reference's path),
- ``LocalCommandRunner`` — runs the same commands through a local shell
  (single-host bring-up and the hermetic test double, the
  ``fake_multi_node`` role).

YAML shape::

    cluster_name: demo
    provider: {type: local}          # or ssh
    auth: {ssh_user: ubuntu, ssh_private_key: ~/.ssh/key.pem}
    head_node: {address: 10.0.0.1, num_cpus: 8, num_tpus: 4}
    worker_nodes:
      - {address: 10.0.0.2, num_cpus: 8, num_tpus: 4}
    head_start_extra: "--dashboard-port 8265"
"""

from __future__ import annotations

import json
import shlex
import subprocess
import sys
import time
from typing import Any, Dict, List, Optional


class CommandRunner:
    """Run a shell command 'on' a host; subclasses decide transport."""

    def run(self, address: str, cmd: str, timeout: float = 300.0) -> str:
        raise NotImplementedError


class LocalCommandRunner(CommandRunner):
    """Execute on this machine (single-host clusters + hermetic tests)."""

    def run(self, address: str, cmd: str, timeout: float = 300.0) -> str:
        proc = subprocess.run(
            ["bash", "-lc", cmd], capture_output=True, text=True,
            timeout=timeout,
        )
        if proc.returncode != 0:
            raise RuntimeError(
                f"[{address}] command failed ({proc.returncode}): "
                f"{cmd}\n{proc.stderr[-2000:]}")
        return proc.stdout


class SSHCommandRunner(CommandRunner):
    """ssh into each host (the reference's default transport)."""

    def __init__(self, ssh_user: Optional[str] = None,
                 ssh_private_key: Optional[str] = None,
                 ssh_options: Optional[List[str]] = None):
        self.ssh_user = ssh_user
        self.ssh_private_key = ssh_private_key
        self.ssh_options = list(ssh_options or [
            "-o", "StrictHostKeyChecking=no",
            "-o", "ConnectTimeout=15",
        ])

    def run(self, address: str, cmd: str, timeout: float = 300.0) -> str:
        target = f"{self.ssh_user}@{address}" if self.ssh_user else address
        argv = ["ssh", *self.ssh_options]
        if self.ssh_private_key:
            argv += ["-i", self.ssh_private_key]
        argv += [target, cmd]
        proc = subprocess.run(argv, capture_output=True, text=True,
                              timeout=timeout)
        if proc.returncode != 0:
            raise RuntimeError(
                f"[{address}] ssh command failed ({proc.returncode}): "
                f"{cmd}\n{proc.stderr[-2000:]}")
        return proc.stdout


def _runner_for(config: Dict[str, Any]) -> CommandRunner:
    provider = (config.get("provider") or {}).get("type", "ssh")
    if provider == "local":
        return LocalCommandRunner()
    if provider == "ssh":
        auth = config.get("auth") or {}
        return SSHCommandRunner(
            ssh_user=auth.get("ssh_user"),
            ssh_private_key=auth.get("ssh_private_key"),
            ssh_options=auth.get("ssh_options"),
        )
    raise ValueError(f"unknown provider type {provider!r} (local|ssh)")


def _node_flags(node: Dict[str, Any]) -> str:
    parts = []
    if node.get("num_cpus") is not None:
        parts += ["--num-cpus", str(node["num_cpus"])]
    if node.get("num_tpus") is not None:
        parts += ["--num-tpus", str(node["num_tpus"])]
    return " ".join(parts)


def load_cluster_config(path: str) -> Dict[str, Any]:
    import yaml

    with open(path) as f:
        config = yaml.safe_load(f)
    if not isinstance(config, dict) or "head_node" not in config:
        raise ValueError(f"{path}: cluster YAML needs at least a head_node")
    return config


def up(config: Dict[str, Any], runner: Optional[CommandRunner] = None,
       python: str = sys.executable) -> Dict[str, Any]:
    """Start the head, read its session record, join every worker host.
    Returns {"address", "authkey", "head_pid", "workers": [...]}."""
    runner = runner or _runner_for(config)
    head = config["head_node"]
    head_addr = head.get("address", "127.0.0.1")
    name = config.get("cluster_name", "cluster")

    remote_head = head_addr not in ("127.0.0.1", "localhost")
    # a multi-host head must bind its control plane on all interfaces, or
    # remote workers' dials are refused (the default bind is loopback)
    env_prefix = "RAY_TPU_HOST=0.0.0.0 " if remote_head else ""
    head_cmd = (
        f"{env_prefix}nohup {shlex.quote(python)} -m ray_tpu start --head "
        f"{_node_flags(head)} {config.get('head_start_extra', '')} "
        f"> /tmp/ray_tpu_{name}_head.log 2>&1 & echo $!"
    )
    head_pid = int(runner.run(head_addr, head_cmd).split()[-1])

    # the head writes its tcp:// address + authkey to the record in its own
    # session directory (named by its pid): THIS head's, whatever other
    # head has written the box-wide last_session.json meanwhile
    session = None
    deadline = time.time() + float(config.get("start_timeout_s", 120))
    while time.time() < deadline:
        try:
            out = runner.run(
                head_addr, f"cat /tmp/ray_tpu/session_{head_pid}_*/session.json",
                timeout=30)
            session = json.loads(out)
            break
        except Exception:
            time.sleep(1.0)
    if session is None:
        raise RuntimeError(
            f"head on {head_addr} did not write a session record; see "
            f"/tmp/ray_tpu_{name}_head.log there")
    address = session["address"]
    if remote_head and (address.startswith("tcp://127.")
                        or address.startswith("tcp://0.0.0.0")):
        # the record names a non-routable bind; workers dial the head host
        address = f"tcp://{head_addr}:{address.rsplit(':', 1)[1]}"

    joined = []
    for i, node in enumerate(config.get("worker_nodes") or []):
        addr = node["address"]
        join_cmd = (
            f"nohup {shlex.quote(python)} -m ray_tpu._private.node_agent "
            f"--address {shlex.quote(address[len('tcp://'):])} "
            f"--authkey {session['authkey']} {_node_flags(node)} "
            f"--node-id node-{name}-{i} "
            f"> /tmp/ray_tpu_{name}_worker{i}.log 2>&1 & echo joined"
        )
        runner.run(addr, join_cmd)
        joined.append({"address": addr, "node_id": f"node-{name}-{i}"})
    return {"address": address, "authkey": session["authkey"],
            "head_pid": head_pid, "workers": joined,
            "head_address": head_addr}


def down(config: Dict[str, Any], runner: Optional[CommandRunner] = None) -> None:
    """Stop agents and the head on every host in the YAML.  Patterns use
    the ``[.]`` char-class trick so the kill command's own shell never
    matches them; the head is killed by the pid in its session record."""
    runner = runner or _runner_for(config)
    name = config.get("cluster_name", "cluster")
    # scope the kill to THIS cluster's agents (up() names them
    # node-<cluster>-<i>) so co-hosted clusters survive a neighbor's down
    kill_agents = (
        f"pkill -f 'ray_tpu[.]_private[.]node_agent.*node-{name}-' || true"
    )
    # kill by the session-record pid, but ONLY if that pid's cmdline is
    # really a launched head — a stale record can name an unrelated (or
    # the calling!) process, and `ray down` must never kill those
    kill_head = (
        "kill $(python3 - <<'PYEOF'\n"
        "import json\n"
        "try:\n"
        "    pid = json.load(open('/tmp/ray_tpu/last_session.json'))['pid']\n"
        "    cmd = open(f'/proc/{pid}/cmdline', 'rb').read().decode()\n"
        "    cmd = cmd.replace(chr(0), ' ')\n"
        "    if 'ray_tpu' in cmd and '--head' in cmd:\n"
        "        print(pid)\n"
        "except Exception:\n"
        "    pass\n"
        "PYEOF\n"
        ") 2>/dev/null; pkill -f 'ray_tpu start [-][-]head' || true"
    )
    for node in config.get("worker_nodes") or []:
        try:
            runner.run(node["address"], kill_agents, timeout=60)
        except Exception:
            pass
    head_addr = config["head_node"].get("address", "127.0.0.1")
    try:
        runner.run(head_addr, f"{kill_agents}; {kill_head}", timeout=60)
    except Exception:
        pass
