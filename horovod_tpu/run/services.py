"""Driver/task services for multi-host launches.

Re-architecture of the reference's launcher RPC layer
(reference: horovod/run/common/service/driver_service.py:43-152,
task_service.py, horovod/run/task_fn.py:23-52): a driver TCP service
collects task registrations (host index + routable addresses), tasks
probe their ring-neighbour's interfaces to drop NAT'ed/unroutable ones
(reference: run/task_fn.py:32-46 match_intf), the driver intersects
what remains, assigns ranks grouped by host, and commands each task to
exec the training processes. Wire format is JSON over the framed
HMAC channel (common/network.py) — no pickle on the wire, unlike the
reference's cloudpickle ``Wire``, so a forged frame can't execute code
even if the secret leaks.
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import threading
import time
from typing import Dict, List, Optional, Sequence

from horovod_tpu.common import config as hconfig
from horovod_tpu.common import lockdep
from horovod_tpu.common import logging as hlog
from horovod_tpu.common import network
from horovod_tpu.run.chips import chip_env

TAG_MSG = 7


def local_addresses() -> List[str]:
    """Routable-looking addresses of this host (loopback excluded
    unless nothing else exists)."""
    addrs: List[str] = []
    hostname = socket.gethostname()
    try:
        for info in socket.getaddrinfo(hostname, None, socket.AF_INET):
            a = info[4][0]
            if a not in addrs:
                addrs.append(a)
    except socket.gaierror:
        pass
    try:
        s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        s.connect(("10.255.255.255", 1))
        a = s.getsockname()[0]
        if a not in addrs:
            addrs.append(a)
        s.close()
    except OSError:
        pass
    non_loop = [a for a in addrs if not a.startswith("127.")]
    return non_loop or ["127.0.0.1"]


def probe(addr: str, port: int, timeout: float = 2.0) -> bool:
    """Can this process open a TCP connection to addr:port?
    (reference: run/common/util/network.py:152-246 BasicClient
    multi-interface probing)."""
    try:
        with socket.create_connection((addr, port), timeout=timeout):
            return True
    except OSError:
        return False


class _JsonChannel:
    def __init__(self, ch: network.Channel):
        self._ch = ch

    def send(self, obj) -> None:
        self._ch.send(json.dumps(obj).encode(), TAG_MSG)

    def recv(self):
        tag, payload = self._ch.recv()
        if tag != TAG_MSG:
            raise ConnectionError(f"unexpected tag {tag}")
        return json.loads(payload.decode())

    def close(self):
        self._ch.close()


class DriverService:
    """Launcher-side registry + command fan-out
    (reference: horovod/run/driver/driver_service.py +
    common/service/driver_service.py)."""

    def __init__(self, num_hosts: int, secret: bytes = b""):
        self._num_hosts = num_hosts
        self._secret = secret
        self._server = network.listen(0)
        self.port = self._server.getsockname()[1]
        self._tasks: Dict[int, _JsonChannel] = {}
        self._task_info: Dict[int, dict] = {}
        self._lock = lockdep.lock("services.DriverService._lock")

    def wait_for_registration(self, timeout: float = 60.0) -> None:
        """Accept one connection per host; each sends
        {host_index, hostname, addresses, task_port}."""
        deadline = time.monotonic() + timeout
        self._server.settimeout(1.0)
        while len(self._tasks) < self._num_hosts:
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"only {len(self._tasks)}/{self._num_hosts} task "
                    "servers registered before timeout")
            try:
                sock, _ = self._server.accept()
            except socket.timeout:
                continue
            try:
                sock.settimeout(10.0)
                ch = _JsonChannel(network.Channel(sock, self._secret))
                hello = ch.recv()
                idx = int(hello["host_index"])
                if idx < 0 or idx >= self._num_hosts or idx in self._tasks:
                    raise ConnectionError(f"bad host index {idx}")
            except (ConnectionError, socket.timeout, ValueError, KeyError,
                    TypeError, UnicodeDecodeError) as e:
                hlog.warning(f"driver rejected connection: {e}")
                try:
                    sock.close()
                except OSError:
                    pass
                continue
            sock.settimeout(None)
            self._tasks[idx] = ch
            self._task_info[idx] = hello

    def ring_probe(self) -> None:
        """Ask each task to probe its successor's addresses; keep only
        addresses the predecessor could reach (reference:
        run/task_fn.py:32-46 — NAT'ed interface filtering)."""
        n = self._num_hosts
        if n <= 1:
            return
        for i in range(n):
            nxt = self._task_info[(i + 1) % n]
            self._tasks[i].send({
                "cmd": "probe",
                "addresses": nxt["addresses"],
                "port": nxt["task_port"],
            })
        for i in range(n):
            result = self._tasks[i].recv()
            reachable = result.get("reachable", [])
            target = (i + 1) % n
            info = self._task_info[target]
            kept = [a for a in info["addresses"] if a in reachable]
            if kept:
                info["addresses"] = kept

    def assign_ranks(self, slots: Sequence[int]) -> List[dict]:
        """Contiguous ranks per host, host 0 first (reference:
        spark/__init__.py:144-154 host-hash grouping w/ rank 0 first).
        Returns one assignment dict per host."""
        assignments = []
        next_rank = 0
        for i in range(self._num_hosts):
            ranks = list(range(next_rank, next_rank + slots[i]))
            next_rank += slots[i]
            assignments.append({
                "host_index": i,
                "ranks": ranks,
                "size": sum(slots),
            })
        return assignments

    def controller_endpoint(self) -> dict:
        """Rank-0 host's reachable address + a port reserved ON that
        host (a port free on the launcher machine may be taken on the
        rank-0 host — the TaskServer holds the reservation until just
        before it spawns the training processes)."""
        self._tasks[0].send({"cmd": "alloc_port"})
        port = int(self._tasks[0].recv()["port"])
        info0 = self._task_info[0]
        addr = info0["addresses"][0]
        return {"addr": addr, "port": port}

    def launch(self, assignments: List[dict], command: List[str],
               env: Dict[str, str], controller: dict) -> None:
        for i in range(self._num_hosts):
            self._tasks[i].send({
                "cmd": "launch",
                "assignment": assignments[i],
                "command": command,
                "env": env,
                "controller": controller,
            })

    def wait_for_exit(self, timeout: Optional[float] = None) -> List[int]:
        """Collect per-host exit codes (first nonzero local process,
        signal deaths preserved as negatives)."""
        codes = []
        for i in range(self._num_hosts):
            msg = self._tasks[i].recv()
            codes.append(int(msg.get("exit_code", 1)))
        return codes

    def shutdown(self) -> None:
        for ch in self._tasks.values():
            try:
                ch.send({"cmd": "shutdown"})
            except OSError:
                pass
            try:
                ch.close()
            except OSError:
                pass  # stage-guarded: the listener below must still close
        self._server.close()


class TaskServer:
    """Per-host agent: registers with the driver, answers probes,
    spawns the local training processes, reports exit status
    (reference: horovod/run/task/task_service.py + task_fn.py)."""

    def __init__(self, host_index: int, driver_addr: str,
                 driver_port: int, secret: bytes = b""):
        self.host_index = host_index
        self._reserved: Optional[socket.socket] = None
        # listening socket other tasks probe against
        self._probe_server = network.listen(0)
        self.task_port = self._probe_server.getsockname()[1]
        self._accepting = threading.Thread(target=self._accept_probes,
                                           daemon=True)
        self._accepting.start()
        ch = network.connect(driver_addr, driver_port, secret,
                             timeout=30.0, retry_deadline=30.0)
        self._ch = _JsonChannel(ch)
        self._ch.send({
            "host_index": host_index,
            "hostname": socket.gethostname(),
            "addresses": local_addresses(),
            "task_port": self.task_port,
        })

    def _accept_probes(self) -> None:
        while True:
            try:
                sock, _ = self._probe_server.accept()
                sock.close()
            except OSError:
                return

    def serve_forever(self) -> int:
        """Process driver commands until shutdown; returns exit code."""
        exit_code = 0
        while True:
            msg = self._ch.recv()
            cmd = msg.get("cmd")
            if cmd == "probe":
                reachable = [a for a in msg["addresses"]
                             if probe(a, msg["port"])]
                self._ch.send({"reachable": reachable})
            elif cmd == "alloc_port":
                # Reserve a controller port on THIS host; held until
                # launch so nothing else can grab it meanwhile.
                self._reserved = network.listen(0)
                self._ch.send(
                    {"port": self._reserved.getsockname()[1]})
            elif cmd == "launch":
                exit_code = self._launch(msg)
                self._ch.send({"exit_code": exit_code})
            elif cmd == "shutdown":
                self._probe_server.close()
                self._ch.close()
                return exit_code
            else:
                hlog.warning(f"task {self.host_index}: unknown driver "
                             f"command {cmd!r}")

    def _launch(self, msg) -> int:
        assignment = msg["assignment"]
        controller = msg["controller"]
        procs = []
        ranks = assignment["ranks"]
        for local_rank, rank in enumerate(ranks):
            env = dict(os.environ)
            env.update(msg.get("env", {}))
            env.update(chip_env(local_rank, len(ranks), env))
            env["HOROVOD_RANK"] = str(rank)
            env["HOROVOD_SIZE"] = str(assignment["size"])
            env["HOROVOD_CONTROLLER_ADDR"] = controller["addr"]
            env["HOROVOD_CONTROLLER_PORT"] = str(controller["port"])
            pass_fds = ()
            if rank == 0 and self._reserved is not None:
                # Hand the reserved listener to rank 0 as an inherited
                # fd (socket-activation style): the endpoint published
                # to every host can never be stolen, because the socket
                # is never unbound between reservation and init.
                fd = self._reserved.fileno()
                os.set_inheritable(fd, True)
                env["HOROVOD_CONTROLLER_FD"] = str(fd)
                pass_fds = (fd,)
            procs.append(subprocess.Popen(msg["command"], env=env,
                                          close_fds=True,
                                          pass_fds=pass_fds))
        if self._reserved is not None:
            # The child owns a duplicate now; drop ours.
            self._reserved.close()
            self._reserved = None
        # Same teardown contract as run_local: a local rank dying
        # nonzero starts the abort-propagation grace window — the
        # in-band ABORT usually fails this host's survivors cleanly —
        # then the remainder is hard-killed as a backstop.
        from horovod_tpu.run.launch import reap_with_grace
        return reap_with_grace(procs)


def task_main() -> None:
    """Entry for ``python -m horovod_tpu.run.services <host_index>
    <driver_addr> <driver_port>`` — what the launcher execs over ssh
    (reference: ssh-launched ``python -m horovod.run.task_fn``,
    run/run.py:103-190)."""
    host_index = int(sys.argv[1])
    driver_addr = sys.argv[2]
    driver_port = int(sys.argv[3])
    secret = hconfig.env_str("HOROVOD_SECRET_KEY", "").encode()
    server = TaskServer(host_index, driver_addr, driver_port, secret)
    sys.exit(server.serve_forever())


if __name__ == "__main__":
    task_main()
