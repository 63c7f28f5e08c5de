"""hvdtpurun CLI + local/ssh launch drivers
(reference: horovod/run/run.py:295-483 + bin/horovodrun).

Unlike the reference, there is no mpirun at the bottom: the task
servers spawn the training processes directly and the controller
coordinates, so the whole stack is ours.
"""

from __future__ import annotations

import argparse
import json
import os
import secrets as _secrets
import shlex
import socket
import subprocess
import sys
import threading
import time
from typing import Dict, List, Optional, Tuple

from horovod_tpu.common import config as hconfig
from horovod_tpu.run.chips import ChipShortage, chip_env
from horovod_tpu.run.services import DriverService, local_addresses


class HostCheckCache:
    """Cached host-reachability results, one hour by default
    (reference: run/util/cache.py — the 60-minute ``~/.horovod`` result
    cache keyed by check parameters; ``--disable-cache`` bypasses it).
    Only successes are cached: a host that was down may come back, so
    failures are always re-probed."""

    def __init__(self, path: Optional[str] = None, ttl_s: float = 3600.0):
        base = hconfig.env_str("HOROVOD_TPU_CACHE_DIR", "~/.horovod_tpu")
        self._path = path or os.path.join(
            os.path.expanduser(base), "hostcheck.json")
        self._ttl = ttl_s
        self._data: Dict[str, dict] = {}
        try:
            with open(self._path) as f:
                self._data = json.load(f)
        except (OSError, ValueError):
            pass

    def get(self, key: str) -> Optional[bool]:
        ent = self._data.get(key)
        if ent and ent.get("ok") and time.time() - ent["t"] < self._ttl:
            return True
        return None

    def put_all(self, results: Dict[str, bool]) -> None:
        """Record a batch of results and persist once. Call from ONE
        thread after the probe threads have joined — the store is not
        synchronized."""
        for key, ok in results.items():
            if ok:
                self._data[key] = {"ok": True, "t": time.time()}
            else:
                self._data.pop(key, None)
        try:
            os.makedirs(os.path.dirname(self._path), exist_ok=True)
            tmp = f"{self._path}.{os.getpid()}.tmp"
            with open(tmp, "w") as f:
                json.dump(self._data, f)
            os.replace(tmp, self._path)
        except OSError:
            pass


def _local_hosts() -> set:
    return {"localhost", "127.0.0.1", socket.gethostname()}


def _ssh_base(ssh_port: Optional[int],
              connect_timeout: Optional[float] = None) -> List[str]:
    cmd = ["ssh", "-o", "StrictHostKeyChecking=no"]
    if connect_timeout is not None:
        cmd += ["-o", f"ConnectTimeout={max(1, int(connect_timeout))}"]
    if ssh_port:
        cmd += ["-p", str(ssh_port)]
    return cmd


def _default_ssh_check(host: str, ssh_port: Optional[int],
                       timeout: float) -> bool:
    cmd = _ssh_base(ssh_port, connect_timeout=timeout) + [host, "true"]
    try:
        return subprocess.run(
            cmd, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
            timeout=timeout + 5).returncode == 0
    except (subprocess.TimeoutExpired, OSError):
        return False


def check_hosts_reachable(hosts: List[Tuple[str, int]],
                          ssh_port: Optional[int] = None,
                          timeout: float = 10.0,
                          check_fn=None,
                          cache: Optional[HostCheckCache] = None) -> None:
    """Threaded ssh reachability pre-check before anything is spawned
    (reference: run/run.py:44-100 — parallel `ssh true` probes): a dead
    host fails fast with a per-host message instead of surfacing later
    as a generic registration timeout. ``check_fn(host) -> bool`` is
    injectable for tests; successes are cached (see HostCheckCache).
    Cache reads/writes happen on this thread only — probe threads just
    run the checks."""
    to_check = [h for h, _ in hosts if h not in _local_hosts()]
    if not to_check:
        return
    check = check_fn or (
        lambda h: _default_ssh_check(h, ssh_port, timeout))
    results: Dict[str, bool] = {}
    need_probe = []
    for h in to_check:
        if cache is not None and cache.get(f"{h}:{ssh_port or 22}"):
            results[h] = True
        else:
            need_probe.append(h)

    def _probe(h: str) -> None:
        results[h] = bool(check(h))

    threads = [threading.Thread(target=_probe, args=(h,), daemon=True)
               for h in need_probe]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout + 10)
    if cache is not None and need_probe:
        cache.put_all({f"{h}:{ssh_port or 22}": results.get(h, False)
                       for h in need_probe})
    dead = [h for h in to_check if not results.get(h)]
    if dead:
        raise RuntimeError(
            f"host(s) unreachable over ssh: {', '.join(dead)} — verify "
            f"connectivity (`ssh {dead[0]} true`), the -H host list, "
            f"and --ssh-port, then retry.")


def parse_hosts(spec: str) -> List[Tuple[str, int]]:
    """'a:4,b:4' -> [('a', 4), ('b', 4)]
    (reference: run/run.py -H format)."""
    out = []
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        if ":" in part:
            host, slots = part.rsplit(":", 1)
            out.append((host, int(slots)))
        else:
            out.append((part, 1))
    return out


def _free_port() -> int:
    s = socket.socket()
    s.bind(("", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def abort_grace_seconds() -> float:
    """Seconds the launcher waits, after a rank dies, for survivors to
    fail themselves through the coordinator-mediated abort protocol
    (heartbeats + ABORT fan-out in common/controller.py) before the
    mpirun-style hard kill. The grace turns "launcher murdered me" into
    a clean Python-level WorldAbortedError in every surviving rank's
    training script; the kill stays as the backstop for survivors too
    wedged to run the protocol."""
    return hconfig.env_float("HOROVOD_TPU_ABORT_GRACE", 5.0)


def reap_with_grace(procs) -> int:
    """Wait for every child; on the first nonzero exit, give the
    survivors ``abort_grace_seconds()`` to fail themselves through the
    in-band ABORT protocol, then SIGTERM the stragglers (mpirun-style
    kill-on-first-exit, softened). Polls only these children — a bare
    ``os.wait()`` would reap unrelated subprocesses of an embedding
    process. Returns the FIRST nonzero returncode, preserving signal
    deaths (negative values) — never folds them back to success."""
    exit_code = 0
    pending = list(procs)
    grace_deadline = None
    killed = False
    while pending:
        for p in list(pending):
            rc = p.poll()
            if rc is None:
                continue
            pending.remove(p)
            if rc != 0:
                exit_code = exit_code or rc
                if grace_deadline is None:
                    grace_deadline = (time.monotonic()
                                      + abort_grace_seconds())
        if pending and not killed and grace_deadline is not None \
                and time.monotonic() >= grace_deadline:
            killed = True
            for q in pending:
                try:
                    q.terminate()
                except OSError:
                    pass
        if pending:
            time.sleep(0.05)
    return exit_code


def run_local(np_: int, command: List[str],
              env: Optional[Dict[str, str]] = None,
              start_timeout: float = 30.0) -> int:
    """Spawn np_ ranks on this host (the ``-H`` -less fast path; the
    reference always shells out to mpirun even locally — we don't
    need to)."""
    port = _free_port()
    procs = []
    for rank in range(np_):
        penv = dict(os.environ)
        if env:
            penv.update(env)
        penv.update(chip_env(rank, np_, penv))
        penv["HOROVOD_RANK"] = str(rank)
        penv["HOROVOD_SIZE"] = str(np_)
        penv["HOROVOD_CONTROLLER_ADDR"] = "127.0.0.1"
        penv["HOROVOD_CONTROLLER_PORT"] = str(port)
        penv.setdefault("HOROVOD_START_TIMEOUT", str(start_timeout))
        procs.append(subprocess.Popen(command, env=penv))

    exit_code = 0
    try:
        # One rank failing still tears the world down like mpirun
        # does, but only after the abort-propagation grace window: the
        # in-band ABORT protocol usually fails the survivors cleanly
        # first, so they exit with a structured error, not a SIGTERM.
        exit_code = reap_with_grace(procs)
    except KeyboardInterrupt:
        for p in procs:
            p.terminate()
        exit_code = 130
    finally:
        deadline = time.monotonic() + 10.0
        for p in procs:
            try:
                p.wait(timeout=max(0.1, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                p.kill()
    return exit_code


class HostBlacklist:
    """Per-slot failure ledger with exponential backoff — the
    launcher-side half of elastic mode (upstream analog: Elastic
    Horovod's host blacklist). A slot whose worker died waits
    ``base * 2^(failures-1)`` seconds (capped) before its respawn
    rejoins at the next rendezvous barrier; a slot that keeps dying
    past ``retries`` is blacklisted for good."""

    def __init__(self, base_s: Optional[float] = None,
                 cap_s: float = 60.0, retries: Optional[int] = None):
        self.base_s = base_s if base_s is not None else \
            hconfig.env_float("HOROVOD_TPU_ELASTIC_BACKOFF", 1.0)
        self.cap_s = cap_s
        self.retries = retries if retries is not None else \
            hconfig.env_int("HOROVOD_TPU_ELASTIC_RETRIES", 3)
        self._failures: Dict[int, int] = {}
        self._until: Dict[int, float] = {}

    def record_failure(self, slot: int, now: Optional[float] = None) -> None:
        now = time.monotonic() if now is None else now
        n = self._failures.get(slot, 0) + 1
        self._failures[slot] = n
        self._until[slot] = now + min(
            self.cap_s, self.base_s * (2.0 ** (n - 1)))

    def permanently_dead(self, slot: int) -> bool:
        return self._failures.get(slot, 0) > self.retries

    def ready_to_retry(self, slot: int,
                       now: Optional[float] = None) -> bool:
        if self.permanently_dead(slot):
            return False
        now = time.monotonic() if now is None else now
        return now >= self._until.get(slot, 0.0)

    def backlog(self) -> Dict[int, int]:
        """slot -> failure count, for logs and the launcher summary."""
        return dict(self._failures)


def run_local_elastic(np_: int, command: List[str],
                      env: Optional[Dict[str, str]] = None,
                      start_timeout: float = 30.0,
                      min_np: int = 1,
                      max_np: Optional[int] = None,
                      spawn_fn=None,
                      blacklist: Optional[HostBlacklist] = None,
                      poll_s: float = 0.1,
                      restarts: Optional[int] = None) -> int:
    """Elastic local launch (``hvdtpurun --elastic``): spawn ``np_``
    ranks, then SUPERVISE instead of killing the world on the first
    death. A dead worker's slot goes on the blacklist with exponential
    backoff; once its backoff expires it is respawned as a JOINER
    (HOROVOD_ELASTIC_JOIN=1) that rejoins the running world at the
    next rendezvous barrier. The in-process elastic machinery
    (common/elastic.py) keeps the surviving ranks training throughout;
    this loop only manages processes. Every slot's elastic listener
    port is launcher-reserved so a respawn can always dial SOME live
    member (any member redirects a joiner to the current coordinator).

    ``spawn_fn(slot, env, joiner) -> Popen-like`` is injectable for
    tests. Returns 0 when every live worker exits cleanly; the first
    nonzero exit code when the world is lost.

    ``restarts`` (env HOROVOD_TPU_ELASTIC_RESTARTS, default 0): when
    the whole world is lost — below the floor with nothing left to
    respawn — restart up to that many FRESH worlds of ``np_`` ranks
    instead of giving up. With async checkpoints armed
    (HOROVOD_SELFOP_CKPT_DIR, common/selfop.py) each restart resumes
    from state seconds old; fault specs are stripped from restarted
    worlds (the injected failure already did its job)."""
    max_np = max_np or np_
    blacklist = blacklist or HostBlacklist()
    restarts = restarts if restarts is not None else \
        hconfig.env_int("HOROVOD_TPU_ELASTIC_RESTARTS", 0)
    port = _free_port()
    elastic_ports = [_free_port() for _ in range(max_np)]
    restarted_world = False

    def _spawn(slot: int, joiner: bool):
        penv = dict(os.environ)
        if env:
            penv.update(env)
        penv.update(chip_env(slot, max_np, penv))
        penv["HOROVOD_ELASTIC"] = "1"
        penv["HOROVOD_ELASTIC_MIN_WORLD"] = str(min_np)
        penv["HOROVOD_TPU_ELASTIC_PORT"] = str(elastic_ports[slot])
        penv.setdefault("HOROVOD_START_TIMEOUT", str(start_timeout))
        if restarted_world:
            penv.pop("HOROVOD_FAULT_SPEC", None)
        if joiner:
            # Point the joiner at any LIVE member's elastic listener;
            # whoever answers redirects it to the current coordinator.
            alive = [s for s in procs if procs[s].poll() is None
                     and s != slot]
            anchor = alive[0] if alive else 0
            penv["HOROVOD_ELASTIC_JOIN"] = "1"
            penv["HOROVOD_ELASTIC_JOIN_ADDR"] = "127.0.0.1"
            penv["HOROVOD_ELASTIC_JOIN_PORT"] = \
                str(elastic_ports[anchor])
            penv.pop("HOROVOD_RANK", None)
            penv.pop("HOROVOD_SIZE", None)
            # An injected fault already did its job killing the first
            # incarnation; the respawn must not re-arm it.
            penv.pop("HOROVOD_FAULT_SPEC", None)
        else:
            penv["HOROVOD_RANK"] = str(slot)
            penv["HOROVOD_SIZE"] = str(np_)
        penv["HOROVOD_CONTROLLER_ADDR"] = "127.0.0.1"
        penv["HOROVOD_CONTROLLER_PORT"] = str(port)
        if spawn_fn is not None:
            return spawn_fn(slot, penv, joiner)
        return subprocess.Popen(command, env=penv)

    procs: Dict[int, object] = {}
    while True:
        for slot in range(np_):
            procs[slot] = _spawn(slot, joiner=False)
        pending_respawn: set = set()
        exit_code = 0
        clean_exits = 0
        interrupted = False
        try:
            while True:
                for slot, p in list(procs.items()):
                    rc = p.poll()
                    if rc is None:
                        continue
                    del procs[slot]
                    if rc == 0:
                        clean_exits += 1
                        continue  # finished training: never respawned
                    exit_code = exit_code or rc
                    blacklist.record_failure(slot)
                    if blacklist.permanently_dead(slot):
                        print(f"hvdtpurun: slot {slot} failed "
                              f"{blacklist.backlog()[slot]} times — "
                              f"blacklisted for good", file=sys.stderr)
                    else:
                        pending_respawn.add(slot)
                for slot in sorted(pending_respawn):
                    if len(procs) >= max_np or not procs:
                        break
                    if blacklist.ready_to_retry(slot):
                        pending_respawn.discard(slot)
                        procs[slot] = _spawn(slot, joiner=True)
                if not procs:
                    break
                if len(procs) < min_np and not pending_respawn \
                        and clean_exits == 0:
                    # Below the floor with nothing left to respawn and
                    # nobody finishing normally: the in-process
                    # min-world check aborts the survivors; we just
                    # stop supervising. (With clean exits the job is
                    # simply draining — lockstep training finishes
                    # everywhere at once, so keep reaping until empty.)
                    break
                time.sleep(poll_s)
        except KeyboardInterrupt:
            exit_code = 130
            interrupted = True
        finally:
            deadline = time.monotonic() + abort_grace_seconds() + 10.0
            for p in procs.values():
                try:
                    p.terminate()
                except OSError:
                    pass
            for p in procs.values():
                try:
                    p.wait(timeout=max(0.1,
                                       deadline - time.monotonic()))
                except subprocess.TimeoutExpired:
                    p.kill()
        # A world that ended with every (surviving) worker clean is a
        # success even if some workers died and were replaced on the
        # way.
        if clean_exits > 0 and exit_code != 0 and not procs \
                and clean_exits >= min_np:
            return 0
        if exit_code == 0 or interrupted or restarts <= 0:
            return exit_code
        # World lost, restart budget left: start a FRESH world of np_
        # ranks. Async checkpoints (common/selfop.py) make this resume
        # from state seconds old rather than step 0; a fresh blacklist
        # gives every slot a clean ledger in the new world.
        restarts -= 1
        restarted_world = True
        procs.clear()
        blacklist = HostBlacklist(base_s=blacklist.base_s,
                                  cap_s=blacklist.cap_s,
                                  retries=blacklist.retries)
        print(f"hvdtpurun: world lost (exit {exit_code}) — "
              f"restarting a fresh world ({restarts} restart(s) "
              f"left)", file=sys.stderr)


def _ssh_spawn(host: str, ssh_port: Optional[int], remote_cmd: str,
               env_to_forward: Dict[str, str]) -> subprocess.Popen:
    """ssh-launch a task server on ``host``
    (reference: run/run.py:103-190 _launch_task_servers)."""
    exports = " ".join(
        f"{k}={shlex.quote(v)}" for k, v in env_to_forward.items())
    cmd = _ssh_base(ssh_port) + [host, f"{exports} {remote_cmd}"]
    return subprocess.Popen(cmd)


def run_multihost(hosts: List[Tuple[str, int]], command: List[str],
                  ssh_port: Optional[int] = None,
                  env: Optional[Dict[str, str]] = None,
                  start_timeout: float = 60.0,
                  spawn_fn=None, host_check_fn=None,
                  disable_cache: bool = False) -> int:
    """Driver flow: ssh reachability pre-check → start DriverService →
    launch task servers (ssh by default; ``spawn_fn(host_index,
    driver_addr, driver_port, env)`` is injectable for tests) →
    registration → ring probe → rank assignment → launch → collect
    exits (reference: run/run.py:193-264 _driver_fn; pre-check
    run/run.py:44-100)."""
    # Injected check_fns (tests) must never write fabricated results
    # into the real ssh-check cache under real-looking keys.
    use_cache = not disable_cache and host_check_fn is None
    check_hosts_reachable(
        hosts, ssh_port=ssh_port, check_fn=host_check_fn,
        cache=HostCheckCache() if use_cache else None)
    secret = hconfig.env_str("HOROVOD_SECRET_KEY") or \
        _secrets.token_hex(16)
    driver = DriverService(len(hosts), secret=secret.encode())
    driver_addr = local_addresses()[0]

    forward_env = {"HOROVOD_SECRET_KEY": secret}
    if env:
        forward_env.update(env)

    spawned = []
    try:
        for i, (host, _slots) in enumerate(hosts):
            if spawn_fn is not None:
                spawned.append(spawn_fn(i, driver_addr, driver.port,
                                        forward_env))
            else:
                remote = (f"{shlex.quote(sys.executable)} -m "
                          f"horovod_tpu.run.services {i} {driver_addr} "
                          f"{driver.port}")
                spawned.append(_ssh_spawn(host, ssh_port, remote,
                                          forward_env))

        driver.wait_for_registration(timeout=start_timeout)
        driver.ring_probe()
        slots = [s for _, s in hosts]
        assignments = driver.assign_ranks(slots)
        controller = driver.controller_endpoint()
        driver.launch(assignments, command, forward_env, controller)
        codes = driver.wait_for_exit()
        # First nonzero wins: max() would fold a signal death
        # (negative returncode) back to 0 when another host is clean.
        return next((c for c in codes if c != 0), 0)
    finally:
        driver.shutdown()
        for p in spawned:
            if hasattr(p, "poll") and p.poll() is None:
                try:
                    p.terminate()
                except OSError:
                    pass


def main(argv: Optional[List[str]] = None) -> None:
    parser = argparse.ArgumentParser(
        prog="hvdtpurun",
        description="Launch a horovod_tpu training job "
                    "(reference: horovodrun).")
    parser.add_argument("-np", "--num-proc", type=int, required=True,
                        help="total number of training processes")
    parser.add_argument("--elastic", action="store_true",
                        help="supervise instead of kill-on-first-exit: "
                             "dead workers are blacklisted with "
                             "backoff and respawned to rejoin the "
                             "running world (HOROVOD_ELASTIC=1 on "
                             "every rank; docs/fault_tolerance.md)")
    parser.add_argument("--min-np", type=int, default=None,
                        help="elastic world floor: abort for real "
                             "below this many members (env "
                             "HOROVOD_ELASTIC_MIN_WORLD; default 1)")
    parser.add_argument("--max-np", type=int, default=None,
                        help="elastic world ceiling for rejoins "
                             "(default: -np)")
    parser.add_argument("--restarts", type=int, default=None,
                        help="elastic only: restart up to this many "
                             "fresh worlds after a total world loss "
                             "(env HOROVOD_TPU_ELASTIC_RESTARTS; "
                             "default 0). Pair with "
                             "HOROVOD_SELFOP_CKPT_DIR so restarts "
                             "resume from the async checkpoints")
    parser.add_argument("-H", "--hosts", default=None,
                        help="host1:slots,host2:slots (default: local)")
    parser.add_argument("-p", "--ssh-port", type=int, default=None)
    parser.add_argument("--start-timeout", type=float, default=None,
                        help="seconds to wait for ranks/hosts to start "
                             "(env HOROVOD_START_TIMEOUT)")
    parser.add_argument("--disable-cache", action="store_true",
                        help="re-probe ssh reachability of every host "
                             "even if a recent check succeeded "
                             "(reference: horovodrun --disable-cache)")
    parser.add_argument("--metrics", action="store_true",
                        help="arm the metrics plane on every rank "
                             "(env HOROVOD_TPU_METRICS; docs/metrics.md)")
    parser.add_argument("--metrics-port", type=int, default=None,
                        help="rank-0 Prometheus /metrics port (implies "
                             "--metrics; 0 = ephemeral; env "
                             "HOROVOD_TPU_METRICS_PORT)")
    parser.add_argument("--metrics-interval", type=float, default=None,
                        help="seconds between world metric folds (env "
                             "HOROVOD_TPU_METRICS_INTERVAL)")
    parser.add_argument("--metrics-log", default=None,
                        help="rank-0 JSONL snapshot file (implies "
                             "--metrics; env HOROVOD_TPU_METRICS_LOG)")
    parser.add_argument("--trace", default=None, metavar="PATH",
                        help="arm the world trace plane on every rank "
                             "and write the merged clock-aligned "
                             "Chrome trace to PATH on rank 0 (env "
                             "HOROVOD_TPU_TRACE; docs/tracing.md)")
    parser.add_argument("--trace-interval", type=float, default=None,
                        help="seconds between trace-span shipments "
                             "up the control tree (env "
                             "HOROVOD_TPU_TRACE_INTERVAL)")
    parser.add_argument("--service", action="store_true",
                        help="run the fleet as a long-lived collective "
                             "SERVICE (env HOROVOD_TPU_SERVICE; "
                             "docs/multitenancy.md): rank 0 opens the "
                             "tenant gate so jobs attach/detach and "
                             "pull parameter snapshots without the "
                             "fleet re-rendezvousing. With no "
                             "training command, runs the built-in "
                             "warm host (horovod_tpu.run.service_host)")
    parser.add_argument("--service-port", type=int, default=None,
                        help="fixed port for the rank-0 service gate "
                             "(0 = ephemeral; env "
                             "HOROVOD_TPU_SERVICE_PORT)")
    parser.add_argument("--verbose", action="store_true")
    parser.add_argument("command", nargs=argparse.REMAINDER,
                        help="training command")
    args = parser.parse_args(argv)

    command = args.command
    if command and command[0] == "--":
        command = command[1:]
    if not command:
        if args.service:
            # Warm-fleet default: an idle service host per slot that
            # inits the world and serves until terminated.
            command = [sys.executable, "-m",
                       "horovod_tpu.run.service_host"]
        else:
            parser.error("no training command given")

    if args.verbose:
        os.environ.setdefault("HOROVOD_LOG_LEVEL", "debug")
    start_timeout = args.start_timeout or \
        hconfig.env_float("HOROVOD_START_TIMEOUT", 30.0)

    # Metrics-plane knobs, plumbed to every spawned rank (workers read
    # them through Config.from_env; the flags win over inherited env).
    metrics_env: Dict[str, str] = {}
    if args.metrics or args.metrics_port is not None \
            or args.metrics_log is not None:
        metrics_env["HOROVOD_TPU_METRICS"] = "1"
    if args.metrics_port is not None:
        metrics_env["HOROVOD_TPU_METRICS_PORT"] = str(args.metrics_port)
    if args.metrics_interval is not None:
        metrics_env["HOROVOD_TPU_METRICS_INTERVAL"] = \
            str(args.metrics_interval)
    if args.metrics_log is not None:
        metrics_env["HOROVOD_TPU_METRICS_LOG"] = args.metrics_log
    # World trace plane + flight recorder knobs, same plumbing. The
    # trace path must reach EVERY rank (workers collect spans; rank 0
    # writes the merged file).
    if args.trace is not None:
        metrics_env["HOROVOD_TPU_TRACE"] = args.trace
    if args.trace_interval is not None:
        metrics_env["HOROVOD_TPU_TRACE_INTERVAL"] = \
            str(args.trace_interval)
    # Service mode: every rank learns the knob (rank 0 opens the
    # gate); the port pin keeps the attach endpoint stable.
    if args.service or args.service_port is not None:
        metrics_env["HOROVOD_TPU_SERVICE"] = "1"
    if args.service_port is not None:
        metrics_env["HOROVOD_TPU_SERVICE_PORT"] = \
            str(args.service_port)
    # Multihost task servers forward only an explicit env set; carry
    # env-configured metrics/trace/flight knobs across hosts too,
    # not just flags.
    for key in ("HOROVOD_TPU_METRICS", "HOROVOD_TPU_METRICS_PORT",
                "HOROVOD_TPU_METRICS_INTERVAL",
                "HOROVOD_TPU_METRICS_LOG", "HOROVOD_TPU_TRACE",
                "HOROVOD_TPU_TRACE_INTERVAL", "HOROVOD_TPU_FLIGHT",
                "HOROVOD_TPU_FLIGHT_EVENTS",
                "HOROVOD_TPU_FLIGHT_DIR", "HOROVOD_TPU_SERVICE",
                "HOROVOD_TPU_SERVICE_PORT", "HOROVOD_SELFOP",
                "HOROVOD_SELFOP_CKPT_DIR",
                "HOROVOD_SELFOP_CKPT_INTERVAL",
                "HOROVOD_PREEMPT_GRACE", "HOROVOD_PREEMPT_NOTICE"):
        if key in os.environ:
            metrics_env.setdefault(key, os.environ[key])

    if not args.hosts or all(
            h in _local_hosts() for h, _ in parse_hosts(args.hosts)):
        if args.hosts:
            total = sum(s for _, s in parse_hosts(args.hosts))
            if total != args.num_proc:
                parser.error(f"-np {args.num_proc} != total slots {total}")
        try:
            if args.elastic:
                sys.exit(run_local_elastic(
                    args.num_proc, command, env=metrics_env,
                    start_timeout=start_timeout,
                    min_np=args.min_np or 1,
                    max_np=args.max_np,
                    restarts=args.restarts))
            sys.exit(run_local(args.num_proc, command, env=metrics_env,
                               start_timeout=start_timeout))
        except ChipShortage as e:
            print(f"hvdtpurun: {e}", file=sys.stderr)
            sys.exit(1)

    if args.elastic:
        parser.error("--elastic currently drives the local launch "
                     "path only; run one elastic launcher per host or "
                     "drop -H (remote supervision is tracked in "
                     "ROADMAP item 1)")
    hosts = parse_hosts(args.hosts)
    total = sum(s for _, s in hosts)
    if total != args.num_proc:
        parser.error(f"-np {args.num_proc} != total slots {total}")
    try:
        sys.exit(run_multihost(hosts, command, ssh_port=args.ssh_port,
                               env=metrics_env,
                               start_timeout=start_timeout,
                               disable_cache=args.disable_cache))
    except RuntimeError as e:
        print(f"hvdtpurun: {e}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
