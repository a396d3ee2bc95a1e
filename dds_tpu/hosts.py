"""Replica processes: every replica of one launch in an OS process of its own.

`transport.replica_processes` (with `transport.kind = "tcp"`) is the
reference's placement, `dds-system.conf:113-128` (an `akka.ssl.tcp://host:port`
URI per replica endpoint) with `Main.scala:90-99` (a process spawns only its
`local` replicas), on ONE machine: `run.launch` keeps the REST proxy, the
crypto backend and the accelerator, and starts a child for every replica
endpoint, spares included. A child is the program's normal node entry,

    python -m dds_tpu.run --config <dir>/<replica>.json --ops 0 --serve --die-with-parent

given the launcher's own configuration with `replicas.local = [<replica>]`, a
free loopback port of its own, the same `replicas.addresses` as every other
process, and the supervisor placed with the first endpoint's process. It is
held to the CPU (`JAX_PLATFORMS=cpu`, `proxy.crypto_backend = "cpu"`: the
accelerator is one process's), runs no workload, and writes to
`<dir>/<replica>.log` and never to the launcher's stdout. Hosts proper are
configured with `replicas.addresses` / `replicas.local` as before; nothing
here stands in for a network.

A child dies with its launcher however that dies: its stdin is a pipe only
the launcher holds open, and `--die-with-parent` exits at its end of file.
`stop()` ends and reaps every child.

What the children count about the PROTOCOL (`obs.metrics.PROTOCOL_FAMILIES`)
the launcher's registry reads as their sum, at most `PULL_EVERY` seconds
behind and exactly after `stop()`: every `PULL_EVERY` seconds each child's
node-host agent is asked (`CountersRequest`) and answers its cumulative
counts (`Counters`), and the registry takes in what grew. What describes
one process (its event loop's ledger, its collector's pauses, its frames,
its spans) stays in that process. On the same tick
`dds_process_cpu_seconds_total{role}` grows by the CPU seconds the
launcher (`proxy`) and all children together (`replica`, one sum: nothing
downstream sums over a label) have used, the children's from
`/proc/<pid>/stat`.
"""

from __future__ import annotations

import asyncio
import dataclasses
import json
import logging
import os
import shutil
import socket
import subprocess
import sys
import tempfile
import time

from dds_tpu.core import messages as M
from dds_tpu.obs.metrics import metrics
from dds_tpu.utils.tasks import supervised_task
from dds_tpu.utils.trace import tracer

log = logging.getLogger("dds.hosts")

__all__ = ["ReplicaProcesses", "NODEHOST", "PULL_EVERY"]

NODEHOST = "nodehost"       # the per-process agent `run.launch` registers
PULL_EVERY = 0.25           # seconds between two rounds of `pull`
READY_TIMEOUT = 120.0       # a child imports the program and launches
_TICKS = os.sysconf("SC_CLK_TCK")
_CPU_HELP = ("CPU seconds of the launching process (proxy) and of all its "
             "replica processes together (replica)")


def _free_ports(host: str, n: int) -> list[int]:
    """`n` loopback ports nobody holds: bound together, so that they
    differ, and let go for the children to bind."""
    socks = []
    try:
        for _ in range(n):
            s = socket.socket()
            s.bind((host, 0))
            socks.append(s)
        return [s.getsockname()[1] for s in socks]
    finally:
        for s in socks:
            s.close()


@dataclasses.dataclass
class _Child:
    name: str
    hostport: str
    proc: subprocess.Popen
    log_path: str
    cpu_s: float = 0.0      # its last reading of /proc/<pid>/stat

    @property
    def alive(self) -> bool:
        return self.proc.poll() is None


class ReplicaProcesses:
    """The children of one launch: started by `spawn`, awaited by `ready`,
    read by `pull`, ended by `stop`."""

    def __init__(self, cfg, net, ssl_client=None):
        self.cfg, self.net, self._ssl_client = cfg, net, ssl_client
        self.children: list[_Child] = []
        self.dir = ""
        self._src = net.local_addr(NODEHOST)
        self._answers: dict[str, asyncio.Future] = {}
        self._round = asyncio.Lock()   # one round of `pull` at a time
        self._cpu_self = 0.0
        self._task: asyncio.Task | None = None
        self._t_spawn = 0.0

    # ------------------------------------------------------------- start

    def spawn(self) -> None:
        """Choose the ports, write every process's configuration and
        start the children. Sets `replicas.addresses` and
        `replicas.supervisor_address` on the launcher's own configuration:
        the address book is the same in every process."""
        cfg = self.cfg
        names = list(cfg.replicas.endpoints)
        host = cfg.transport.host
        self._t_spawn = time.perf_counter()
        ports = _free_ports(host, len(names))
        book = {n: f"{host}:{p}" for n, p in zip(names, ports)}
        cfg.replicas.addresses = dict(book)
        cfg.replicas.supervisor_address = book[names[0]]
        self.dir = tempfile.mkdtemp(prefix="dds-replica-processes-")
        # children find the package where this process found it
        pkg_parent = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        t = os.times()
        self._cpu_self = t.user + t.system
        for name, port in zip(names, ports):
            child = dataclasses.asdict(cfg)
            child["transport"].update(port=port, advertise="",
                                      replica_processes=False)
            child["replicas"]["local"] = [name]
            child["proxy"].update(port=0, crypto_backend="cpu")
            child["client"]["nr_of_operations"] = 0
            child["attacks"]["at_launch"] = False   # the launcher fires it
            conf_path = os.path.join(self.dir, f"{name}.json")
            with open(conf_path, "w") as f:
                json.dump(child, f)
            log_path = os.path.join(self.dir, f"{name}.log")
            with open(log_path, "w") as out:
                proc = subprocess.Popen(
                    [sys.executable, "-m", "dds_tpu.run", "--config",
                     conf_path, "--ops", "0", "--serve", "--die-with-parent"],
                    cwd=pkg_parent, env=env, stdin=subprocess.PIPE,
                    stdout=out, stderr=subprocess.STDOUT,
                )
            self.children.append(_Child(name, book[name], proc, log_path))

    async def ready(self) -> None:
        """Return once every child listens and its node-host agent has
        answered; start the rounds of `pull`."""
        deadline = time.perf_counter() + READY_TIMEOUT
        for child in self.children:
            host, port = child.hostport.rsplit(":", 1)
            while True:
                self._check_alive(child, deadline)
                try:
                    _, w = await asyncio.open_connection(
                        host, int(port), ssl=self._ssl_client)
                    w.close()
                    break
                except OSError:
                    await asyncio.sleep(0.05)
        while True:
            answered = await self.pull(timeout=0.5)
            for child in self.children:
                self._check_alive(child, deadline)
            if answered:
                break
        took = time.perf_counter() - self._t_spawn
        tracer.record("launch.children", took * 1e3,
                      children=len(self.children))
        log.info(
            "replica processes: %d children up in %.2f s (%s); their output "
            "is in %s/<replica>.log",
            len(self.children), took,
            ", ".join(f"{c.name} pid {c.proc.pid} at {c.hostport}"
                      for c in self.children), self.dir)
        self._task = supervised_task(self._pull_loop(), name="hosts.pull")

    def _check_alive(self, child: _Child, deadline: float) -> None:
        if child.alive and time.perf_counter() < deadline:
            return
        why = (f"exited with {child.proc.returncode}" if not child.alive
               else f"was not up within {READY_TIMEOUT:.0f} s")
        raise RuntimeError(f"replica process {child.name} {why}; its "
                           f"output ended:\n{self._tail(child)}")

    @staticmethod
    def _tail(child: _Child) -> str:
        try:
            with open(child.log_path, errors="replace") as f:
                return f.read()[-2000:]
        except OSError:
            return ""

    # ---------------------------------------------------------- counters

    async def _pull_loop(self) -> None:
        while True:
            await asyncio.sleep(PULL_EVERY)
            await self.pull()

    async def pull(self, timeout: float = 1.0) -> bool:
        """One round: ask every living child for its protocol counters,
        wait for the answers, take them into the registry, and read
        everybody's CPU seconds. True when every living child answered."""
        loop = asyncio.get_running_loop()
        async with self._round:
            self._answers = {c.hostport: loop.create_future()
                             for c in self.children if c.alive}
            for hostport in self._answers:
                self.net.send(self._src, f"{hostport}/{NODEHOST}",
                              M.CountersRequest())
            late = ()
            if self._answers:
                _, late = await asyncio.wait(self._answers.values(),
                                             timeout=timeout)
            self._answers = {}
            self._read_cpu()
        return not late

    def on_counters(self, sender: str, msg: M.Counters) -> None:
        """A child's node-host agent answered (`run.launch` routes it
        here). Late answers count too: the values are cumulative."""
        hostport = sender.rsplit("/", 1)[0]
        if not any(c.hostport == hostport for c in self.children):
            return
        metrics.absorb(hostport, msg.samples)
        fut = self._answers.get(hostport)
        if fut is not None and not fut.done():
            fut.set_result(None)

    def _read_cpu(self) -> None:
        t = os.times()
        mine = t.user + t.system
        metrics.inc("dds_process_cpu_seconds_total", mine - self._cpu_self,
                    role="proxy", help=_CPU_HELP)
        self._cpu_self = mine
        grew = 0.0
        for child in self.children:
            try:
                with open(f"/proc/{child.proc.pid}/stat", "rb") as f:
                    # "pid (comm) state ppid ...": utime and stime are the
                    # 14th and 15th fields, comm may hold anything
                    fields = f.read().rsplit(b") ", 1)[1].split()
                now = (int(fields[11]) + int(fields[12])) / _TICKS
            except (OSError, IndexError, ValueError):
                continue   # gone: what it used stays counted
            grew += max(0.0, now - child.cpu_s)
            child.cpu_s = max(child.cpu_s, now)
        metrics.inc("dds_process_cpu_seconds_total", grew, role="replica",
                    help=_CPU_HELP)

    # --------------------------------------------------------------- stop

    async def stop(self) -> None:
        """A last round of `pull`, then end and reap every child."""
        if self._task is not None:
            self._task.cancel()
            try:
                await self._task
            except asyncio.CancelledError:
                pass
            self._task = None
            try:
                await self.pull()
            except Exception:  # noqa: BLE001 — the children go whatever it says
                log.exception("last pull of the replica processes failed")
        for child in self.children:
            if child.alive:
                child.proc.terminate()
            else:
                # it went by itself: what it last said goes with its file
                log.warning("replica process %s had gone before stop() "
                            "(exit %s); its output ended:\n%s", child.name,
                            child.proc.returncode, self._tail(child))
        deadline = time.perf_counter() + 5.0
        while (any(c.alive for c in self.children)
               and time.perf_counter() < deadline):
            await asyncio.sleep(0.01)
        for child in self.children:
            if child.alive:
                child.proc.kill()
            child.proc.wait()
            child.proc.stdin.close()
        self.children = []
        if self.dir:
            shutil.rmtree(self.dir, ignore_errors=True)
            self.dir = ""
