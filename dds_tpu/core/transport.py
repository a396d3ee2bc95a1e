"""Async message transports for the replicated core.

The reference rides Akka remoting (netty SSL TCP) for every actor-to-actor
hop (`dds-system.conf:18-58`). The TPU-native design keeps control-plane
messaging on the CPU in plain asyncio (quorum logic is control flow, not
math — SURVEY.md §5.8) with two interchangeable transports:

- `InMemoryNet`: zero-copy in-process delivery with per-link fault hooks
  (drop / delay / duplicate / corrupt) — the unit/property-test fabric the
  reference never had, and the single-process deployment fabric (the
  reference also runs its whole 9-replica quorum in one process when the
  topology says so, SURVEY.md §4).
- `TcpNet`: length-prefixed frames over asyncio TCP, optional TLS — the
  multi-host fabric.

Addresses are opaque strings ("replica-3", "host:port/replica-3"). Delivery
is fire-and-forget and unordered, like actor tell; all integrity comes from
the HMAC layer inside the messages.
"""

from __future__ import annotations

import asyncio
import hashlib
import hmac
import itertools
import json
import logging
import time
from typing import Awaitable, Callable, Optional

from dds_tpu.core import messages as M
from dds_tpu.obs import context as obs_context
from dds_tpu.obs.metrics import metrics
from dds_tpu.utils.tasks import supervised_task
from dds_tpu.utils.trace import tracer

log = logging.getLogger("dds.transport")

Handler = Callable[[str, object], Awaitable[None]]


class Transport:
    """Interface: register local endpoints, send to any endpoint."""

    def register(self, addr: str, handler: Handler) -> None:
        raise NotImplementedError

    def unregister(self, addr: str) -> None:
        raise NotImplementedError

    def send(self, src: str, dest: str, msg: object) -> None:
        raise NotImplementedError

    def has_endpoint(self, addr: str) -> bool:
        raise NotImplementedError


class InMemoryNet(Transport):
    def __init__(self):
        self._handlers: dict[str, Handler] = {}
        # test hooks: (src, dest) or dest -> async fn(msg) -> msg | None (drop)
        self.link_filters: dict[object, Callable] = {}
        self._tasks: set[asyncio.Task] = set()

    def register(self, addr: str, handler: Handler) -> None:
        self._handlers[addr] = handler

    def unregister(self, addr: str) -> None:
        self._handlers.pop(addr, None)

    def has_endpoint(self, addr: str) -> bool:
        return addr in self._handlers

    def send(self, src: str, dest: str, msg: object) -> None:
        task = supervised_task(self._deliver(src, dest, msg),
                               name=f"inmem.deliver:{dest}")
        self._tasks.add(task)
        task.add_done_callback(self._tasks.discard)

    async def _deliver(self, src: str, dest: str, msg: object) -> None:
        for key in ((src, dest), dest):
            f = self.link_filters.get(key)
            if f is not None:
                msg = await f(msg)
                if msg is None:
                    return
        handler = self._handlers.get(dest)
        if handler is None:
            log.debug("drop %s -> %s (no endpoint): %s", src, dest, type(msg).__name__)
            return
        try:
            await handler(src, msg)
        except Exception:
            log.exception("handler error at %s for %s", dest, type(msg).__name__)

    async def quiesce(self) -> None:
        """Wait until all in-flight deliveries (and their follow-ups) drain."""
        while True:
            pending = [t for t in self._tasks if not t.done()]
            if not pending:
                break
            await asyncio.gather(*pending, return_exceptions=True)
            await asyncio.sleep(0)  # let done-callbacks prune the task set


class TcpNet(Transport):
    """Multi-host transport: frames are 4-byte big-endian length + JSON.

    Each frame carries (src, dest, payload) and, when `frame_secret` is set,
    an HMAC-SHA256 over the canonical frame — the channel-authentication
    role the reference delegates to mutual-TLS Akka remoting
    (`dds-system.conf:18-58`). Without it, a keyless network attacker could
    spoof the `src` field and forge sender-keyed quorum votes (WriteAck,
    Suspect). TLS contexts can be layered on top/instead.

    One listening socket per host serves all endpoints registered on it;
    outbound connections are cached per destination process ("host:port"),
    one a pair of processes and direction. A send to a peer that is not up
    (yet, or any more) is a failed send: logged, counted by nobody here,
    the sender's timeout and the breaker above it see it. A peer that goes
    closes its end; the cached connection goes with it (`_watch`), so the
    first frame after the peer is back opens a new one.

    What the wire costs is recorded per frame, never per key: a
    `net.serialize` span on the sending side (dict-encode, both JSON
    encodes, MAC/signature) and a `net.deserialize` span on the receiving
    side (JSON decode, MAC/signature check, `from_dict`), each with meta
    `bytes`, `msg` (the message class) and `dest`; counters
    `dds_net_frames_total` and `dds_net_frame_bytes_total` by `direction`
    and `msg`, and `dds_net_frames_dropped_total` by `reason`.
    """

    def __init__(
        self,
        host: str,
        port: int,
        ssl_server=None,
        ssl_client=None,
        frame_secret: bytes | None = None,
        node_key=None,
        peer_keys: dict | None = None,
        advertise: str = "",
    ):
        self.host, self.port = host, port
        # The address peers use to reach/name this process. A process that
        # binds 0.0.0.0 (or binds an IP while peers address it by hostname)
        # must advertise the peer-visible address, or every signed inbound
        # frame fails the dest-host check below and the fabric silently
        # drops all traffic. "host" or "host:port"; empty = the bind
        # address.
        self._advertise = advertise
        self._handlers: dict[str, Handler] = {}
        self._server: Optional[asyncio.AbstractServer] = None
        self._conns: dict[str, asyncio.StreamWriter] = {}
        self._watchers: set[asyncio.Task] = set()
        self._inbound: set[asyncio.StreamWriter] = set()
        self._ssl_server, self._ssl_client = ssl_server, ssl_client
        self._frame_secret = frame_secret
        # per-node identity (utils/nodeauth): node_key is THIS process's
        # Ed25519 private key; peer_keys maps "host:port" -> public key.
        # When peer_keys is set, inbound frames are accepted only if their
        # signature verifies against the claimed src's registered key —
        # the sender-authenticity layer the sender-keyed quorum votes need
        # (a shared frame secret or cluster-wide TLS cert only proves
        # membership, not which member).
        self._node_key = node_key
        self._peer_keys = peer_keys
        # signed frames carry a strictly increasing counter (seeded with
        # wall time so process restarts keep increasing); receivers track
        # the max seen per src host:port and drop non-increasing frames —
        # without it a captured signed frame (e.g. a Kill) could be
        # replayed verbatim. Sound because each sender->receiver pair
        # rides ONE cached FIFO connection. Known limits (documented, not
        # closed): the receiver-side counter state is in-memory, so frames
        # captured before a receiver RESTART can be replayed into the
        # fresh process until the genuine sender next transmits; and a
        # sender whose clock steps far backwards across ITS restart sends
        # below peers' recorded max until the clock catches up. Pair with
        # intranet TLS (which closes on-path capture entirely) where those
        # windows matter.
        self._send_ctr = itertools.count(time.time_ns())
        self._seen_ctr: dict[str, int] = {}
        self._lock = asyncio.Lock()

    @staticmethod
    def _frame_body(src: str, dest: str, payload: dict, ctr=None) -> bytes:
        return json.dumps([src, dest, ctr, payload], sort_keys=True).encode()

    def _frame_mac(self, body: bytes) -> str:
        return hmac.new(self._frame_secret, body, hashlib.sha256).hexdigest()

    # endpoint addresses look like "host:port/name"
    @staticmethod
    def split(addr: str) -> tuple[str, int, str]:
        hostport, name = addr.split("/", 1)
        host, port = hostport.rsplit(":", 1)
        return host, int(port), name

    @property
    def advertised(self) -> str:
        """This process's peer-visible "host:port" (see `advertise`)."""
        if self._advertise:
            if ":" in self._advertise:
                return self._advertise
            return f"{self._advertise}:{self.port}"
        return f"{self.host}:{self.port}"

    def local_addr(self, name: str) -> str:
        return f"{self.advertised}/{name}"

    def register(self, addr: str, handler: Handler) -> None:
        _, _, name = self.split(addr) if "/" in addr else (None, None, addr)
        self._handlers[name] = handler

    def unregister(self, addr: str) -> None:
        _, _, name = self.split(addr) if "/" in addr else (None, None, addr)
        self._handlers.pop(name, None)

    def has_endpoint(self, addr: str) -> bool:
        name = addr.rsplit("/", 1)[-1]
        return name in self._handlers

    async def start(self) -> None:
        self._server = await asyncio.start_server(
            self._serve, self.host, self.port, ssl=self._ssl_server
        )
        if self.port == 0:  # resolve an OS-assigned port for local_addr()
            self.port = self._server.sockets[0].getsockname()[1]

    async def stop(self) -> None:
        # close every connection first, outbound and inbound: a peer in
        # another process keeps its end open for as long as it lives, and
        # wait_closed() waits for every _serve loop
        for w in [*self._conns.values(), *self._inbound]:
            w.close()
        self._conns.clear()
        for t in list(self._watchers):
            t.cancel()
        if self._server:
            self._server.close()
            try:
                await asyncio.wait_for(self._server.wait_closed(), 2)
            except asyncio.TimeoutError:
                pass

    # max inbound frame (reference: akka maximum-frame-size = 30 MB,
    # dds-system.conf:58): a peer declaring a huge length must not make
    # the receiver buffer it
    MAX_FRAME = 32 * 1024 * 1024

    @staticmethod
    def _note_frame(span: str, direction: str, t0: float, parent, size: int,
                    msg: str, dest: str) -> None:
        """One frame crossed the codec: its span (ending now, a child of
        the sender's span where the frame carries one) and counts."""
        tracer.record(
            span, (time.perf_counter() - t0) * 1e3,
            _ctx=obs_context.child(parent) if parent is not None else None,
            bytes=size, msg=msg, dest=dest.rsplit("/", 1)[-1])
        metrics.inc("dds_net_frames_total", direction=direction, msg=msg,
                    help="TcpNet frames by direction and message class")
        metrics.inc("dds_net_frame_bytes_total", size, direction=direction,
                    msg=msg,
                    help="TcpNet frame bytes (length prefix not counted) by "
                         "direction and message class")

    @staticmethod
    def _drop(reason: str, why: str, *args) -> None:
        log.warning("dropping frame: " + why, *args)
        metrics.inc("dds_net_frames_dropped_total", reason=reason,
                    help="TcpNet frames refused, by reason")

    async def _serve(self, reader: asyncio.StreamReader, writer: asyncio.StreamWriter):
        asyncio.current_task().set_name("tcp.serve")
        self._inbound.add(writer)
        try:
            while True:
                hdr = await reader.readexactly(4)
                size = int.from_bytes(hdr, "big")
                if size > self.MAX_FRAME:
                    # the connection goes with it: nothing after a length
                    # that was not read can be told apart from its body
                    self._drop("oversize", "%d bytes declared by %s", size,
                               writer.get_extra_info("peername"))
                    break
                frame = await reader.readexactly(size)
                # Per-frame decode must not tear down the shared connection:
                # a malformed frame (or one from a peer speaking a newer
                # codec during a rolling upgrade) is logged and skipped —
                # killing the loop here would silently drop every queued
                # frame behind it from the same peer.
                got = self._open_frame(frame)
                if got is None:
                    continue
                handler, tc, src, name, msg = got
                if tc is not None:
                    supervised_task(
                        self._handle_traced(handler, tc, src, msg),
                        name=f"tcp.handle:{name}",
                    )
                else:
                    supervised_task(handler(src, msg),
                                    name=f"tcp.handle:{name}")
        except (asyncio.IncompleteReadError, ConnectionResetError):
            pass
        finally:
            self._inbound.discard(writer)
            writer.close()

    def _open_frame(self, frame: bytes):
        """Decode and authenticate one inbound frame: (handler, the
        sender's trace context, src, the endpoint's name here, message), or
        None for a frame that is dropped (counted by reason) or addressed
        to no endpoint here."""
        t0 = time.perf_counter()
        try:
            obj = json.loads(frame)
            src, dest, payload = obj["src"], obj["dest"], obj["msg"]
            if not isinstance(src, str) or not isinstance(dest, str):
                raise ValueError("non-string src/dest")
        except Exception as e:
            return self._drop("undecodable", "undecodable (%s)", e)
        body = None
        if self._frame_secret is not None or self._peer_keys is not None:
            body = self._frame_body(src, dest, payload, obj.get("ctr"))
        if self._frame_secret is not None:
            mac = obj.get("mac")
            if not (isinstance(mac, str) and mac.isascii()
                    and hmac.compare_digest(mac, self._frame_mac(body))):
                return self._drop("bad_mac", "bad MAC (src claims %s)", src)
        if self._peer_keys is not None:
            src_host = src.split("/", 1)[0]
            pub = self._peer_keys.get(src_host)
            try:
                if pub is None:
                    raise ValueError("unregistered src host")
                # the signed dest must name THIS process (by its
                # ADVERTISED address): endpoint names repeat across
                # hosts (proxy-0, nodehost), so a frame captured on
                # the wire to host A must not verify and dispatch
                # on host B
                if "/" in dest and dest.split("/", 1)[0] != self.advertised:
                    raise ValueError("frame destined for another host")
                pub.verify(bytes.fromhex(obj.get("sig", "")), body)
                ctr = int(obj["ctr"])
                if ctr <= self._seen_ctr.get(src_host, -1):
                    raise ValueError("replayed frame counter")
                self._seen_ctr[src_host] = ctr
            except Exception:
                return self._drop(
                    "bad_signature", "bad/missing node signature, wrong "
                    "dest host, or replayed counter (src claims %s)", src)
        name = dest.split("/", 1)[1] if "/" in dest else dest
        handler = self._handlers.get(name)
        if handler is None:
            return None
        try:
            msg = M.from_dict(payload)
        except Exception as e:
            return self._drop("bad_payload",
                              "undecodable payload from %s (%s)", src, e)
        # restore the sender's trace context (frame `tc`, see _send) so
        # spans recorded by the handler join the originating request's
        # trace tree across the TCP hop. Observability metadata only —
        # outside the MAC, and a malformed field degrades to an unlinked
        # span, never a dropped message.
        tc = obs_context.from_wire(obj.get("tc"))
        self._note_frame("net.deserialize", "received", t0, tc, len(frame),
                         type(msg).__name__, dest)
        return handler, tc, src, name, msg

    @staticmethod
    async def _handle_traced(handler, tc, src: str, msg) -> None:
        token = obs_context.attach(tc)
        try:
            await handler(src, msg)
        finally:
            obs_context.detach(token)

    async def _watch(self, conn_key: str, reader: asyncio.StreamReader,
                     writer: asyncio.StreamWriter) -> None:
        """An outbound connection carries nothing back, so its reader ends
        when the peer does: drop the connection then, and the next send
        opens one to whoever listens there now. Without this the first
        frame after a peer went is written into a dead socket and lost
        without a sign."""
        try:
            while await reader.read(4096):
                pass
        except OSError:
            pass
        writer.close()
        if self._conns.get(conn_key) is writer:
            del self._conns[conn_key]

    def send(self, src: str, dest: str, msg: object) -> None:
        supervised_task(self._send(src, dest, msg),
                        name=f"tcp.send:{dest}")

    async def _send(self, src: str, dest: str, msg: object) -> None:
        host, port, _ = self.split(dest)
        conn_key = f"{host}:{port}"
        try:
            async with self._lock:
                w = self._conns.get(conn_key)
                if w is None or w.is_closing():
                    r, w = await asyncio.open_connection(host, port, ssl=self._ssl_client)
                    self._conns[conn_key] = w
                    t = supervised_task(self._watch(conn_key, r, w),
                                        name=f"tcp.watch:{conn_key}")
                    self._watchers.add(t)
                    t.add_done_callback(self._watchers.discard)
            t_ser = time.perf_counter()
            payload = M.to_dict(msg)
            obj = {"src": src, "dest": dest, "msg": payload}
            # trace-context propagation (ensure_future copied the caller's
            # contextvars into this task, so current() is the sender's span)
            cur = obs_context.current()
            if cur is not None:
                obj["tc"] = obs_context.to_wire(cur)
            if self._frame_secret is not None or self._node_key is not None:
                ctr = next(self._send_ctr) if self._node_key is not None else None
                if ctr is not None:
                    obj["ctr"] = ctr
                body = self._frame_body(src, dest, payload, ctr)
                if self._frame_secret is not None:
                    obj["mac"] = self._frame_mac(body)
                if self._node_key is not None:
                    obj["sig"] = self._node_key.sign(body).hex()
            frame = json.dumps(obj).encode()
            if len(frame) > self.MAX_FRAME:
                # symmetric with the receive bound: sending it anyway would
                # get the shared cached connection killed at the receiver,
                # silently losing queued frames behind it
                return self._drop(
                    "too_large_to_send", "%d bytes %s -> %s not sent "
                    "(MAX_FRAME %d)", len(frame), src, dest, self.MAX_FRAME)
            # Chronoscope's serialize stage: dict-encode + json + frame
            # MAC/signature
            self._note_frame("net.serialize", "sent", t_ser, cur, len(frame),
                             type(msg).__name__, dest)
            w.write(len(frame).to_bytes(4, "big") + frame)
            await w.drain()
        except OSError:
            log.warning("send failed %s -> %s", src, dest)
            self._conns.pop(conn_key, None)
