"""The load generator's HTTP client: one request on one connection, as the
reference client makes them, with its own timeout. It never raises into a
client loop: whatever goes wrong comes back as status 0 and the reason."""

from __future__ import annotations

import asyncio


async def _exchange(host, port, method, target, body):
    reader, writer = await asyncio.open_connection(host, port)
    try:
        payload = body or b""
        writer.write((
            f"{method} {target} HTTP/1.1\r\nHost: {host}:{port}\r\n"
            "Content-Type: application/json\r\n"
            f"Content-Length: {len(payload)}\r\nConnection: close\r\n\r\n"
        ).encode() + payload)
        await writer.drain()
        status = int((await reader.readline()).split()[1])
        length = None
        while True:
            line = await reader.readline()
            if line in (b"\r\n", b"\n", b""):
                break
            name, _, val = line.decode("latin-1").partition(":")
            if name.strip().lower() == "content-length":
                length = int(val)
        data = (await reader.readexactly(length) if length is not None
                else await reader.read())
        return status, data
    finally:
        writer.close()


async def request(host: str, port: int, method: str, target: str,
                  body: bytes | None = None,
                  timeout: float = 30.0) -> tuple[int, bytes]:
    """(status, body); status 0 and the reason as bytes on any failure."""
    try:
        return await asyncio.wait_for(
            _exchange(host, port, method, target, body), timeout)
    except asyncio.TimeoutError:
        return 0, f"client timeout after {timeout} s".encode()
    except (OSError, ValueError, IndexError,
            asyncio.IncompleteReadError) as e:
        return 0, f"{type(e).__name__}: {e}".encode()
