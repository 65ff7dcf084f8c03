"""Asyncio HTTP/SSE client for ``POST /v1/session`` (copied from
``launch/serve.py::sse_submit`` so the yardstick stays with the
benchmark), stamping each token event with the time it was read."""
from __future__ import annotations

import asyncio
import dataclasses
import json
import time
from typing import Dict, List, Optional


@dataclasses.dataclass
class Stream:
    """What one client saw of one session."""
    spec_seed: int
    sent_s: float = 0.0                      # connection opened
    status: int = 0                          # HTTP status; 0 = none yet
    events: List[Dict] = dataclasses.field(default_factory=list)
    received_s: List[float] = dataclasses.field(default_factory=list)
    end: str = ""                    # "done" | "aborted" | "cut" | ""

    @property
    def session_id(self) -> Optional[int]:
        return self.events[0]["session_id"] if self.events else None


async def submit(host: str, port: int, spec: Dict, stream: Stream,
                 ) -> Stream:
    """POST one session spec and read its SSE stream into ``stream``
    as it arrives (so a caller that cancels the task keeps what was
    read)."""
    stream.sent_s = time.perf_counter()
    reader, writer = await asyncio.open_connection(host, port)
    try:
        body = json.dumps(spec).encode()
        writer.write((f"POST /v1/session HTTP/1.1\r\nHost: {host}\r\n"
                      f"Content-Type: application/json\r\n"
                      f"Content-Length: {len(body)}\r\n\r\n").encode()
                     + body)
        await writer.drain()
        status_line = await reader.readline()
        stream.status = int(status_line.split()[1])
        while (await reader.readline()) not in (b"\r\n", b"\n", b""):
            pass                             # response headers
        if stream.status != 200:
            return stream
        while True:
            line = await reader.readline()
            if not line:
                break
            line = line.strip()
            if line in (b"event: done", b"event: aborted"):
                stream.end = line.split(b": ")[1].decode()
                await reader.readline()      # the terminal data record
                break
            if line.startswith(b"data: "):
                stream.received_s.append(time.perf_counter())
                stream.events.append(json.loads(line[len(b"data: "):]))
        return stream
    finally:
        writer.close()
        try:
            await writer.wait_closed()
        except (ConnectionResetError, BrokenPipeError):
            pass
