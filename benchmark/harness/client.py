"""The client side: sends the generated requests to ``POST /v1/generate`` on
a localhost socket and stamps every SSE ``data:`` event as it arrives.

The client has a thread and an asyncio loop of its own, so that an event is
stamped when its bytes reach the socket and not when the serving loop (which
runs engine passes on the main thread's loop) next yields. One process, one
extra thread: the chip belongs to this process, so the load cannot come from
a child.
"""

from __future__ import annotations

import asyncio
import itertools
import json
import threading
import time
from typing import List, Optional, Sequence

from .loadgen import Request
from .metrics import RequestLog


class LoadDriver:
    """Open loop: each request goes out at ``t_start + due``. Closed loop:
    ``clients`` tasks each send their next request when the last one ended.
    The load runs until :meth:`stop`; requests in flight then are dropped
    (their sockets close, the server cancels them)."""

    def __init__(self, host: str, port: int, requests: Sequence[Request],
                 prompts: Sequence[List[int]], *, loop_kind: str,
                 clients: int = 0):
        self.host, self.port = host, port
        self.requests, self.prompts = requests, prompts
        self.loop_kind, self.clients = loop_kind, clients
        self.logs: List[RequestLog] = []
        self.fault: Optional[BaseException] = None
        self._thread: Optional[threading.Thread] = None
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._main: Optional[asyncio.Task] = None
        self._t_start = 0.0

    # -- lifecycle ---------------------------------------------------------
    def start(self, t_start: float) -> None:
        self._t_start = t_start
        ready = threading.Event()
        self._thread = threading.Thread(target=self._run, args=(ready,),
                                        name="bench-client", daemon=True)
        self._thread.start()
        ready.wait()

    def stop(self) -> None:
        if self._loop is not None and self._main is not None:
            try:
                self._loop.call_soon_threadsafe(self._main.cancel)
            except RuntimeError:     # the loop has ended: its fault is below
                pass
        if self._thread is not None:
            self._thread.join(timeout=30)
            if self._thread.is_alive():
                raise RuntimeError("the client thread did not stop")
        if self.fault is not None:
            raise RuntimeError("the load generator failed") from self.fault

    def _run(self, ready: threading.Event) -> None:
        self._loop = asyncio.new_event_loop()
        try:
            self._main = self._loop.create_task(self._drive())
            ready.set()
            self._loop.run_until_complete(self._main)
        except asyncio.CancelledError:
            pass
        except BaseException as e:            # surfaced by stop()
            self.fault = e
        finally:
            ready.set()
            self._loop.close()

    # -- the two loops -----------------------------------------------------
    async def _drive(self) -> None:
        tasks: List[asyncio.Task] = []
        try:
            if self.loop_kind == "open":
                for req in self.requests:
                    delay = self._t_start + req.due - time.perf_counter()
                    if delay > 0:
                        await asyncio.sleep(delay)
                    log = self._new_log(req, due=self._t_start + req.due)
                    tasks.append(asyncio.ensure_future(self._send(log)))
                await asyncio.gather(*tasks)
            else:
                counter = itertools.count()
                tasks = [asyncio.ensure_future(self._closed_client(counter))
                         for _ in range(self.clients)]
                await asyncio.gather(*tasks)
        finally:
            for t in tasks:
                t.cancel()
            await asyncio.gather(*tasks, return_exceptions=True)

    async def _closed_client(self, counter) -> None:
        while True:
            i = next(counter)
            if i >= len(self.requests):
                raise RuntimeError(
                    f"closed loop used all {len(self.requests)} requests of "
                    "the mix's pool; raise pool_requests in the mix file")
            await self._send(self._new_log(self.requests[i], due=None))

    def _new_log(self, req: Request, due: Optional[float]) -> RequestLog:
        log = RequestLog(index=req.index, prompt_len=req.prompt_len,
                         asked=req.max_new_tokens, due=due)
        self.logs.append(log)
        return log

    # -- one request -------------------------------------------------------
    async def _send(self, log: RequestLog) -> None:
        body = json.dumps({"prompt": self.prompts[log.index],
                           "max_new_tokens": log.asked}).encode()
        writer = None
        try:
            reader, writer = await asyncio.open_connection(self.host,
                                                           self.port)
            writer.write(b"POST /v1/generate HTTP/1.1\r\nContent-Length: "
                         + str(len(body)).encode() + b"\r\n\r\n" + body)
            await writer.drain()
            log.sent = time.perf_counter()
            status = await reader.readline()
            if b" 200 " not in status:
                rest = await reader.read()
                log.error = (f"HTTP {status.decode(errors='replace').strip()}"
                             f": {rest[-200:].decode(errors='replace')}")
                return
            while True:
                line = await reader.readline()
                now = time.perf_counter()
                if not line:
                    log.error = log.error or "stream closed with no done event"
                    return
                if not line.startswith(b"data: "):
                    continue
                event = json.loads(line[6:])
                if event.get("done"):
                    log.ended, log.reason = now, event.get("reason")
                    if "error" in event:
                        log.error = f"done with error: {event['error']}"
                    return
                log.token_times.append(now)
                log.tokens.append(event["token"])
        except (ConnectionError, OSError, ValueError, KeyError) as e:
            log.error = f"{type(e).__name__}: {e}"
        finally:
            if writer is not None:
                writer.close()

    # -- what the orchestrator polls ---------------------------------------
    def waiting_for_first_token(self, lo: float, hi: float) -> int:
        """Requests that started in the window and have no token, no end and
        no error yet."""
        return sum(1 for r in list(self.logs)
                   if r.start is not None and lo <= r.start < hi
                   and not r.token_times and r.ended is None
                   and r.error is None)
