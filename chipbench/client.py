"""The load generator: an asyncio HTTP/1.1 client of ``/v1/completions``
with server-sent-event streaming, on one event-loop thread of its own.

Every request streams, so the client stamps each token as it arrives.  The
loop is open: each request is sent when it falls due, whether or not
earlier ones have finished, and is timed from that due time.  Every
request carries one deadline, the same for all, a little past the window's
end, at which the server retires what is still running ("timeout")
instead of decoding tokens that no metric reads."""
from __future__ import annotations

import asyncio
import json
import threading
import time
from typing import List, Optional


def _record(req, due: float) -> dict:
    return {"prompt": req.prompt, "max_tokens": req.max_tokens, "due": due,
            "sent": None, "status": None, "times": [], "tokens": [],
            "finish": None, "error": None}


async def _one(port: int, req, rec: dict, deadline: float) -> None:
    rec["sent"] = time.monotonic()
    try:
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
    except OSError as e:
        rec["error"] = f"connect: {e}"
        return
    try:
        body = json.dumps({"prompt": req.prompt,
                           "max_tokens": req.max_tokens, "stream": True,
                           "max_time": deadline - rec["sent"]}).encode()
        writer.write(b"POST /v1/completions HTTP/1.1\r\nHost: localhost\r\n"
                     b"Content-Type: application/json\r\nContent-Length: "
                     + str(len(body)).encode() + b"\r\n\r\n" + body)
        await writer.drain()
        status = (await reader.readline()).split()
        rec["status"] = int(status[1]) if len(status) > 1 else None
        while (await reader.readline()) not in (b"\r\n", b""):
            pass
        if rec["status"] != 200:
            rec["error"] = (await reader.read(2000)).decode("latin-1")
            return
        while True:
            line = await reader.readline()
            if not line:
                break
            if not line.startswith(b"data: "):
                continue
            now = time.monotonic()
            data = line[6:].strip()
            if data == b"[DONE]":
                break
            payload = json.loads(data)
            if "error" in payload:
                rec["error"] = str(payload["error"])
                break
            choice = payload["choices"][0]
            for t in choice.get("token_ids", []):
                rec["tokens"].append(int(t))
                rec["times"].append(now)
            if choice.get("finish_reason"):
                rec["finish"] = choice["finish_reason"]
    except (OSError, ValueError, asyncio.IncompleteReadError) as e:
        rec["error"] = f"{type(e).__name__}: {e}"
    finally:
        writer.close()


async def _open_loop(port, plan, t0, deadline, records):
    async def due(req, rec):
        await asyncio.sleep(max(0.0, rec["due"] - time.monotonic()))
        await _one(port, req, rec, deadline)

    tasks = []
    for req in plan.requests:
        rec = _record(req, t0 + req.due_s)
        records.append(rec)
        tasks.append(asyncio.ensure_future(due(req, rec)))
    await asyncio.gather(*tasks)


class Load:
    """Runs a plan against ``port`` on its own thread: request i falls due
    at ``t0 + due_s`` and every request ends by ``deadline`` (monotonic
    times)."""

    def __init__(self, port: int, plan, t0: float, deadline: float):
        self.records: List[dict] = []
        self.error: Optional[BaseException] = None
        self._args = (port, plan, t0, deadline)
        self.thread = threading.Thread(target=self._run, daemon=True,
                                       name="chipbench-client")

    def _run(self):
        port, plan, t0, deadline = self._args
        loop = asyncio.new_event_loop()
        try:
            loop.run_until_complete(
                _open_loop(port, plan, t0, deadline, self.records))
        except BaseException as e:          # reported by join()
            self.error = e
        finally:
            loop.close()

    def start(self):
        self.thread.start()

    def join(self, timeout: float) -> None:
        """Waits for every request to end, at most ``timeout`` seconds; one
        that has not ended has no complete record and counts as failed."""
        self.thread.join(timeout)
        if self.error is not None:
            raise self.error


def window_gaps(records, t0: float, t1: float) -> List[float]:
    """Gaps between consecutive streamed tokens of one request, over every
    request, whose later token arrived in [t0, t1)."""
    return [b - a for r in records for a, b in zip(r["times"], r["times"][1:])
            if t0 <= b < t1]
