"""The load generator: SSE streams over asyncio, one thread.

Every request is timed from the moment it was DUE (open loop) or from
its send (closed loop), by the arrival of each streamed chunk. The
server sends one chunk per generated token (the synthetic tokenizer
sees to it), so the first chunk is the first token and the gaps between
chunks are the gaps between tokens. Stdlib only.
"""

from __future__ import annotations

import asyncio
import dataclasses
import json
import random
import time
from typing import Dict, List, Optional, Sequence

import modeldir
from traffic import Planned


@dataclasses.dataclass
class Answer:
    index: int
    due: float = 0.0               # monotonic seconds
    sent: float = 0.0
    status: int = 0                # HTTP status; 0 = no answer
    arrivals: List[float] = dataclasses.field(default_factory=list)
    words: List[str] = dataclasses.field(default_factory=list)
    finish_reason: Optional[str] = None
    usage_tokens: Optional[int] = None
    request_id: Optional[str] = None
    done: bool = False             # saw [DONE]
    error: Optional[str] = None
    prompt_ids: Optional[List[int]] = None
    max_tokens: int = 0
    temperature: float = 0.0

    @property
    def failed(self) -> bool:
        """Non-200, a broken stream, or no first token. An answer that
        stopped early with fewer tokens is an answered request."""
        return (self.status != 200 or not self.done
                or not self.arrivals or self.error is not None)

    def token_ids(self) -> Optional[List[int]]:
        """The served ids, when every chunk carried exactly one word
        and their count is the server's own count."""
        try:
            ids = [modeldir.token_id(w) for w in self.words]
        except ValueError:
            return None
        return ids if len(ids) == self.usage_tokens else None


async def stream_one(host: str, port: int, ans: Answer, prompt: str,
                     timeout: float) -> None:
    body = json.dumps({"prompt": prompt, "max_tokens": ans.max_tokens,
                       "temperature": ans.temperature,
                       "stream": True}).encode()
    head = (f"POST /v1/completions HTTP/1.1\r\nHost: {host}:{port}\r\n"
            "Content-Type: application/json\r\n"
            f"Content-Length: {len(body)}\r\nConnection: close\r\n\r\n")
    writer = None
    try:
        reader, writer = await asyncio.open_connection(host, port)
        ans.sent = time.monotonic()
        writer.write(head.encode() + body)
        await writer.drain()

        async def read():
            status = await reader.readline()
            parts = status.split()
            ans.status = int(parts[1]) if len(parts) > 1 else 0
            while (await reader.readline()).strip():
                pass                          # headers
            if ans.status != 200:
                return
            while True:
                line = await reader.readline()
                if not line:
                    return
                if not line.startswith(b"data: "):
                    continue                  # chunk sizes, blank lines
                now = time.monotonic()
                data = line[6:].strip()
                if data == b"[DONE]":
                    ans.done = True
                    return
                ev = json.loads(data)
                if ans.request_id is None:
                    ans.request_id = ev.get("id")
                choice = ev["choices"][0]
                if "usage" in ev:
                    ans.usage_tokens = ev["usage"]["completion_tokens"]
                    ans.finish_reason = choice.get("finish_reason")
                    continue
                text = choice.get("text")
                if text:
                    ans.arrivals.append(now)
                    ans.words.append(text)

        await asyncio.wait_for(read(), timeout)
    except Exception as e:  # a broken stream is a failed request
        ans.error = f"{type(e).__name__}: {e}"
    finally:
        if writer is not None:
            writer.close()


def _prompt(p: Planned, vocab_size: int):
    ids = modeldir.prompt_ids(random.Random(p.prompt_seed),
                              p.prompt_tokens, vocab_size)
    return ids, modeldir.prompt_text(ids)


async def _drive(host, port, planned: Sequence[Planned], vocab_size: int,
                 seconds: float, drain_s: float, on_start,
                 exhaust_ok: bool) -> Dict:
    answers = [Answer(index=p.index, max_tokens=p.max_tokens,
                      temperature=p.temperature) for p in planned]
    prompts = []
    for p, a in zip(planned, answers):
        ids, text = _prompt(p, vocab_size)
        a.prompt_ids = ids
        prompts.append(text)
    timeout = seconds + drain_s
    t0 = time.monotonic() + 0.05
    if on_start is not None:
        on_start(t0)
    tasks = []
    if planned and planned[0].due_s is not None:      # open loop
        for p, a, text in zip(planned, answers, prompts):
            a.due = t0 + p.due_s
            delay = a.due - time.monotonic()
            if delay > 0:
                await asyncio.sleep(delay)
            tasks.append(asyncio.ensure_future(
                stream_one(host, port, a, text, timeout)))
        attempted = list(answers)
    else:                                             # closed loop
        queues: Dict[int, List[int]] = {}
        for i, p in enumerate(planned):
            queues.setdefault(p.client, []).append(i)
        attempted = []

        async def client(indices):
            for i in indices:
                now = time.monotonic()
                if now >= t0 + seconds:
                    return
                a = answers[i]
                a.due = max(now, t0)
                if a.due > now:
                    await asyncio.sleep(a.due - now)
                attempted.append(a)
                await stream_one(host, port, a, prompts[i], timeout)
            if not exhaust_ok:
                raise RuntimeError(
                    "a closed-loop client ran out of requests inside the "
                    "window: raise requests_per_client in the traffic file")

        tasks = [asyncio.ensure_future(client(q)) for q in queues.values()]
    done, pending = await asyncio.wait(
        tasks, timeout=max(t0 + timeout - time.monotonic(), 0.1))
    for t in pending:
        t.cancel()
    await asyncio.gather(*pending, return_exceptions=True)
    for t in done:
        if t.exception() is not None:
            raise t.exception()
    return {"t0": t0, "t1": t0 + seconds, "answers": attempted}


def drive(url: str, planned: Sequence[Planned], vocab_size: int,
          seconds: float, drain_s: float = 60.0, on_start=None,
          exhaust_ok: bool = False) -> Dict:
    """Run one window against `url`; returns t0, t1 (monotonic) and the
    attempted requests, drained. A closed-loop client that runs out of
    requests inside the window is an error of the traffic file, unless
    `exhaust_ok` (warm-up sends a fixed list)."""
    host, port = url.split("//", 1)[1].rsplit(":", 1)
    return asyncio.run(_drive(host, int(port), planned, vocab_size,
                              seconds, drain_s, on_start, exhaust_ok))
