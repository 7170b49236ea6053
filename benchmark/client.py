"""The load generator: raw asyncio HTTP/1.1 clients that send MCP
`tools/call` to the gateway, closed loop or open loop, and log every
call. Copied in spirit from scripts/loadgen.py (one keep-alive
connection per client, responses framed by Content-Length), extended to
build each call from the schedule and to keep the result.

A call record is (client, due, sent, done, ok, completion_tokens, info).
Times are time.monotonic(). `due` is when the call should have been
sent (open loop: its arrival time; closed loop: equal to `sent`), and
latency is timed from `due`, so a stall charges the calls behind it.

A greedy row that samples the end-of-sequence id ends early, and which
rows do depends on the token ids, that is on the seed. So that every
seed still asks the model for the same number of tokens, a call that
stops early is CONTINUED: one seeded filler id takes the place of the
end-of-sequence id, and the rest of the scheduled tokens are asked for
in a follow-up request on the same connection. The logical call runs
from the first send to the last reply (until the sidecar has an
ignore-EOS field: PERF.md, Open questions).
"""

from __future__ import annotations

import asyncio
import dataclasses
import json
import time

from benchmark import schedule

GENERATE = "ggrmcp_tpu_generateservice_generate"
# An early stop is continued at most this often within one call.
MAX_CONTINUATIONS = 8


@dataclasses.dataclass
class Call:
    client: int
    due: float
    sent: float
    done: float
    ok: bool
    completion_tokens: int
    finish: str
    prompt: list  # ids sent
    output: list  # ids returned
    error: str = ""
    phase: str = ""  # probe | ramp | run
    session: int = -1  # closed loop: the client's session number
    turn: int = 0
    stops: int = 0  # times the model ended it early and it was continued
    # [start, end) of the tokens the model made, as positions in
    # prompt + output (fillers that replace an end-of-sequence id are
    # in `output` but in no segment)
    segments: list = dataclasses.field(default_factory=list)

    @property
    def ms(self) -> float:
        return (self.done - self.due) * 1000.0


class _Conn(asyncio.Protocol):
    """One keep-alive connection, one request in flight."""

    def __init__(self) -> None:
        self.transport = None
        self.buf = b""
        self.waiter = None
        self.closed = None

    def connection_made(self, transport) -> None:
        self.transport = transport

    def connection_lost(self, exc) -> None:
        self.closed = exc or ConnectionResetError("server closed connection")
        if self.waiter is not None and not self.waiter.done():
            self.waiter.set_exception(self.closed)

    def data_received(self, data: bytes) -> None:
        self.buf += data
        if self.waiter is None or self.waiter.done():
            return
        end = self.buf.find(b"\r\n\r\n")
        if end < 0:
            return
        head = self.buf[:end]
        lower = head.lower()
        idx = lower.find(b"content-length:")
        clen = 0
        if idx >= 0:
            eol = lower.find(b"\r\n", idx)
            clen = int(lower[idx + 15: eol if eol >= 0 else len(lower)])
        total = end + 4 + clen
        if len(self.buf) < total:
            return
        payload = self.buf[end + 4: total]
        self.buf = self.buf[total:]
        self.waiter.set_result((head, payload))


class Http:
    """POST / and GET on one connection to the gateway."""

    def __init__(self, host: str, port: int, timeout_s: float) -> None:
        self.host, self.port, self.timeout_s = host, port, timeout_s
        self.conn: _Conn | None = None

    async def _ensure(self) -> _Conn:
        if self.conn is None or self.conn.closed is not None:
            loop = asyncio.get_running_loop()
            _, self.conn = await loop.create_connection(
                _Conn, self.host, self.port
            )
        return self.conn

    async def request(self, method: str, path: str, body: bytes = b"") -> bytes:
        conn = await self._ensure()
        head = (
            f"{method} {path} HTTP/1.1\r\nHost: {self.host}:{self.port}\r\n"
            f"Content-Type: application/json\r\nAccept: application/json\r\n"
            f"Content-Length: {len(body)}\r\n\r\n"
        ).encode()
        conn.waiter = asyncio.get_running_loop().create_future()
        conn.transport.write(head + body)
        try:
            status, payload = await asyncio.wait_for(
                conn.waiter, self.timeout_s
            )
        except BaseException:
            # A half-read reply would poison the next request.
            self.close()
            raise
        if not status.startswith(b"HTTP/1.1 200"):
            raise RuntimeError(
                f"{method} {path}: {status[:40]!r} {payload[:200]!r}"
            )
        return payload

    async def tool(self, name: str, arguments: dict) -> dict:
        body = json.dumps({
            "jsonrpc": "2.0", "method": "tools/call", "id": 1,
            "params": {"name": name, "arguments": arguments},
        }).encode()
        reply = json.loads(await self.request("POST", "/", body))
        if "error" in reply or reply["result"].get("isError"):
            raise RuntimeError(f"{name}: {json.dumps(reply)[:300]}")
        return json.loads(reply["result"]["content"][0]["text"])

    def close(self) -> None:
        if self.conn is not None and self.conn.transport is not None:
            self.conn.transport.close()
        self.conn = None


def generate_arguments(prompt: list, new: int, constraint=None) -> dict:
    args = {
        "promptIds": {
            "dtype": "int32", "shape": [len(prompt)], "intValues": prompt,
        },
        "maxNewTokens": new,
        "sampling": {"temperature": 0},
        "returnTokens": True,
    }
    if constraint:
        args["constraint"] = constraint
    return args


class Load:
    """Drives one schedule against one gateway and keeps the log."""

    def __init__(
        self, sched: schedule.Schedule, seed: int, vocab: int, host: str,
        port: int, timeout_s: float,
    ) -> None:
        self.sched, self.seed, self.vocab = sched, seed, vocab
        self.host, self.port, self.timeout_s = host, port, timeout_s
        self.calls: list[Call] = []
        self.phase = "ramp"
        self.stopping = False
        self.first_done = [False] * max(sched.clients, 1)
        self.sessions_done = [0] * max(sched.clients, 1)
        self.late_s: list[float] = []  # open loop: sent - due
        self.prefix = schedule.token_ids(
            seed, vocab, sched.shared_prefix_tokens, "prefix"
        )

    async def one(
        self, http: Http, client: int, prompt: list, new: int,
        due: float | None = None, session: int = -1, turn: int = 0,
    ) -> Call:
        sent = time.monotonic()
        call = Call(
            client=client, due=sent if due is None else due, sent=sent,
            done=sent, ok=False, completion_tokens=0, finish="",
            prompt=prompt, output=[], phase=self.phase, session=session,
            turn=turn,
        )
        made = 0
        try:
            while True:
                out = await http.tool(GENERATE, generate_arguments(
                    prompt + call.output, new - len(call.output),
                    self.sched.constraint))
                ids = [int(i) for i in out.get("tokenIds", [])]
                call.finish = out.get("finishReason", "")
                if (int(out.get("completionTokens", 0)) != len(ids)
                        or len(call.output) + len(ids) > new
                        or call.finish not in ("length", "stop",
                                               "grammar_complete")):
                    call.error = f"bad result: {json.dumps(out)[:200]}"
                    break
                at = len(prompt) + len(call.output)
                call.segments.append([at, at + len(ids)])
                call.output += ids
                made += len(ids)
                if (call.finish != "stop" or len(call.output) + 1 >= new
                        or call.stops >= MAX_CONTINUATIONS):
                    call.ok = True
                    break
                call.stops += 1
                call.output += schedule.token_ids(
                    self.seed, self.vocab, 1, "eos", client, session, turn,
                    call.stops)
            call.completion_tokens = made
        except asyncio.CancelledError:
            raise
        except Exception as exc:  # refused, failed, timed out: all failed
            call.error = f"{type(exc).__name__}: {exc}"[:300]
        call.done = time.monotonic()
        self.calls.append(call)
        return call

    async def client_loop(self, client: int) -> None:
        """Closed loop: walk the list from this client's offset,
        session after session, until told to stop."""
        sched = self.sched
        http = Http(self.host, self.port, self.timeout_s)
        index = sched.offsets[client]
        session = 0
        try:
            while not self.stopping:
                history: list[int] = []
                block = schedule.session_block(sched, index)
                for turn, (p_len, o_len) in enumerate(block):
                    new_ids = schedule.token_ids(
                        self.seed, self.vocab, p_len,
                        "c", client, session, turn,
                    )
                    prompt = self.prefix + history + new_ids
                    call = await self.one(
                        http, client, prompt, o_len, session=session, turn=turn)
                    self.first_done[client] = True
                    if self.stopping:
                        return
                    if sched.session_turns > 1:
                        # History keeps the scheduled length whatever
                        # the model returned: pad an early stop.
                        pad = schedule.token_ids(
                            self.seed, self.vocab, o_len - len(call.output),
                            "pad", client, session, turn,
                        ) if len(call.output) < o_len else []
                        history = history + new_ids + call.output + pad
                    if sched.think_time_s:
                        await asyncio.sleep(sched.think_time_s)
                index = (index + sched.session_turns) % len(sched.pairs)
                session += 1
                self.sessions_done[client] += 1
        finally:
            http.close()

    async def open_loop(self, horizon_s: float) -> None:
        """Open loop: every call is sent when due, on a connection of
        its own, and timed from when it was due."""
        sched = self.sched
        t0 = time.monotonic()
        tasks = []

        async def fire(k: int, due: float) -> None:
            http = Http(self.host, self.port, self.timeout_s)
            try:
                p_len, o_len = sched.pairs[k % len(sched.pairs)]
                prompt = self.prefix + schedule.token_ids(
                    self.seed, self.vocab, p_len, "o", k
                )
                self.late_s.append(time.monotonic() - due)
                await self.one(http, k, prompt, o_len, due=due, session=0)
            finally:
                http.close()

        for k, offset in enumerate(schedule.arrivals(sched, horizon_s)):
            if self.stopping:
                break
            delay = t0 + offset - time.monotonic()
            if delay > 0:
                await asyncio.sleep(delay)
            tasks.append(asyncio.ensure_future(fire(k, t0 + offset)))
        for task in tasks:
            if self.stopping:
                task.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)

    def ramped(self) -> bool:
        if self.sched.loop == "open":
            return any(c.ok for c in self.calls)
        if self.sched.ramp == "session":
            return all(n >= 1 for n in self.sessions_done)
        return all(self.first_done)
