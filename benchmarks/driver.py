"""The client: sends a plan's chains to the server's ``/dialog/`` with
``stream: true`` from one asyncio thread of a process that holds no chip, and
logs, on its own clock, when each turn was due, when each token arrived and
what it was.

The served model's vocabulary is set up so that every token is one character
(``benchmarks/weights.py``), and the server writes one event per token: the
characters of an event's ``delta`` are the served token ids, and the time the
event was read is when they arrived.  The terminal event carries the server's
own count of prompt and completion tokens; the log keeps both so that the
check can hold them against what was sent and what was read.

Open loop: every chain has its due time and is sent then, whether or not
earlier ones have finished.  Closed loop and sessions: ``clients`` coroutines
each take the next chain when the last one is done; a turn is due when its
client is ready for it (after the think time).  Every turn is timed from when
it was due.  A turn is *measured* when it was due inside the window; the run
waits for those (up to the mix's cap) however long after the window they end.
"""

from __future__ import annotations

import asyncio
import json
import time
from typing import Any, Callable, Dict, List, Optional

from benchmarks.traffic_gen import Chain, Plan, Turn

now = time.monotonic


class Log:
    def __init__(self) -> None:
        self.events: List[Dict[str, Any]] = []
        self.late_ms: List[float] = []  # how late the generator sent an open-loop request
        self.lag_ms: List[float] = []   # how late this thread's own 10 ms heartbeat woke


async def _turn(http, url: str, model: str, turn: Turn, due: float, ev: Dict[str, Any]) -> None:
    ids = turn.prompt_ids
    ev.update(due=due, prompt_ids=ids, prompt_len=len(ids), prefix_len=turn.prefix_len,
              max_tokens=turn.max_tokens, tokens=[], times=[], error=None)
    body = {"model": model, "messages": turn.messages, "max_tokens": turn.max_tokens,
            "temperature": 0.0, "top_p": 1.0, "stream": True}
    try:
        ev["submit"] = now()
        async with http.post(url, json=body) as resp:
            if resp.status != 200:
                ev["error"] = f"HTTP {resp.status}: {(await resp.text())[:200]}"
                return
            buf = b""
            async for chunk in resp.content.iter_any():
                t = now()
                buf += chunk
                while b"\n\n" in buf:
                    block, buf = buf.split(b"\n\n", 1)
                    if not block.startswith(b"data: ") or block == b"data: [DONE]":
                        continue
                    msg = json.loads(block[6:])
                    if "delta" in msg:
                        for ch in msg["delta"]:
                            ev["tokens"].append(ord(ch))
                            ev["times"].append(t)
                    elif msg.get("done"):
                        if msg.get("error") or msg.get("finish_reason") == "error":
                            ev["error"] = str(msg.get("error"))
                        ev["usage"] = msg.get("usage")
                        ev["done"] = t
    except Exception as e:  # a refused connection, a reset stream
        ev["error"] = repr(e)


async def _chain(http, url: str, model: str, chain: Chain, t_open: float, t_close: float, log: Log,
                 first_due: Optional[float]) -> None:
    ready = first_due if first_due is not None else now()
    for i, turn in enumerate(chain.turns):
        due = ready + turn.think_s
        delay = due - now()
        if delay > 0:
            await asyncio.sleep(delay)
        if first_due is None and due >= t_close:
            return  # closed loop: nothing new is due after the window
        ev: Dict[str, Any] = {"chain": chain.index, "turn": i,
                              "measured": chain.measured and t_open <= due < t_close}
        log.events.append(ev)
        if first_due is not None and i == 0:
            log.late_ms.append((now() - due) * 1e3)
        await _turn(http, url, model, turn, due, ev)
        ready = now()


async def run(base_url: str, model: str, plan: Plan, on_open: Callable[[], None],
              on_close: Callable[[], None]) -> Dict[str, Any]:
    """Warm traffic, then the window.  ``on_open``/``on_close`` run at the
    window's edges (counter snapshots).  Returns the log and the window's
    edges on the client's clock."""
    import aiohttp

    log = Log()
    url = base_url + "/dialog/"
    t_open = now() + plan.warm_s
    t_close = t_open + plan.seconds

    async def edges():
        await asyncio.sleep(max(0.0, t_open - now()))
        on_open()
        await asyncio.sleep(max(0.0, t_close - now()))
        on_close()

    async def heartbeat():
        while now() < t_close:
            t = now()
            await asyncio.sleep(0.01)
            if t >= t_open:
                log.lag_ms.append((now() - t - 0.01) * 1e3)

    timeout = aiohttp.ClientTimeout(total=None, sock_connect=10.0)
    async with aiohttp.ClientSession(connector=aiohttp.TCPConnector(limit=0), timeout=timeout) as http:
        side = [asyncio.create_task(edges()), asyncio.create_task(heartbeat())]
        if plan.kind == "open":
            tasks = [asyncio.create_task(_chain(http, url, model, c, t_open, t_close, log, t_open + c.due_s))
                     for c in plan.open_chains()]
        else:
            chains = plan.closed_chains()

            async def client(j: int):
                await asyncio.sleep(max(0.0, t_open - plan.warm_s + j * plan.stagger_s - now()))
                while now() < t_close:
                    await _chain(http, url, model, next(chains), t_open, t_close, log, None)

            tasks = [asyncio.create_task(client(j)) for j in range(plan.clients)]
        cap = float(plan.mix.get("finish_cap_s", 30.0))
        done, pending = await asyncio.wait(tasks, timeout=plan.warm_s + plan.seconds + cap)
        for t in side:
            await t
        for t in pending:
            t.cancel()
        await asyncio.gather(*pending, return_exceptions=True)
        for t in done:
            t.result()  # a bug in the driver itself must not pass silently
    for ev in log.events:
        if ev["measured"] and "done" not in ev and not ev["error"]:
            ev["error"] = "not finished within the cap"
    return {"events": log.events, "late_ms": log.late_ms, "lag_ms": log.lag_ms,
            "t_open": t_open, "t_close": t_close}
