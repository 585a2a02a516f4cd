"""Drive one measured window through ``AsyncCacheServer.submit_request``.

Each request is sent from an eager task, so the program's ``submit`` runs
at once, up to its first wait: the two clock readings around the send
bracket the instant the scheduler stamps the request's arrival, and
``Response.latency_s`` counts from that stamp to the end of its batch. So
every answer carries the interval in which its batch ended; the check uses
it to replay the program's own batches.

The window's own bookkeeping stays out of the garbage collector's way, so
that the program pays no more for a collection late in the window than
early: a reply is kept as a plain tuple of numbers and text, which the
collector stops tracking, and a request's task is dropped once it is
done. Kept as objects, the replies and tasks of a closed-loop window
(~60,000 in 30 s) would make each full collection slower as the window
goes on, and the served rate would fall with the window's length.
"""
from __future__ import annotations

import asyncio
import time
from typing import NamedTuple

#: How long past the window's close answers are awaited.
GRACE_S = 60.0


class Reply(NamedTuple):
    """What the check and the metrics read of a ``Response``."""

    answer: str
    cached: bool
    score: float
    latency_s: float
    coalesced: bool


def _fields(resp) -> tuple:
    """A ``Response`` as a plain tuple: atomic values only, so the
    collector untracks it."""
    return (str(resp.answer), bool(resp.cached), float(resp.score),
            float(resp.latency_s), bool(resp.coalesced))


class Records:
    """Per request: the item, its due time (open loop), the send bracket,
    when the reply was seen, and the reply (a ``Reply`` once the window
    is sealed) or the error."""

    def __init__(self):
        self.items: list = []
        self.due: list = []
        self.t0: list = []
        self.t1: list = []
        self.t_obs: list = []
        self.resp: list = []
        self.error: list = []

    def add(self, item, due) -> int:
        self.items.append(item)
        self.due.append(due)
        self.t0.append(None)
        self.t1.append(None)
        self.t_obs.append(None)
        self.resp.append(None)
        self.error.append(None)
        return len(self.items) - 1

    def __len__(self) -> int:
        return len(self.items)

    def seal(self) -> None:
        """Turn the window's plain reply tuples into ``Reply``s."""
        self.resp = [r if r is None else Reply._make(r) for r in self.resp]


async def _send(server, request, rec: Records, i: int) -> None:
    try:
        resp = await server.submit_request(request)
    except Exception as exc:  # a refused or failed request is a result
        rec.t_obs[i] = time.perf_counter()
        rec.error[i] = f"{type(exc).__name__}: {exc}"
        return
    rec.t_obs[i] = time.perf_counter()
    rec.resp[i] = _fields(resp)


def _start(server, make_request, rec: Records, i: int,
           pending: set) -> asyncio.Task:
    req = make_request(rec.items[i])
    rec.t0[i] = time.perf_counter()
    task = asyncio.get_running_loop().create_task(_send(server, req, rec, i))
    rec.t1[i] = time.perf_counter()
    if not task.done():
        pending.add(task)
        task.add_done_callback(pending.discard)
    return task


async def _finish(pending: set, close: float) -> None:
    if pending:
        await asyncio.wait(list(pending),
                           timeout=max(0.0, close + GRACE_S
                                       - time.perf_counter()))
    for t in list(pending):
        t.cancel()


async def drive(server, traffic, make_request, seconds: float, *,
                on_open=None, on_close=None) -> tuple[Records, float]:
    """Run the window; returns the records and its start on the
    ``perf_counter`` clock. ``on_open``/``on_close`` run at its edges."""
    loop = asyncio.get_running_loop()
    loop.set_task_factory(asyncio.eager_task_factory)
    rec = Records()
    pending: set[asyncio.Task] = set()   # sent and not yet answered
    if on_open is not None:
        on_open()
    start = time.perf_counter()
    close = start + seconds

    async def closer() -> None:
        await asyncio.sleep(max(0.0, close - time.perf_counter()))
        if on_close is not None:
            on_close()

    closing = loop.create_task(closer())
    if traffic.loop == "open":
        for item, due in zip(traffic.items, traffic.due_s):
            delay = start + due - time.perf_counter()
            if delay > 0:
                await asyncio.sleep(delay)
            _start(server, make_request, rec, rec.add(item, start + due),
                   pending)
    else:
        k = 0

        async def client() -> None:
            nonlocal k
            while time.perf_counter() < close:
                item = traffic.item(k)
                k += 1
                await _start(server, make_request, rec, rec.add(item, None),
                             pending)

        clients = [loop.create_task(client()) for _ in range(traffic.clients)]
        await asyncio.wait(clients, timeout=seconds + GRACE_S)
        for c in clients:
            c.cancel()
    await closing
    await _finish(pending, close)
    loop.set_task_factory(None)
    rec.seal()
    return rec, start
