"""Load generation: closed and open loops over `service.verify`.

One process, one asyncio loop: the clients are coroutines on the loop the
service's collector runs on, as co-located Handel nodes are. A request is
timed from the client's side — issued (open loop: DUE) to verdicts returned.

Both loops run a RAMP first (counted as set-up: it fills the pipeline and
warms the service's tasks), then the WINDOW [t0, t1]; requests issued in the
window are the latency sample, and those still in flight at t1 are awaited
(and counted) but nothing new is sent after t1.
"""

from __future__ import annotations

import asyncio
import time
from array import array
from dataclasses import dataclass, field

clock = time.perf_counter


@dataclass
class Record:
    req: int          # index into the pool
    due: float        # closed: == sent
    sent: float
    done: float
    verdicts: list | None  # None: the call raised
    error: str = ""


class RecordLog:
    """What the loops write a finished request into: arrays of numbers, which
    the interpreter's cyclic collector does not track. A list of `Record`s
    with their verdict lists grows by three tracked objects a request, and
    every full collection in the window walks all of them (76 000 requests
    in 30 s of the BLS12-381 cell) while it holds the interpreter lock.
    `records()` makes the `Record`s once the loops have ended."""

    def __init__(self):
        self.req, self.n = array("l"), array("l")  # n: verdicts; -1: raised
        self.due, self.sent, self.done = array("d"), array("d"), array("d")
        self.bits = bytearray()  # the verdicts, a byte each, request by request
        self.errors: dict[int, str] = {}

    def add(self, req, due, sent, done, verdicts, error="") -> None:
        if verdicts is None:
            self.errors[len(self.req)] = error
            self.n.append(-1)
        else:
            self.n.append(len(verdicts))
            self.bits.extend(map(bool, verdicts))
        self.req.append(req)
        self.due.append(due)
        self.sent.append(sent)
        self.done.append(done)

    def records(self) -> list[Record]:
        out, at = [], 0
        for i, n in enumerate(self.n):
            verdicts = None
            if n >= 0:
                verdicts = [bool(b) for b in self.bits[at:at + n]]
                at += n
            out.append(Record(self.req[i], self.due[i], self.sent[i],
                              self.done[i], verdicts, self.errors.get(i, "")))
        return out


@dataclass
class LoadResult:
    log: RecordLog = field(default_factory=RecordLog)
    records: list[Record] = field(default_factory=list)  # from `log`, at the end
    t0: float = 0.0   # window start (perf_counter)
    t1: float = 0.0   # window end
    t0_epoch: float = 0.0  # same instants on time.time, for the spans
    t1_epoch: float = 0.0
    marks: dict = field(default_factory=dict)  # what on_edge returned at t0/t1

    def in_window(self) -> list[Record]:
        """The latency sample: requests issued (due) inside the window."""
        return [r for r in self.records if self.t0 <= r.due < self.t1]


async def _call(service, msg, pubkeys, pool_reqs, idx, session, scope, due,
                out: RecordLog) -> None:
    sent = clock()
    try:
        verdicts = await service.verify(
            msg, pubkeys, pool_reqs[idx], session=session, dedup_scope=scope
        )
        out.add(idx, due, sent, clock(), verdicts)
    except Exception as e:  # a refused or failed verify is a FAILED request
        out.add(idx, due, sent, clock(), None, f"{type(e).__name__}: {e}")


async def _marker(res: LoadResult, ramp_s: float, seconds: float, on_edge,
                  timed=()) -> None:
    """Stamp the window's two edges, calling `on_edge("t0"|"t1")` on the loop
    at each (counter snapshots), and run each `(offset_s, fn)` of `timed` in
    a thread at t0 + offset (the profiler's start and stop)."""
    loop = asyncio.get_running_loop()
    await asyncio.sleep(ramp_s)
    res.t0, res.t0_epoch = clock(), time.time()
    res.marks["t0"] = on_edge("t0")
    res.t1 = res.t0 + seconds
    pending = []
    for offset, fn in sorted(timed, key=lambda h: h[0]):
        await asyncio.sleep(max(0.0, res.t0 + min(offset, seconds) - clock()))
        if offset >= seconds:
            break
        pending.append(loop.run_in_executor(None, fn))
    await asyncio.sleep(max(0.0, res.t1 - clock()))
    res.t1, res.t1_epoch = clock(), time.time()
    res.marks["t1"] = on_edge("t1")
    for offset, fn in timed:
        if offset >= seconds:  # hooks due at or after the window's end
            pending.append(loop.run_in_executor(None, fn))
    await asyncio.gather(*pending)


async def closed_loop(service, msg, pubkeys, pool_reqs, starts, scope_of,
                      ramp_s, seconds, on_edge, timed=()) -> LoadResult:
    """`len(starts)` clients, each one session, each sending its next pool
    request when the last returned."""
    res = LoadResult()
    res.t1 = float("inf")
    n = len(pool_reqs)

    async def client(i: int, start: int):
        session, j = f"c{i}", 0
        while clock() < res.t1:
            idx = (start + j) % n
            await _call(service, msg, pubkeys, pool_reqs, idx, session,
                        scope_of(session, j), clock(), res.log)
            j += 1

    mark = asyncio.ensure_future(_marker(res, ramp_s, seconds, on_edge, timed))
    await asyncio.gather(*(client(i, s) for i, s in enumerate(starts)))
    await mark
    res.records = res.log.records()
    return res


async def open_loop(service, msg, pubkeys, pool_reqs, offsets, sessions,
                    scope_of, ramp_s, seconds, on_edge, timed=()) -> LoadResult:
    """Requests sent on the seeded clock `offsets` (seconds from the ramp's
    start) whether or not the service keeps up; request k belongs to session
    k mod `sessions` and is timed from when it was DUE."""
    res = LoadResult()
    n = len(pool_reqs)
    start = clock()
    mark = asyncio.ensure_future(_marker(res, ramp_s, seconds, on_edge, timed))
    tasks = []
    for k, off in enumerate(offsets):
        due = start + off
        wait = due - clock()
        if wait > 0:
            await asyncio.sleep(wait)
        session = f"c{k % sessions}"
        tasks.append(asyncio.ensure_future(_call(
            service, msg, pubkeys, pool_reqs, k % n, session,
            scope_of(session, k), due, res.log,
        )))
    await mark
    await asyncio.gather(*tasks)
    res.records = res.log.records()
    return res
