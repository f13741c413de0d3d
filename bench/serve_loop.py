"""Drives the program's open-loop front end on the real clock.

The served path is ``repro.serve.frontend.AsyncEngine.run`` (its asyncio
loop) over one ``Engine``: its ``ContinuousScheduler`` slot pool, the
stored ``prefill_row`` and ``decode_step`` programs and the packed TSMM
kernels under them.  This module adds no serving loop of its own.  It
plays the clients: it submits each request at its due time (open loop) or
when the client's last one finished (closed loop), and stamps every token
on the host clock as the client receives it from the request's stream.

One pool runs on one global cache clock that never rewinds (the
scheduler's ``T``, bounded by ``max_len``).  Before each submission the
harness reads the pool's clock: a request is fed only while the clock
leaves room for its output and for an eighth of a pool's decode room
(``ADMIT_WAIT``) of queueing before its admission.  When it does not,
the harness stops feeding the pool, lets it drain, and opens a new pool,
as a deployment of this code would have to; held requests keep their
due times, so the stall shows in their latencies.  The harness knows
nothing of the front end's admission policy: a request that waits
longer than that near the clock's end is truncated by the scheduler and
counts as failed.
"""

from __future__ import annotations

import asyncio
import dataclasses
import gc
import time
from collections import deque
from typing import Optional

import numpy as np

ADMIT_WAIT = 8      # a request may queue 1/ADMIT_WAIT of a pool's decode room


@dataclasses.dataclass
class Record:
    """One request as the client saw it (host clock, seconds)."""
    req: object                       # bench.traffic.Req
    due: float                        # absolute perf_counter due time
    lb: int                           # prompt-length bucket
    stream: object = None             # the front end's TokenStream
    times: list = dataclasses.field(default_factory=list)
    tokens: list = dataclasses.field(default_factory=list)
    done: Optional[float] = None
    finished: object = None           # asyncio.Event
    task: object = None               # the client's consuming task

    @property
    def ok(self) -> bool:
        """Completed with every token it asked for."""
        return (self.done is not None and self.stream is not None
                and self.stream.completed
                and len(self.tokens) == self.req.out_len)


class Server:
    """The current pool (an ``AsyncEngine``) over one ``Engine``."""

    def __init__(self, engine, mix: dict, slots: int, *, spans: bool = False):
        self.engine = engine
        self.mix = mix
        self.slots = slots
        # a pool opens with its clock at the largest prompt bucket
        self.admit_wait = ((engine.max_len - engine.grid.length[-1])
                           // ADMIT_WAIT)
        self.budget = mix["prefill_budget"]
        self.spans = spans
        self.afe = None
        self.task = None
        self.hold: deque = deque()
        self.live: list = []
        self.draining = False
        self.pools = 0
        self.retired = {"steps": 0, "slot_steps_active": 0}
        self._keeper = None

    # -- pools -------------------------------------------------------------

    def _open_pool(self) -> None:
        from repro.serve.frontend import AsyncEngine
        afe = AsyncEngine(self.engine, slots=self.slots,
                          queue_limit=self.mix["queue_limit"],
                          prefill_budget=self.budget)
        if self.spans:
            _annotate(afe.sched)
        afe.open()
        self.afe = afe
        self.task = asyncio.ensure_future(afe.run())
        self.pools += 1

    async def _close_pool(self, abort: bool = False) -> None:
        afe, task = self.afe, self.task
        self.afe = self.task = None
        if abort:
            task.cancel()
        else:
            afe.request_stop()
        try:
            await task
        except asyncio.CancelledError:
            if not abort:
                raise
        self.retired["steps"] += afe.stats.steps
        self.retired["slot_steps_active"] += afe.stats.slot_steps_active
        # the pool's cache must be gone before the next one is allocated
        del afe, task
        gc.collect()

    async def start(self) -> None:
        self._open_pool()
        self._keeper = asyncio.ensure_future(self._keep())

    async def stop(self, abort: bool = False) -> None:
        if self._keeper is not None:
            self._keeper.cancel()
            try:
                await self._keeper
            except asyncio.CancelledError:
                pass
            self._keeper = None
        if self.afe is not None:
            await self._close_pool(abort=abort)
        for rec in list(self.live):
            rec.task.cancel()
            if rec.done is None:
                rec.done = time.perf_counter()
            rec.finished.set()
        self.live.clear()

    async def _keep(self) -> None:
        """Reopen a drained pool and feed it what was held."""
        while True:
            if self.draining and not self.live:
                await self._close_pool()
                self._open_pool()
                self.draining = False
                self._feed()
            await asyncio.sleep(0.001)

    def counters(self) -> dict:
        """Decode steps and live rows summed over every pool so far."""
        out = dict(self.retired)
        if self.afe is not None and self.afe.stats is not None:
            out["steps"] += self.afe.stats.steps
            out["slot_steps_active"] += self.afe.stats.slot_steps_active
        return out

    # -- feeding -----------------------------------------------------------

    def _fits(self, rec: Record) -> bool:
        """The pool's clock leaves room for ``rec``'s output after at
        most ``self.admit_wait`` decode steps in the front end's queue."""
        return (self.afe.sched.T + self.admit_wait + rec.req.out_len
                <= self.engine.max_len)

    def offer(self, rec: Record) -> None:
        rec.finished = asyncio.Event()
        self.hold.append(rec)
        self._feed()

    def _feed(self) -> None:
        while (self.hold and self.afe is not None and not self.draining):
            if not self._fits(self.hold[0]):
                self.draining = True
                break
            self._submit(self.hold.popleft())

    def _submit(self, rec: Record) -> None:
        from repro.serve.scheduler import Request
        r = rec.req
        req = Request(tokens=r.prompt, max_new_tokens=r.out_len,
                      rid=r.index, arrival_time=rec.due)
        rec.stream = self.afe.submit_nowait(req)
        self.live.append(rec)
        rec.task = asyncio.ensure_future(self._consume(rec))

    async def _consume(self, rec: Record) -> None:
        async for tok in rec.stream:
            rec.times.append(time.perf_counter())
            rec.tokens.append(int(tok))
        rec.done = time.perf_counter()
        if rec in self.live:
            self.live.remove(rec)
        rec.finished.set()


def _annotate(sched) -> None:
    """Host spans around the scheduler's two operations, for the trace."""
    import jax

    step, admit = sched.step, sched.admit

    def traced_step(*a, **k):
        with jax.profiler.TraceAnnotation("bench.decode_step"):
            return step(*a, **k)

    def traced_admit(req, toks=None, lb=None, **k):
        with jax.profiler.TraceAnnotation(f"bench.prefill_row.{lb}"):
            return admit(req, toks, lb, **k)

    sched.step = traced_step
    sched.admit = traced_admit


async def sleep_until(t: float) -> None:
    dt = t - time.perf_counter()
    if dt > 0:
        await asyncio.sleep(dt)


def make_record(engine, req, due: float) -> Record:
    return Record(req=req, due=due,
                  lb=engine.grid.length_bucket(len(req.prompt)))


async def run_open(server: Server, reqs: list, t0: float,
                   drain_s: float) -> list:
    """Submit each request at ``t0 + due``; then wait until every one has
    finished, at most ``drain_s`` past the last due time."""
    records = []
    for r in reqs:
        await sleep_until(t0 + r.due)
        rec = make_record(server.engine, r, t0 + r.due)
        records.append(rec)
        server.offer(rec)
    deadline = time.perf_counter() + drain_s
    for rec in records:
        left = deadline - time.perf_counter()
        if left <= 0:
            break
        try:
            await asyncio.wait_for(rec.finished.wait(), left)
        except asyncio.TimeoutError:
            break
    return records


async def run_closed(server: Server, reqs, clients: int, t0: float,
                     seconds: float) -> list:
    """``clients`` callers, each submitting its next request (from the
    shared iterator ``reqs``) once its last one has finished, from ``t0``
    for ``seconds``."""
    records = []
    stop = t0 + seconds

    async def client():
        while time.perf_counter() < stop:
            rec = make_record(server.engine, next(reqs), time.perf_counter())
            records.append(rec)
            server.offer(rec)
            await rec.finished.wait()

    await sleep_until(t0)
    tasks = [asyncio.ensure_future(client()) for _ in range(clients)]
    await sleep_until(stop)
    for t in tasks:
        t.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)
    return records


def percentile(values, q: float) -> Optional[float]:
    """Linear-interpolated percentile (numpy's default), None if empty."""
    if not len(values):
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))
