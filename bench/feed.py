"""Drives the program's paged ``Server`` with a traffic mix, on the host
clock.

After each ``Server.tick()`` returns, the tokens it produced are already
on the host (every phase of a tick ends by reading its argmax back), so
the time a tick returns is the time of its tokens.  Each request keeps:

* ``due``: when it was to be sent (open loop: its scheduled arrival;
  closed loop: the moment its client's previous reply came back);
* ``admitted``: the end of the first tick after which it holds a slot;
* one time per output token.

Each tick keeps what the model step did, counted on the host from the
request sizes: the context of every decoding slot and the chunk of
every prefilling slot.  That is what the cost functions price.
"""

from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field

from . import traffic


@dataclass
class Rec:
    treq: traffic.Request
    handle: object               # the Server's Request
    client: int | None
    due: float
    submitted: float
    admitted: float | None = None
    stamps: list[float] = field(default_factory=list)
    prefilled: int = 0           # prompt tokens the server has consumed

    @property
    def prompt_len(self) -> int:
        return len(self.treq.prompt)


@dataclass
class Tick:
    t0: float
    t1: float
    decode: list[int]                       # keys attended per decoding slot
    prefill: list[tuple[int, int, bool]]    # (start, length, emits) per slot
    active: int = 0


def _null(name):
    return contextlib.nullcontext()


class Feed:
    """One run's traffic against one ``Server``.  ``annotate(name)``
    returns a context manager (a profiler annotation in traced runs)."""

    def __init__(self, server, gen: traffic.Generator, *,
                 clock=time.perf_counter, sleep=time.sleep, annotate=_null):
        self.server = server
        self.gen = gen
        self.clock = clock
        self.sleep = sleep
        self.annotate = annotate
        self.chunk = server.prefill_chunk
        self.live: list[Rec] = []
        self.done: list[Rec] = []
        self.ticks: list[Tick] = []
        self.lateness: list[float] = []     # open loop: submit - due
        self.mismatches = 0                 # host count vs tokens emitted
        self.on_done = None                 # closed loop: client callback

    # -- requests --------------------------------------------------------
    def submit(self, treq: traffic.Request, due: float,
               client: int | None = None) -> Rec:
        with self.annotate("bench.submit"):
            now = self.clock()
            h = self.server.submit(treq.prompt.tolist(), treq.max_new)
        rec = Rec(treq=treq, handle=h, client=client, due=due, submitted=now)
        self.live.append(rec)
        return rec

    def busy(self) -> bool:
        return bool(self.live)

    # -- one engine tick -----------------------------------------------
    def tick(self) -> Tick:
        srv = self.server
        queued = {id(r) for r in srv.queue}
        before = [(rec, len(rec.handle.out)) for rec in self.live]
        with self.annotate("bench.tick"):
            t0 = self.clock()
            active = srv.tick()
            t1 = self.clock()
        queued_after = {id(r) for r in srv.queue}
        tk = Tick(t0=t0, t1=t1, decode=[], prefill=[], active=active)
        finished = []
        for rec, n0 in before:
            h = rec.handle
            if id(h) in queued_after and not h.done:
                continue                    # still waiting for a slot
            if id(h) in queued:
                rec.admitted = t1
            P = rec.prompt_len
            new = len(h.out) - n0
            if rec.prefilled < P:
                n = min(self.chunk, P - rec.prefilled)
                emits = rec.prefilled + n == P
                tk.prefill.append((rec.prefilled, n, emits))
                rec.prefilled += n
                self.mismatches += new != int(emits)
            else:
                # feeds out[-1] at position P + n0 - 1: attends P + n0 keys
                tk.decode.append(P + n0)
                self.mismatches += new != 1
            rec.stamps.extend([t1] * new)
            if h.done:
                finished.append(rec)
        self.ticks.append(tk)
        for rec in finished:
            self.live.remove(rec)
            self.done.append(rec)
            if self.on_done is not None:
                self.on_done(rec, t1)
        return tk

    # -- loops -----------------------------------------------------------
    def start_closed(self, clients: int) -> None:
        """Each client sends one request and the next when its reply is
        complete.  The first requests are staggered
        (``traffic.staggered``), so the window opens on slots at
        spread-out points of their requests, as in steady state."""

        for c in range(clients):
            treq = traffic.staggered(self.gen.next(), c, clients)
            self.submit(treq, due=self.clock(), client=c)
        self.on_done = lambda rec, t: self.submit(self.gen.next(), due=t,
                                                  client=rec.client)

    def run_until(self, t_end: float, *, t_base: float | None = None,
                  hook=None) -> None:
        """Tick until ``t_end``.  With ``t_base`` (open loop) requests
        are sent when ``t_base + offset_s`` comes, and the loop sleeps
        while nothing is due or running.  ``hook(now)`` is called once
        per turn of the loop (tracing starts and stops there)."""

        nxt = self.gen.next() if t_base is not None else None
        while True:
            now = self.clock()
            if now >= t_end:
                break
            if hook is not None:
                hook(now)
            if nxt is not None:
                while t_base + nxt.offset_s <= now:
                    self.submit(nxt, due=t_base + nxt.offset_s)
                    self.lateness.append(self.clock()
                                         - (t_base + nxt.offset_s))
                    nxt = self.gen.next()
            if self.busy():
                self.tick()
            elif nxt is not None:
                wake = min(t_base + nxt.offset_s, t_end)
                with self.annotate("bench.idle"):
                    while self.clock() < wake:
                        self.sleep(min(0.0005, max(0.0, wake - self.clock())))
            else:
                break
        self.pending = nxt
