"""Offer a traffic mix to the engine and record what came back.

Every session goes in through ``EngineManager.submit`` with its prompts as
the ``reqs`` input, exactly as a client of the resident engine would send
it; its ``responses`` are read when its report resolves.  Times are the
host's monotonic clock.
"""
from __future__ import annotations

import threading
import time
from dataclasses import dataclass
from typing import Dict, List, Optional

import numpy as np

from repro.core.manager import AdmissionError

from .traffic import Mix, Session


@dataclass
class Record:
    session: Session
    due: float                        # when it was due to be sent
    submit_s: float = 0.0             # wall of EngineManager.submit
    queue_delay: Optional[float] = None
    exec_wall: Optional[float] = None
    done: Optional[float] = None      # when its responses were read
    ok: bool = False
    error: str = ""
    tokens: Optional[np.ndarray] = None   # (requests, decode_tokens)
    longest_chain: Optional[float] = None  # s; traced runs only

    @property
    def latency(self) -> float:
        """Due to answered; a miss (failed, refused, unfinished) is inf."""
        return self.done - self.due if self.ok and self.done else float("inf")


def _longest_chain(session) -> Optional[float]:
    """The longest microbatch's prefill + decode walls plus the assemble
    wall, from a session's ``Timeline`` (None when the timeline is off)."""
    tl = session.timeline
    if tl is None:
        return None
    pgt = session.pgt
    t0, t1 = tl.t_start, tl.t_end
    chain: Dict[int, float] = {}
    assemble = 0.0
    for i in range(pgt.num_drops):
        name = pgt.group_of(i).name
        if name in ("prefill", "decode"):
            (mb,) = pgt.oid_of(i)
            chain[mb] = chain.get(mb, 0.0) + float(t1[i] - t0[i])
        elif name == "assemble":
            assemble = float(t1[i] - t0[i])
    return max(chain.values()) + assemble


class Driver:
    def __init__(self, mgr, graph, mix: Mix) -> None:
        self.mgr = mgr
        self.graph = graph
        self.mix = mix
        self.records: List[Record] = []
        self._lock = threading.Lock()

    def _submit(self, rec: Record, block: bool):
        t = time.monotonic()
        try:
            ticket = self.mgr.submit(self.graph,
                                     inputs={"reqs": rec.session.prompts},
                                     timeout=3600, block=block)
        except AdmissionError as exc:
            rec.error = f"refused: {exc}"
            return None
        finally:
            rec.submit_s = time.monotonic() - t
            with self._lock:
                self.records.append(rec)
        return ticket

    def _finish(self, rec: Record, ticket) -> None:
        """Read a resolved session's responses, then free it."""
        try:
            report = ticket.future.result()
            rec.ok = bool(report.ok)
            rec.exec_wall = report.wall_time
            if rec.ok:
                rec.tokens = np.asarray(ticket.session.read("responses"))
                rec.longest_chain = _longest_chain(ticket.session)
            else:
                rec.error = "; ".join(report.errors[:2])
            rec.queue_delay = ticket.queue_delay
        except Exception as exc:  # noqa: BLE001 - a failed session is a miss
            rec.ok, rec.error = False, f"{type(exc).__name__}: {exc}"
        rec.done = time.monotonic()
        self.mgr.close_session(ticket.session_id)

    def run_one(self, session: Session) -> Record:
        """One session, submitted now, waited for."""
        rec = Record(session, time.monotonic())
        ticket = self._submit(rec, block=True)
        if ticket is not None:
            ticket.result()
            self._finish(rec, ticket)
        return rec

    def closed(self, streams, t0: float, seconds: float) -> None:
        """Each client sends its next session once the last is answered,
        until ``t0 + seconds``."""
        stop = t0 + seconds

        def client(it):
            while time.monotonic() < stop:
                self.run_one(next(it))

        threads = [threading.Thread(target=client, args=(it,),
                                    name=f"bench-client-{c}")
                   for c, it in enumerate(streams)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()

    def open(self, schedule: List[Session], t0: float,
             late_s: float) -> float:
        """Send each session at ``t0 + session.at`` whatever the state of
        the engine; wait until all are answered or ``late_s`` past the last
        one's due time.  Returns how late the sender ran at most (s)."""
        lateness = 0.0
        pending = []
        for s in schedule:
            due = t0 + s.at
            wait = due - time.monotonic()
            if wait > 0:
                time.sleep(wait)
            lateness = max(lateness, time.monotonic() - due)
            rec = Record(s, due)
            ticket = self._submit(rec, block=False)
            if ticket is None:
                rec.done = time.monotonic()
                continue
            ev = threading.Event()

            def cb(_, rec=rec, ticket=ticket, ev=ev):
                self._finish(rec, ticket)
                ev.set()
            ticket.future.add_done_callback(cb)
            pending.append(ev)
        deadline = t0 + (schedule[-1].at if schedule else 0.0) + late_s
        for ev in pending:
            ev.wait(max(0.0, deadline - time.monotonic()))
        return lateness
