"""Load ``sessions``: ``docs`` documents loaded in set-up (one log
each), ``per_doc`` editor sessions on each, each with its own replica
id, typing deltas of ``delta_min``..``delta_max`` ops into a branch of
its document (backspaces at ``backspace_p``), then reading
``GET /docs/{id}/ops?since=`` from its last add, then thinking
``think_ms``.  ``warm_rounds`` rounds of every session run in set-up.
"""
from __future__ import annotations

import json
import random
import threading
import time

from benchmark.traffic import wire, workloads
from benchmark.traffic.generator import Client, Req, expect


class Load:
    def __init__(self, traffic, params: dict):
        self.traffic = traffic
        self.p = params
        self.logs_needed = params["docs"]
        self.sessions: list = []

    def prepare(self, server, mark) -> None:
        tr = self.traffic
        c = Client(server.port)
        for d in range(self.p["docs"]):
            doc = f"s{d}"
            st, out = c.request("POST", f"/docs/{doc}/ops", tr.logs[d].body)
            expect(st == 200, f"loading {doc}: {st} {out[:200]!r}")
            tr.check(doc, d)
        for d in range(self.p["docs"]):
            for _ in range(self.p["per_doc"]):
                doc = f"s{d}"
                st, out = c.request("POST", f"/docs/{doc}/replicas")
                expect(st == 200, f"replica for {doc}: {st}")
                s = workloads.EditorSession(
                    json.loads(out)["replica"],
                    tr.rng.choice(tr.logs[d].branch_paths),
                    self.p["backspace_p"], random.Random(tr.rng.random()),
                    tag=len(self.sessions) + 1)
                s.doc = doc
                self.sessions.append(s)
        c.close()
        mark("load")

    def warm(self, server, mark) -> None:
        for _ in range(self.p.get("warm_rounds", 0)):
            threads = [threading.Thread(target=self._step,
                                        args=(s, Client(server.port),
                                              False))
                       for s in self.sessions]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        mark("sessions")

    def threads(self, server, t0: float, stop: float) -> list:
        return [threading.Thread(target=self._loop, args=(server, s, stop))
                for s in self.sessions]

    def _loop(self, server, s, stop: float) -> None:
        c = Client(server.port)
        while time.perf_counter() < stop:
            self._step(s, c, True)
        c.close()

    def _step(self, s, c: Client, think: bool) -> None:
        p = self.p
        ops = s.delta(s.rng.randint(p["delta_min"], p["delta_max"]))
        r = Req("delta", time.perf_counter(), time.perf_counter(),
                doc=s.doc, ops=ops)
        try:
            st, out = c.request("POST", f"/docs/{s.doc}/ops",
                                wire.dumps_ops(ops))
            r.status = st
            if st != 200:
                r.error = out[:200].decode(errors="replace")
            r.n_ops = len(ops)
        except Exception as e:   # noqa: BLE001 — counted as failed
            r.error = repr(e)
        r.t_done = time.perf_counter()
        if not r.ok:
            s.undo(ops)
        self.traffic.record(r)
        if s.last_add_ts:
            since = s.last_add_ts
            q = Req("read", time.perf_counter(), time.perf_counter(),
                    doc=s.doc)
            try:
                st, out = c.request("GET",
                                    f"/docs/{s.doc}/ops?since={since}")
                q.status = st
                q.ryw = f'"ts":{since},'.encode() in out
            except Exception as e:   # noqa: BLE001 — counted as failed
                q.error = repr(e)
            q.t_done = time.perf_counter()
            self.traffic.record(q)
        if think:
            time.sleep(p["think_ms"] / 1e3)
