"""Load ``bulk``: whole document logs POSTed to fresh documents, as a
replica that takes over documents after a partition sends them.

Parameters: ``loop`` ``closed`` (``clients`` clients, each sending its
next log when the last is acked) or ``open`` (one log every
``interval_s``, each timed from when it was due); ``distinct_logs``
made in set-up and sent in turn; ``check_share``: the first document,
and each later one with this chance drawn from the seed, is kept for
the check; the others are dropped once acked, so that the server holds
the same state from the first request of the window to the last;
``warm_widths``: the cross-document group widths set-up drives, each
staged with the scheduler paused so that its round launches exactly
that many documents.
"""
from __future__ import annotations

import json
import random
import threading
import time

from benchmark.traffic.generator import TIMEOUT_S, Client, Req, expect


class Load:
    def __init__(self, traffic, params: dict):
        self.traffic = traffic
        self.p = params
        self.logs_needed = params["distinct_logs"]
        self.n_sent = 0
        self.keep_rng = None

    def prepare(self, server, mark) -> None:
        self.keep_rng = random.Random(self.traffic.rng.random())

    def warm(self, server, mark) -> None:
        for k, width in enumerate(self.p.get("warm_widths", ())):
            self._warm_group(server, width, k)
            mark(f"width{width}")

    def _warm_group(self, server, width: int, k: int) -> None:
        """One round in which exactly ``width`` fresh documents are
        merged together (the scheduler is held until all are queued)."""
        logs = self.traffic.logs
        docs = [f"w{k}-{i}" for i in range(width)]
        errors: list = []

        def post(i, doc):
            st, out = Client(server.port).request(
                "POST", f"/docs/{doc}/ops", logs[i % len(logs)].body)
            if st != 200:
                errors.append(f"{doc}: {st} {out[:200]!r}")

        server.pause()
        try:
            threads = [threading.Thread(target=post, args=(i, d))
                       for i, d in enumerate(docs)]
            for t in threads:
                t.start()
            server.wait_queued(docs, TIMEOUT_S)
        finally:
            server.resume()
        for t in threads:
            t.join()
        expect(not errors, f"warm-up width {width}: {errors[:2]}")
        for d in docs:
            server.drop(d)

    def threads(self, server, t0: float, stop: float) -> list:
        if self.p["loop"] == "closed":
            return [threading.Thread(target=self._client,
                                     args=(server, stop))
                    for _ in range(self.p["clients"])]
        return [threading.Thread(target=self._schedule,
                                 args=(server, t0, stop))]

    def _next(self) -> tuple:
        with self.traffic.lock:
            n = self.n_sent
            self.n_sent += 1
            keep = n == 0 or self.keep_rng.random() < self.p["check_share"]
        return n, keep

    def _send(self, server, c: Client, t_due: float) -> None:
        n, keep = self._next()
        li = n % len(self.traffic.logs)
        log = self.traffic.logs[li]
        doc = f"b{n}"
        r = Req("bulk", t_due, time.perf_counter(), doc=doc)
        try:
            st, out = c.request("POST", f"/docs/{doc}/ops", log.body)
            r.status = st
            if st == 200:
                got = json.loads(out).get("applied_count")
                if got != log.n_ops:
                    r.error = f"applied_count {got}"
                r.n_ops = log.n_ops
            else:
                r.error = out[:200].decode(errors="replace")
        except Exception as e:   # noqa: BLE001 — counted as failed
            r.error = repr(e)
        r.t_done = time.perf_counter()
        self.traffic.record(r)
        if keep and r.ok:
            self.traffic.check(doc, li)
        else:
            server.drop(doc)

    def _client(self, server, stop: float) -> None:
        c = Client(server.port)
        while time.perf_counter() < stop:
            self._send(server, c, time.perf_counter())
        c.close()

    def _schedule(self, server, t0: float, stop: float) -> None:
        interval = self.p["interval_s"]
        senders = []
        k = 0
        while True:
            due = t0 + k * interval
            if due >= stop:
                break
            delay = due - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            self.traffic.lateness_ms.append(
                (time.perf_counter() - due) * 1e3)
            t = threading.Thread(target=lambda d=due: self._send(
                server, Client(server.port), d))
            t.start()
            senders.append(t)
            k += 1
        for t in senders:
            t.join()
