"""The one traffic generator.  A traffic mix is a JSON file of
parameters beside this module (``<mix>.json``); this code reads it and
drives the server with it.  Everything else is found by name, each in a
file of its own, so that a new shape or load is a new file:

- the document shape: the configuration's ``shape`` names
  ``shapes/<shape>.py``, whose ``make_log(config, rng)`` returns one
  document log (:class:`workloads.DocLog`) made from the seed;
- the loads: every key of the mix but ``about`` names
  ``loads/<key>.py``, whose ``Load(traffic, params)`` drives that part
  of the mix with the key's parameters (``loads/bulk.py``: whole logs
  POSTed to fresh documents; ``loads/sessions.py``: editor sessions).

A load has ``logs_needed`` (how many distinct logs it sends), and
``prepare`` (documents it loads and draws it makes before the warm-up),
``warm`` (the shapes it drives before the window) and ``threads`` (its
clients in the window).  It records every request in ``Traffic.reqs``
and names the documents the check reads back in ``Traffic.checked``.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import random
import threading
import time
from http.client import HTTPConnection
from typing import Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
TIMEOUT_S = 600.0


def load_mix(name: str) -> dict:
    with open(os.path.join(HERE, f"{name}.json")) as f:
        return json.load(f)


def find_module(kind: str, name: str):
    """``traffic/<kind>/<name>.py``, loaded by name."""
    path = os.path.join(HERE, kind, f"{name}.py")
    if not os.path.exists(path):
        raise ValueError(f"no {kind} module {name!r} under traffic/{kind}/")
    mod_name = f"benchmark.traffic.{kind}." + "".join(
        ch if ch.isalnum() else "_" for ch in name)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Req:
    kind: str                 # "bulk" | "delta" | "read"
    t_due: float
    t_sent: float
    t_done: float = 0.0
    status: int = 0
    n_ops: int = 0
    doc: str = ""
    error: str = ""
    ops: Optional[list] = None       # delta ops (kept for the check)
    ryw: bool = True                 # read: holds the session's add

    @property
    def ok(self) -> bool:
        return self.status == 200 and not self.error


class Client:
    """One keep-alive HTTP connection."""

    def __init__(self, port: int):
        self.port = port
        self.conn: Optional[HTTPConnection] = None

    def request(self, method: str, path: str, body=None):
        if self.conn is None:
            self.conn = HTTPConnection("127.0.0.1", self.port,
                                       timeout=TIMEOUT_S)
        try:
            self.conn.request(method, path, body=body)
            resp = self.conn.getresponse()
            return resp.status, resp.read()
        except Exception:
            self.close()
            raise

    def close(self) -> None:
        if self.conn is not None:
            self.conn.close()
            self.conn = None


class Traffic:
    """A mix bound to a configuration and a seed: the logs, the loads,
    and what they recorded."""

    def __init__(self, mix: dict, config: dict, seed: int):
        self.mix = mix
        self.config = config
        self.seed = seed
        self.rng = random.Random(seed)
        self.loads = [find_module("loads", key).Load(self, params)
                      for key, params in mix.items() if key != "about"]
        shape = find_module("shapes", config["shape"])
        self.logs = [shape.make_log(config, random.Random(self.rng.random()))
                     for _ in range(max(ld.logs_needed for ld in self.loads))]
        self.reqs: List[Req] = []
        self.checked: Dict[str, int] = {}     # doc -> log index
        self.lateness_ms: List[float] = []
        self.lock = threading.Lock()

    def setup(self, server, mark) -> None:
        """Before the window: every load's preparation, then every
        load's warm-up.  ``mark(name)`` notes the end of each step."""
        for ld in self.loads:
            ld.prepare(server, mark)
        for ld in self.loads:
            ld.warm(server, mark)

    def run(self, server, seconds: float) -> tuple:
        """Drive the mix: new requests start until ``seconds`` have
        passed; then every request in flight completes.  Returns the
        window's (start, end) on ``time.perf_counter``."""
        t0 = time.perf_counter()
        stop = t0 + seconds
        threads = [t for ld in self.loads for t in ld.threads(server, t0,
                                                                stop)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        end = max([r.t_done for r in self.reqs] + [stop])
        return t0, end

    def record(self, r: Req) -> None:
        with self.lock:
            self.reqs.append(r)

    def check(self, doc: str, li: int) -> None:
        """The check reads ``doc`` back and compares it with log ``li``
        (plus the deltas acked to it)."""
        with self.lock:
            self.checked[doc] = li


def expect(cond: bool, what: str) -> None:
    if not cond:
        raise RuntimeError(what)
