"""REST traffic: the one generator every REST mix is data for.

A traffic file (``benchmark/traffic/<name>.json``) gives::

    {"generator": "rest", "workers": 4, "connections": 64,
     "rows_per_request": 1, "warm_requests_per_connection": 4,
     "arrivals": {"kind": "poisson", "rate_per_s": 12000}}   # open loop
     "arrivals": {"kind": "closed"}                          # closed loop

``workers`` subprocesses (``rest_worker.py``; no JAX, so the parent may
hold the chip) each open ``connections / workers`` keep-alive connections
and send their share of the seeded table. The parent only starts them,
hands them one common start instant and gathers what they wrote.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np

from benchmark.harness.core import Outcome

WORKER = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "rest_worker.py")


class Generator:
    def __init__(self, traffic: dict, seed: int, root: str, workdir: str,
                 handles: dict, table_rows: int):
        self.traffic = traffic
        self.n_workers = int(traffic["workers"])
        if int(traffic["connections"]) % self.n_workers:
            raise ValueError("connections must divide among the workers")
        self.rows_per_request = int(traffic["rows_per_request"])
        self.outs = [os.path.join(workdir, f"rest_worker_{w}.npz")
                     for w in range(self.n_workers)]
        self.args = [{
            "root": root, "host": handles["host"], "port": handles["port"],
            "path": handles["path"], "seed": int(seed), "worker": w,
            "workers": self.n_workers, "table_rows": int(table_rows),
            "rows_per_request": self.rows_per_request,
            "connections": int(traffic["connections"]) // self.n_workers,
            "arrivals": traffic["arrivals"], "out": self.outs[w],
        } for w in range(self.n_workers)]
        self.procs: list[subprocess.Popen] = []

    def _expect(self, word: str, timeout_s: float) -> list[str]:
        """One line starting with ``word`` from every worker. A worker
        that hangs is cut off by the watchdog, which ends it: its pipe
        then reads as closed."""
        watchdog = threading.Timer(timeout_s, self._kill)
        watchdog.daemon = True
        watchdog.start()
        try:
            lines = []
            for p in self.procs:
                line = p.stdout.readline()
                while line and not line.startswith(word):
                    line = p.stdout.readline()
                if not line:
                    raise RuntimeError(
                        f"REST worker gave no {word} (exit {p.poll()})")
                lines.append(line)
            return lines
        finally:
            watchdog.cancel()

    def _kill(self) -> None:
        for p in self.procs:
            if p.poll() is None:
                p.kill()

    def _tell(self, line: str) -> None:
        for p in self.procs:
            p.stdin.write(line + "\n")
            p.stdin.flush()

    def prepare(self) -> None:
        self.procs = [
            subprocess.Popen([sys.executable, WORKER, json.dumps(a)],
                             stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                             text=True)
            for a in self.args]
        self._expect("READY", 120.0)

    def warm(self) -> None:
        self._tell(f"WARM {int(self.traffic['warm_requests_per_connection'])}")
        self._expect("WARMED", 120.0)

    def run(self, seconds: float) -> Outcome:
        t0 = time.perf_counter() + 0.25
        self._tell(f"GO {t0!r} {float(seconds)!r}")
        self._expect("DONE", seconds + 60.0)
        parts = [np.load(path) for path in self.outs]
        cat = lambda key: np.concatenate([p[key] for p in parts])  # noqa: E731
        due, sent, done, ok = cat("due"), cat("sent"), cat("done"), cat("ok")
        k = self.rows_per_request
        latency_ms = np.where(ok, (done - due) * 1e3, np.inf)
        rows = (cat("first_row")[ok][:, None] + np.arange(k)[None, :])
        in_window = ok & (done < t0 + seconds)
        return Outcome(
            t0=t0, seconds=float(seconds), latency_ms=latency_ms,
            attempted=len(due), failed=int((~ok).sum()),
            rows_in_window=int(in_window.sum()) * k,
            served_rows=rows.reshape(-1),
            served_proba=cat("proba")[ok].reshape(-1),
            late_ms=(sent - due) * 1e3,
            statuses={int(s): int(n) for s, n in zip(
                *np.unique(cat("status"), return_counts=True))})

    def close(self) -> None:
        self._kill()
        for p in self.procs:
            p.wait()
            for pipe in (p.stdin, p.stdout):
                if pipe is not None:
                    pipe.close()
        for path in self.outs:
            if os.path.exists(path):
                os.remove(path)
