"""Open-loop serving window: the classifier service as users run it.

``serve_forever()`` runs the service's dispatch thread. A generator thread
calls ``ClassifierService.submit`` at each scheduled arrival, and a
collector thread reads every future's ``result()``, in order. A request's
latency runs from its scheduled arrival to the moment the collector holds
its label. The main thread only waits, and marks the traced slice.

Every request of the plan is accounted for: done, rejected by the queue,
raised, or not done when the drain (``drain_s`` past the window's close)
ends.
"""

from __future__ import annotations

import contextlib
import dataclasses
import queue
import threading
import time
from typing import Callable, Optional

import numpy as np

DONE, REJECTED, RAISED, TIMED_OUT, PENDING = 0, 1, 2, 3, 4
MODEL = "served"


@dataclasses.dataclass
class Window:
    seconds: float
    t_sched: np.ndarray     # seconds from the window's start
    t_submit: np.ndarray
    t_done: np.ndarray      # nan unless done
    label: np.ndarray       # -1 unless done
    status: np.ndarray
    row: np.ndarray
    counters: dict          # service counters: "start", "end", and the
                            # traced slice's "slice_start", "slice_end"
    started_at: float       # time.perf_counter() at the window's start
    slice_s: Optional[tuple] = None   # traced slice, seconds from start
    errors: list = dataclasses.field(default_factory=list)

    def latency_s(self) -> np.ndarray:
        """Latency of every request; inf for one not done."""
        lat = self.t_done - self.t_sched
        return np.where(self.status == DONE, lat, np.inf)

    def done_in_window(self) -> int:
        return int(np.sum((self.status == DONE)
                          & (self.t_done <= self.seconds)))


def counters(svc) -> dict:
    """The service's own counters, with executable calls per bucket."""
    st = svc.stats()
    per_bucket: dict[int, int] = {}
    for key, calls in list(svc.bucket_cache._seen.items()):
        per_bucket[key[-1]] = per_bucket.get(key[-1], 0) + calls
    return {"admitted": st["admitted"], "cycles": st["cycles"],
            "rejected": st["rejected"], "errors": st["errors"],
            "bucket_calls": per_bucket}


def delta(a: dict, b: dict) -> dict:
    """Counters ``b`` minus counters ``a``."""
    out = {k: b[k] - a[k] for k in ("admitted", "cycles", "rejected",
                                    "errors")}
    out["bucket_calls"] = {k: v - a["bucket_calls"].get(k, 0)
                           for k, v in b["bucket_calls"].items()}
    return out


def run(svc, pool: np.ndarray, plan, seconds: float, *, drain_s: float,
        trace_slice: Optional[tuple] = None,
        on_slice: Optional[Callable[[str], None]] = None,
        span=None) -> Window:
    """Drive ``plan`` through ``svc`` (model registered as ``MODEL``).

    ``trace_slice`` = (start, end) seconds into the window: ``on_slice``
    is called with "start" and "end" there. ``span(name)`` returns the
    context manager that marks the generator's and collector's host work
    (profiler annotations in a traced run)."""
    from repro.serving.queue import QueueFullError

    span = span or (lambda name: contextlib.nullcontext())
    n = len(plan)
    t_submit = np.full(n, np.nan)
    t_done = np.full(n, np.nan)
    label = np.full(n, -1, np.int64)
    status = np.full(n, PENDING, np.int8)
    handoff: queue.SimpleQueue = queue.SimpleQueue()
    errors: list = []
    now = svc.now
    row_of = plan.row

    svc.serve_forever()
    c_start = counters(svc)
    t0 = now() + 0.005
    started_at = time.perf_counter() + (t0 - now())
    deadline = t0 + seconds + drain_s
    sched = t0 + plan.t

    def generate():
        for i in range(n):
            wait = sched[i] - now()
            if wait > 0:
                time.sleep(wait)
            with span("bench.submit"):
                try:
                    fut = svc.submit(MODEL, pool[row_of[i]],
                                     t_arrival=sched[i])
                except QueueFullError:
                    status[i] = REJECTED
                    fut = None
            t_submit[i] = now()
            handoff.put((i, fut))

    def collect():
        for _ in range(n):
            i, fut = handoff.get()
            if fut is None:
                continue
            try:
                with span("bench.result"):
                    out = fut.result(timeout=max(deadline - now(), 1e-3))
            except TimeoutError:
                status[i] = TIMED_OUT
                continue
            except Exception as exc:   # noqa: BLE001 - counted, reported
                status[i] = RAISED
                if len(errors) < 5:
                    errors.append(repr(exc))
                continue
            t_done[i] = now()
            label[i] = out
            status[i] = DONE

    threads = [threading.Thread(target=generate, name="bench-generator"),
               threading.Thread(target=collect, name="bench-collector")]
    for th in threads:
        th.start()
    marks = {}
    if trace_slice is not None:
        for what, at in zip(("start", "end"), trace_slice):
            time.sleep(max(t0 + at - now(), 0.0))
            marks[f"slice_{what}"] = counters(svc)
            if on_slice is not None:
                on_slice(what)
    for th in threads:
        th.join()
    c_end = counters(svc)
    svc.shutdown(drain=False)
    return Window(seconds=seconds, t_sched=plan.t, t_submit=t_submit - t0,
                  t_done=t_done - t0, label=label, status=status,
                  row=plan.row,
                  counters={"start": c_start, "end": c_end, **marks},
                  started_at=started_at,
                  slice_s=trace_slice, errors=errors)


class Rig:
    """One cell's service, warmed and loaded with its plan."""

    def __init__(self, cell, built, seed: int, seconds: float):
        from repro.serving import ClassifierService

        from bench.loadgen import schedule
        service = cell.config["service"]
        self.svc = ClassifierService(max_batch=int(service["max_batch"]))
        self.svc.register(MODEL, built.model(),
                          quantize_bits=service.get("quantize_bits"))
        self.svc.warmup()
        self.built = built
        self.seconds = seconds
        self.drain_s = float(cell.traffic.get("drain_s", 60.0))
        self.plan = schedule(cell.traffic, seconds, len(built.pool), seed)

    def measure(self, **kw) -> Window:
        return run(self.svc, self.built.pool, self.plan, self.seconds,
                   drain_s=self.drain_s, **kw)

    def free(self) -> None:
        """Drop the program's state (its copy of the model)."""
        self.svc = None


def prepare(cell, built, seed: int, seconds: float) -> Rig:
    return Rig(cell, built, seed, seconds)


def check(cell, built, window: Window, seed: int) -> dict:
    """Served labels against the configuration's reference."""
    from bench.correct import check_labels
    return check_labels(cell.config, cell.maker, built, window, seed)
