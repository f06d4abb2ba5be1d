"""The measured window: offers go straight into ``InferenceEngine.add_request``
and ``.step()`` runs in this process, on this thread (a copy of
``serving/workload.drive_arrivals`` with what it lacks: a fixed window, the
generator's lateness, a traced sub-window, a drain under a cap, and the time
of every token as the client's streaming callback sees it).

The traffic file says when the window opens (``first_offer``: at the first
offer, the system empty; ``slots_full``: once every decode slot is seated,
that ramp being set-up) and what happens to in-flight work at its close
(``drain`` under ``drain_cap_s``, a request that misses the cap failing; or
``drop``).
"""

from __future__ import annotations

import gc
import time
from contextlib import nullcontext
from dataclasses import dataclass, field
from typing import Callable, List, Optional

from benchmark.records import Served
from benchmark.traffic_gen import Offer

#: host spans the harness records around its calls into the program; the
#: trace reducer labels the device's idle gaps with them
SPAN_STEP = "bench.engine_step"
SPAN_OFFER = "bench.offer"
SPAN_IDLE = "bench.idle_sleep"


def warm_row_counts(engine, vocab: int) -> None:
    """Run the engine once through every decode row count, 1..slots: the
    program slices each dispatch's outputs to its real rows on the device
    (``ModelWrapper._slice_batch_padding``), and that slice compiles once per
    distinct row count. One short request per slot, seated one a step, does it
    in set-up; a backlog's own ramp does the same."""
    from nxdi_tpu.serving.request import SamplingParams

    slots = len(engine.scheduler.slots)
    for i in range(slots):
        engine.add_request(
            [(7 * i + j) % vocab for j in range(16)],
            SamplingParams(max_new_tokens=slots + 4, eos_token_ids=()),
        )
    while engine.has_work():
        engine.step()


@dataclass
class WindowResult:
    served: List[Served] = field(default_factory=list)
    t_loop: float = 0.0  # the loop's start: due times count from here
    t_open: float = 0.0
    t_close: float = 0.0
    t_host_end: float = 0.0  # the profiler's start in a traced run, else t_close
    tokens_in_window: int = 0
    ran_dry: bool = False
    drain_s: float = 0.0
    finished_in_window: List[Served] = field(default_factory=list)


def drive(
    engine,
    offers: List[Offer],
    traffic: dict,
    seconds: float,
    annotate: Optional[Callable[[str], object]] = None,
    tracer=None,
    on_open: Optional[Callable[[], None]] = None,
) -> WindowResult:
    """Run the cell's traffic through ``engine`` for ``seconds``.

    ``annotate(name)`` gives a context manager that writes a host span into
    the profiler's trace (``jax.profiler.TraceAnnotation``); ``tracer`` has
    ``start_after_s``, ``start()`` and ``stop()`` and is started inside the
    window, stopped after its close; ``on_open`` runs as the window opens
    (counter snapshots)."""
    from nxdi_tpu.serving.request import SamplingParams

    span = annotate or (lambda name: nullcontext())
    clock = engine.telemetry.clock
    sched = engine.scheduler
    slots = len(sched.slots)
    res = WindowResult()
    by_id = {}
    n, next_i = len(offers), 0

    def offer_due(now: float) -> None:
        nonlocal next_i
        while next_i < n and offers[next_i].due_s <= now - res.t_loop:
            o = offers[next_i]
            due = res.t_loop + o.due_s
            times: List[float] = []
            req = engine.add_request(
                o.prompt,
                SamplingParams(max_new_tokens=o.max_new, eos_token_ids=()),
                on_token=lambda _req, _tok, times=times: times.append(clock()),
                arrival_s=due,
            )
            s = Served(next_i, due, clock(), len(o.prompt), o.max_new, req, token_times=times,
                       prompt=o.prompt)
            res.served.append(s)
            by_id[req.request_id] = s
            next_i += 1

    def step() -> None:
        with span(SPAN_STEP):
            outs = engine.step()
        if outs:
            now = clock()
            for out in outs:
                s = by_id[out.request_id]
                s.output, s.finished_at = out, now

    def generated() -> int:
        return sum(len(s.request.generated) for s in res.served)

    backlog = traffic["window_opens"] == "slots_full"
    if traffic["window_opens"] == "first_offer":
        warm_row_counts(engine, vocab=256)
    elif not backlog:
        raise ValueError(f"window_opens: {traffic['window_opens']!r}")
    gc.collect()
    gc.freeze()  # set-up's objects are not the window's garbage
    res.t_loop = clock()
    if backlog:
        offer_due(res.t_loop)
        while sched.slots_busy < slots and engine.has_work():
            step()
    if on_open is not None:
        on_open()
    res.t_open = clock()
    base_tokens = generated()
    t_end = res.t_open + seconds
    res.t_host_end = t_end

    while True:
        now = clock()
        if now >= t_end:
            break
        if tracer is not None and not tracer.started and now - res.t_open >= tracer.start_after_s:
            res.t_host_end = now
            tracer.start()
            continue
        if next_i < n and offers[next_i].due_s <= now - res.t_loop:
            with span(SPAN_OFFER):
                offer_due(now)
        if not engine.has_work():
            if next_i >= n and backlog:
                break  # ran dry; flagged below
            wake = res.t_loop + offers[next_i].due_s if next_i < n else t_end
            with span(SPAN_IDLE):
                time.sleep(min(1e-3, max(0.0, wake - now)))
            continue
        step()
    res.t_close = clock()
    res.tokens_in_window = generated() - base_tokens
    res.t_host_end = min(res.t_host_end, res.t_close)
    if backlog and next_i >= n and not sched.waiting:
        res.ran_dry = True  # a backlog that emptied its queue left slots idle
    if tracer is not None and tracer.started:
        tracer.stop()
    res.finished_in_window = [
        s for s in res.served
        if s.finished_at is not None and res.t_open <= s.finished_at <= res.t_close
    ]

    if traffic["at_close"] == "drain":
        cap = res.t_close + float(traffic["drain_cap_s"])
        while engine.has_work() and clock() < cap:
            step()
        res.drain_s = clock() - res.t_close
    elif traffic["at_close"] != "drop":
        raise ValueError(f"at_close: {traffic['at_close']!r}")
    gc.unfreeze()
    return res
