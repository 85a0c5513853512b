#!/usr/bin/env python
"""What the host's own clocks (ISSUE 51) cost where they run, with no profiler
session on. No device is touched: run it on the machine whose host you want
to know (`chiprun -- python scripts/bench_host_clocks.py`, half a minute).

1. The collector's callback: `gc.collect(0)` in a loop with `obs._on_gc` in
   `gc.callbacks` and without it, in microseconds a collection.
2. `GenEngine._stamp` at a phase boundary: the method as it is against its
   body as it was before ISSUE 51 (one clock, one counter), on a stand-in
   that has the fields it touches, in microseconds a call.
3. One publication (`HostClocks.publish`: the collector's sums and a walk of
   `/proc/self/task`) with a few threads and with 200 parked ones, in
   microseconds.
4. The request trees before and after ISSUE 52 (`trees`): a span a riding
   lane a step against one record a step and one span a request, and
   `gc.collect(2)` over the start-up heap and over the 196,608 spans that 384
   requests of 512 tokens kept alive, with the heap walked and frozen (jax
   and the engine are imported: a serving process's heap is a few times it).

Prints one JSON line.
"""

from __future__ import annotations

import gc
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tpuserve import obs  # noqa: E402
from tpuserve.genserve.engine import (LOOP_PHASES, GenEngine,  # noqa: E402
                                      _StepRecord)


def per_call_us(fn, n: int) -> float:
    fn()
    t0 = time.perf_counter()
    for _ in range(n):
        fn()
    return (time.perf_counter() - t0) * 1e6 / n


def best(fn, n: int, rounds: int = 5) -> float:
    return min(per_call_us(fn, n) for _ in range(rounds))


def collector() -> dict:
    def collect():
        gc.collect(0)

    had = obs._on_gc in gc.callbacks
    if had:
        gc.callbacks.remove(obs._on_gc)
    without = best(collect, 20_000)
    gc.callbacks.append(obs._on_gc)
    with_cb = best(collect, 20_000)
    if not had:
        gc.callbacks.remove(obs._on_gc)
    return {"gc_collect0_us": without, "gc_collect0_with_callback_us": with_cb,
            "callback_us": with_cb - without}


class StandIn:
    """The fields `_stamp` touches, on real counters."""

    def __init__(self) -> None:
        m = obs.Metrics()
        self.name, self._iter = "model", 0
        self._phase, self._phase_t, self._phase_cpu = "sweep", time.perf_counter(), time.thread_time()
        self._c_loop = {p: m.counter(f"gen_loop_seconds_total{{model=model,phase={p}}}")
                        for p in LOOP_PHASES}
        self._c_loop_cpu = {p: m.counter(f"gen_loop_cpu_seconds_total{{model=model,phase={p}}}")
                            for p in LOOP_PHASES}


def stamp_before(self, phase: str) -> float:
    """`GenEngine._stamp` as the parent of ISSUE 51 has it."""
    now = time.perf_counter()
    if phase != self._phase:
        self._c_loop[self._phase].inc(now - self._phase_t)
        obs.trace_mark("tpuserve.gen_loop", self._phase_t, now,
                       model=self.name, phase=self._phase, iter=self._iter)
        self._phase, self._phase_t = phase, now
    return now


def stamp() -> dict:
    obs._trace_annotation()  # as a serving process has it
    s = StandIn()

    def alternate(fn):
        def go():
            fn(s, "account")
            fn(s, "sweep")
        return go

    before = best(alternate(stamp_before), 100_000) / 2
    after = best(alternate(GenEngine._stamp), 100_000) / 2
    return {"stamp_before_us": before, "stamp_after_us": after, "thread_time_us":
            best(time.thread_time, 200_000)}


def publication() -> dict:
    clocks = obs.HostClocks(obs.Metrics())
    few = len(os.listdir("/proc/self/task"))
    out = {"threads_few": few, "publish_few_us": best(clocks.publish, 200)}
    stop = threading.Event()
    parked = [threading.Thread(target=stop.wait, daemon=True) for _ in range(200)]
    for t in parked:
        t.start()
    out.update(threads_many=len(os.listdir("/proc/self/task")),
               publish_many_us=best(clocks.publish, 50))
    stop.set()
    for t in parked:
        t.join()
    clocks.close()
    return out


def trees() -> dict:
    """The request trees before and after ISSUE 52, at 384 lanes: a span a
    riding lane a step (written out here: the program has no such caller
    left) against one record a step and one `gen_steps` span a request; and a
    full collection over what each keeps alive for 384 requests of 512
    tokens, the start-up heap walked with it and frozen."""
    lanes, tokens = 384, 512
    ctxs = [obs.TraceContext() for _ in range(lanes)]
    wall = time.time()

    def a_step_before():
        for s, ctx in enumerate(ctxs):
            ctx.span("gen_step", wall - 0.02, wall, tid="model", slot=s, iteration=7)

    record = _StepRecord()
    seq = [0]

    def a_step():
        record.put(seq[0], wall, 0.02)
        seq[0] += 1

    def a_retirement():
        start, end, args = record.ridden(seq[0] - tokens, tokens)
        ctxs[0].span("gen_steps", start, end, tid="model", slot=0, steps=tokens, **args)

    def full_ms() -> float:
        t0 = time.perf_counter()
        gc.collect(2)
        return (time.perf_counter() - t0) * 1e3

    out = {"trees_step_384_lanes_before_us": best(a_step_before, 20, rounds=3),
           "trees_step_record_us": best(a_step, 20_000)}
    for _ in range(tokens):
        a_step()
    out["trees_retirement_us"] = best(a_retirement, 2_000)
    for ctx in ctxs:
        del ctx.spans[:]
    gc.collect()
    out["gc_collect2_start_up_heap_ms"] = full_ms()
    for _ in range(tokens):
        a_step_before()
    out.update(spans_alive_before=lanes * tokens, gc_collect2_over_them_ms=full_ms())
    for ctx in ctxs:
        del ctx.spans[:]
    gc.collect()
    gc.freeze()
    out.update(frozen_objects=gc.get_freeze_count(), gc_collect2_frozen_ms=full_ms())
    for _ in range(tokens):
        a_step_before()
    out["gc_collect2_over_them_frozen_ms"] = full_ms()
    gc.unfreeze()
    return out


def main() -> int:
    out = {"python": sys.version.split()[0], "cpus": os.cpu_count(), **collector(), **stamp(),
           **publication(), **trees()}
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
