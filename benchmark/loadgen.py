"""The benchmark's load generator: closed and open loop over HTTP, from one
asyncio loop in the process that started it. It never touches JAX.

Copied in method from `tpuserve/bench/loadgen.py` and corrected:

- open loop times a request from when it was DUE, not from when it was sent,
  so a stall is charged to every request it delayed;
- arrivals are the due times it is given (seeded gaps), not a fixed interval;
- it reports how late it ran (send time against due time);
- a request still out when the window and the drain allowance have ended is
  attempted and failed, not set aside.

Accounting. The window is [t0, t0 + seconds). A request is ATTEMPTED if it
was due inside the window; it is followed to its end or to the end of the
drain allowance. Latency is taken over every attempted request that was
answered correctly. `items_in_window` counts the items of correct answers
that ARRIVED inside the window, whenever they were sent, so the rate is all
the work over all the time of the window.
"""

from __future__ import annotations

import asyncio
import json
import time
from dataclasses import dataclass, field

CONTENT_TYPE = {"Content-Type": "application/json"}


@dataclass
class LoadResult:
    seconds: float
    attempted: int = 0
    failed: int = 0
    items_in_window: int = 0
    latencies_by_class: dict[str, list[float]] = field(default_factory=dict)
    late_ms: list[float] = field(default_factory=list)
    errors: dict[str, int] = field(default_factory=dict)
    wrapped: bool = False  # the request pool ran out and was reused

    @property
    def latencies_ms(self) -> list[float]:
        """Every attempted request that was answered correctly."""
        return [ms for v in self.latencies_by_class.values() for ms in v]


def percentile(values: list[float], q: float) -> float:
    """Nearest-rank percentile; the caller prints the sample count."""
    if not values:
        return float("nan")
    s = sorted(values)
    return s[min(len(s) - 1, max(0, int(-(-q * len(s) // 1)) - 1))]


def answers_of(obj: dict) -> list:
    """The answers one response holds, for a traffic kind that does not say
    (`traffic/<kind>.py` `answers_of`): a classifier's `{"results": [one per
    text]}`, or one text's own `{"top_k": [...]}`; anything else holds none."""
    if "results" in obj:
        return obj["results"]
    return [obj] if "top_k" in obj else []


async def _post(session, url: str, req, deadline: float,
                answers=answers_of) -> tuple[bool, str]:
    """One POST, bounded by `deadline` (perf_counter). Correct means 200 and
    as many answers, by the traffic kind's `answers`, as items were sent."""
    import aiohttp

    left = deadline - time.perf_counter()
    if left <= 0:
        return False, "not_sent_by_deadline"
    try:
        async with session.post(
                url, data=req.body, headers=CONTENT_TYPE,
                timeout=aiohttp.ClientTimeout(total=left)) as resp:
            raw = await resp.read()
            if resp.status != 200:
                return False, f"http_{resp.status}"
    except asyncio.TimeoutError:
        return False, "still_out_after_drain"
    except aiohttp.ClientError as e:
        return False, type(e).__name__
    try:
        n = len(answers(json.loads(raw)))
    except (ValueError, TypeError, KeyError):
        return False, "bad_answer"
    return (True, "") if n == req.items else (False, "wrong_item_count")


async def _marks(on_window, t0: float, t1: float) -> None:
    """Await `on_window("start")` at t0 and `on_window("end")` at t1, beside
    the senders and never in their way."""
    for which, at in (("start", t0), ("end", t1)):
        await asyncio.sleep(max(0.0, at - time.perf_counter()))
        if on_window is not None:
            await on_window(which)


class _Recorder:
    def __init__(self, result: LoadResult, t0: float) -> None:
        self.r, self.t0, self.t1 = result, t0, t0 + result.seconds

    def record(self, req, due: float, sent: float, done: float, ok: bool,
               why: str) -> None:
        r = self.r
        if ok and self.t0 <= done < self.t1:
            r.items_in_window += req.items
        if not self.t0 <= due < self.t1:
            return  # warm-up, or due after the window closed
        r.attempted += 1
        r.late_ms.append((sent - due) * 1e3)
        if ok:
            r.latencies_by_class.setdefault(req.cls, []).append((done - due) * 1e3)
        else:
            r.failed += 1
            r.errors[why] = r.errors.get(why, 0) + 1


async def closed_loop(url: str, requests: list, clients: int, warmup_s: float,
                      seconds: float, drain_s: float, on_window=None,
                      answers=answers_of) -> LoadResult:
    """`clients` callers, each with one request out, through warm-up and
    window without a pause. A request is due when its caller's previous
    answer arrived. `on_window(which)` is awaited at the window's start
    ("start") and end ("end"). `answers` is `_post`'s."""
    import aiohttp

    start = time.perf_counter()
    t0 = start + warmup_s
    result = LoadResult(seconds)
    rec = _Recorder(result, t0)
    deadline = t0 + seconds + drain_s
    cursor = 0

    async def caller(session) -> None:
        nonlocal cursor
        due = time.perf_counter()
        while due < rec.t1:
            if cursor >= len(requests):
                result.wrapped = True
            req = requests[cursor % len(requests)]
            cursor += 1
            sent = time.perf_counter()
            ok, why = await _post(session, url, req, deadline, answers)
            done = time.perf_counter()
            rec.record(req, due, sent, done, ok, why)
            due = done

    conn = aiohttp.TCPConnector(limit=clients)
    async with aiohttp.ClientSession(connector=conn) as session:
        await asyncio.gather(_marks(on_window, t0, rec.t1),
                             *(caller(session) for _ in range(clients)))
    return result


async def open_loop(url: str, warm: list, warm_due, requests: list, due,
                    seconds: float, drain_s: float, on_window=None,
                    answers=answers_of) -> LoadResult:
    """Requests sent at their due times whatever the server does. `warm_due`
    are offsets in [0, warmup_s) before the window and `due` offsets in
    [0, seconds) inside it; the warm-up runs into the window without a
    pause. `answers` is `_post`'s."""
    import aiohttp

    warmup_s = float(warm_due[-1]) + 0.01 if len(warm_due) else 0.0
    start = time.perf_counter()
    t0 = start + warmup_s
    result = LoadResult(seconds)
    rec = _Recorder(result, t0)
    deadline = t0 + seconds + drain_s
    plan = [(start + float(d), r) for d, r in zip(warm_due, warm)]
    plan += [(t0 + float(d), r) for d, r in zip(due, requests)]
    tasks: set[asyncio.Task] = set()

    async def one(session, req, at: float) -> None:
        sent = time.perf_counter()
        ok, why = await _post(session, url, req, deadline, answers)
        rec.record(req, at, sent, time.perf_counter(), ok, why)

    conn = aiohttp.TCPConnector(limit=0)
    async with aiohttp.ClientSession(connector=conn) as session:
        marks = asyncio.ensure_future(_marks(on_window, t0, rec.t1))
        for at, req in plan:
            delay = at - time.perf_counter()
            if delay > 0:
                await asyncio.sleep(delay)
            t = asyncio.ensure_future(one(session, req, at))
            tasks.add(t)
            t.add_done_callback(tasks.discard)
        await asyncio.gather(marks, *tasks)
    return result
