"""The one traffic generator: drives calls through a window as a mix's data
file says.

A mix (``traffic/<name>.json``) gives ``loop`` ("closed": a client sends its
next call when the last one returned), ``clients`` (how many such clients,
each a thread; one client runs on the calling thread) and ``think_ms`` (a
client's pause between calls). Callers of a fit or a distance matrix wait
for the answer, so the loop is closed; an open loop is a later benchmark
PR's (PERF.md, Open questions).

The window opens at the first call and closes when the last call that began
before ``seconds`` were over has returned: every call that starts is
finished and counted, and rates are taken over the whole of that time.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, List, Optional


@dataclass
class Call:
    index: int
    client: int
    t0: float
    t1: float
    summary: Any = None
    error: Optional[str] = None

    @property
    def ms(self) -> float:
        return (self.t1 - self.t0) * 1e3


@dataclass
class Window:
    t_open: float
    t_close: float
    calls: List[Call] = field(default_factory=list)
    last_result: Any = None

    @property
    def seconds(self) -> float:
        return self.t_close - self.t_open


def check_mix(mix: dict) -> None:
    if mix.get("loop") != "closed":
        raise ValueError(f"traffic loop {mix.get('loop')!r}: only 'closed' is implemented")
    if not (isinstance(mix.get("clients"), int) and mix["clients"] >= 1):
        raise ValueError("traffic clients: a whole number of at least 1")
    if mix.get("think_ms", 0) < 0:
        raise ValueError("traffic think_ms: not negative")


def drive(
    mix: dict,
    seconds: float,
    one_call: Callable[[int], Any],
    summarise: Callable[[Any], Any],
    annotate: Callable[[str], Any],
    clock: Callable[[], float] = time.perf_counter,
) -> Window:
    """Run the mix for ``seconds``. ``one_call(i)`` makes call number ``i``
    and returns once every output the user would read is ready; its result
    is dropped before the same client's next call (a user who overwrites
    ``D``), except the last one to finish, which the check reads.
    ``summarise(result)`` keeps what is small of a result. ``annotate(name)``
    is a context manager that puts a span on the profiler's clock."""
    check_mix(mix)
    clients, think = mix["clients"], mix.get("think_ms", 0) / 1e3
    lock = threading.Lock()
    calls: List[Call] = []
    counter = [0]
    last: List[Any] = [None, -1.0]
    t_open = clock()

    def client(cid: int) -> None:
        while True:
            with lock:
                i = counter[0]
                counter[0] += 1
            result, error = None, None
            with annotate("chipbench.call"):
                t0 = clock()
                try:
                    result = one_call(i)
                except Exception as e:  # a failed call is counted, not fatal
                    error = f"{type(e).__name__}: {e}"
                t1 = clock()
            with annotate("chipbench.between_calls"):
                summary = summarise(result) if error is None else None
                done = t1 - t_open >= seconds
                with lock:
                    calls.append(Call(i, cid, t0, t1, summary, error))
                    if done and error is None and t1 > last[1]:
                        last[0], last[1] = result, t1
                result = None
                if done:
                    return
                if think:
                    time.sleep(think)

    if clients == 1:
        client(0)
    else:
        threads = [threading.Thread(target=client, args=(c,)) for c in range(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    calls.sort(key=lambda c: c.t1)
    return Window(t_open, max(c.t1 for c in calls), calls, last[0])
