"""Closed loop: the client keeps ``outstanding`` reads in flight.

Every answered read is replaced by a new one at once, as a bulk screening
client streaming a read set does. The window opens at the completion of
the ``preroll_batches``-th batch (the pipeline is full by then) and closes
at the first batch completion ``seconds`` or more later, so it holds whole
batches: the rate is the reads of the batches completed inside it over
its length.
"""

from __future__ import annotations

import functools
import queue
import time


def run(sched, stream, traffic: dict, seconds: float, window,
        seed: int) -> None:
    """Drive ``sched`` until the window closes; fills ``window``."""
    done: queue.SimpleQueue = queue.SimpleQueue()

    def submit() -> None:
        req = stream.next()
        req.sent = time.monotonic()
        window.requests.append(req)
        fut = sched.submit(req.read)
        fut.add_done_callback(functools.partial(window.resolved, req, done))

    for _ in range(int(traffic["outstanding"])):
        submit()
    preroll = int(traffic.get("preroll_batches", 2))
    seen = 0
    while window.t_close is None:
        done.get()
        while True:
            try:
                t, n = window.batch_events.get_nowait()
            except queue.Empty:
                break
            seen += 1
            if window.t_open is None:
                if seen >= preroll:
                    window.open(t)
            else:
                window.batches.append((t, n))
                if t >= window.t_open + seconds:
                    window.close(t)
                    break
        if window.t_close is None:
            submit()
