"""Open loop: reads arrive on a Poisson schedule at ``rate_per_s``.

Independent users send whether or not earlier reads were answered, so a
stall delays every later read and the queue can grow. Each read is timed
from its scheduled arrival, and the generator records how late it sent.
The first ``preroll_s`` seconds of arrivals warm the pipeline and are not
measured; the window is the ``seconds`` of arrivals after them.
"""

from __future__ import annotations

import functools
import queue
import time

from bench import synth


def run(sched, stream, traffic: dict, seconds: float, window,
        seed: int) -> None:
    """Send the schedule; fills ``window`` (answers are awaited later)."""
    rate = float(traffic["rate_per_s"])
    preroll = float(traffic.get("preroll_s", 0.5))
    offsets = synth.arrivals(rate, preroll + seconds, seed)
    done: queue.SimpleQueue = queue.SimpleQueue()
    t0 = time.monotonic() + 0.05
    for off in offsets:
        due = t0 + off
        if window.t_open is None and off >= preroll:
            _sleep_until(t0 + preroll)
            window.open(t0 + preroll)
        _sleep_until(due)
        req = stream.next()
        req.scheduled = due
        req.sent = time.monotonic()
        if off >= preroll:
            window.requests.append(req)
        fut = sched.submit(req.read)
        fut.add_done_callback(functools.partial(window.resolved, req, done))
    _sleep_until(t0 + preroll + seconds)
    window.close(t0 + preroll + seconds)
    while True:
        try:
            window.batches.append(window.batch_events.get_nowait())
        except queue.Empty:
            break


def _sleep_until(t: float) -> None:
    wait = t - time.monotonic()
    if wait > 0:
        time.sleep(wait)
