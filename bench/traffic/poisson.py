"""Open loop: exponential gaps at ``rate_qps``; each request falls due at
its scheduled time, whatever the service is doing.  Gaps and query ids
come from the seed.

The service answers inside ``submit``/``poll``, so the one generator
thread cannot hand a request over while a batch runs: requests that fall
due then are handed over when the batch returns.  The service sees them
arrive at that moment (``arrival_t`` is the hand-over time, which its
``max_wait_s`` batching runs from), as a server's event loop would read
them from its socket after a blocking flush.  Latency runs from each
request's due time, so that wait is charged to the requests, and the
harness prints how late the hand-over ran.
"""

import time

import numpy as np

from generator import Request, answered, span, warm_buckets

SERVICE = True    # the cell builds the service


def schedule(rng, n_queries: int, rate: float, seconds: float):
    """(gaps between due times, query ids), enough for ``seconds`` plus a
    minute."""
    n_max = int(rate * (seconds + 60) + 100)
    return rng.exponential(1.0 / rate, size=n_max), rng.integers(n_queries, size=n_max)


def warm(c, traffic, rng, log):
    svc = c.cfg["service"]
    warm_buckets(c.service, rng.integers(c.cfg["deployment"]["n_queries"],
                                         size=max(svc["batch_buckets"])),
                 svc["batch_buckets"], log)


def drive(c, win, rng, traffic, seconds):
    from repro.launch.serve import RetrievalRequest

    service, max_wait = c.service, c.cfg["service"]["max_wait_s"]
    gaps, qids = schedule(rng, c.cfg["deployment"]["n_queries"],
                          traffic["rate_qps"], seconds)
    n_max = len(gaps)
    queue: list = []
    win.t_start = time.monotonic()
    due = win.t_start + np.cumsum(gaps)
    deadline = win.t_start + seconds
    i = 0
    while True:
        now = time.monotonic()
        out = []
        while i < n_max and due[i] <= now:
            req = Request(int(qids[i]), float(due[i]), now)
            win.requests.append(req)
            queue.append(req)
            with span(win, "bench.submit"):
                got = service.submit(RetrievalRequest(query_id=req.qid, arrival_t=now))
            i += 1
            if got:
                out = got
                break
        if not out:
            with span(win, "bench.poll"):
                out = service.poll()
        if out:
            done = time.monotonic()
            answered(win, out, queue, done)
            if done >= deadline:
                win.t_end = done
                return
            continue
        wake = due[i] if i < n_max else now + max_wait
        if queue:
            wake = min(wake, queue[0].due + max_wait)
        with span(win, "bench.wait"):
            time.sleep(max(0.0, wake - time.monotonic()))
