"""Closed loop: ``clients`` callers that each send their next request as
soon as their last is answered (every batch full at clients = max_batch).
Query ids come from the seed."""

import time

from generator import Request, answered, span, warm_buckets

SERVICE = True    # the cell builds the service


def warm(c, traffic, rng, log):
    svc = c.cfg["service"]
    warm_buckets(c.service, rng.integers(c.cfg["deployment"]["n_queries"],
                                         size=svc["max_batch"]),
                 [svc["max_batch"]], log)


def drive(c, win, rng, traffic, seconds):
    from repro.launch.serve import RetrievalRequest

    service, n_queries = c.service, c.cfg["deployment"]["n_queries"]
    queue: list = []
    win.t_start = now = time.monotonic()
    deadline = win.t_start + seconds
    pending = traffic["clients"]
    while True:
        for _ in range(pending):
            qid = int(rng.integers(n_queries))
            req = Request(qid, now, now)
            win.requests.append(req)
            queue.append(req)
            with span(win, "bench.submit"):
                out = service.submit(RetrievalRequest(query_id=qid, arrival_t=now))
            if out:
                now = time.monotonic()
                pending = answered(win, out, queue, now)
                break
        else:
            with span(win, "bench.poll"):
                out = service.poll() or service.flush()
            now = time.monotonic()
            pending = answered(win, out, queue, now)
        if now >= deadline:
            win.t_end = now
            return
