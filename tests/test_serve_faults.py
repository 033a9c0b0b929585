"""Serve-tier chaos suite: every injected failure mode must end in exactly
one terminal response per request — results, degraded results, error, or
rejection — with the replica loop, the router, and the index all still
live afterwards.

Failure modes covered (all deterministic, via ``launch.faults.FaultPlan``):
deadline-degraded anytime answers (+ prefix consistency against an explicit
shorter run), scorer exceptions contained at the flush boundary, index swap
racing live submissions from other threads, hedged duplicate suppression,
error-driven and straggler-driven quarantine with queue drain, and
admission-control rejection ordering.

The whole module runs under a faulthandler watchdog (SERVE_WATCHDOG_S, like
the multidevice suite): a deadlocked router/replica thread dumps all stacks
and kills the run instead of hanging CI.
"""

import logging
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import AdaCURConfig
from repro.core.engine import AdaCURRetriever, ce_call_plan
from repro.core.index import AnchorIndex
from repro.core.scorer import TabulatedScorer
from repro.launch.faults import (
    FaultInjectedError,
    FaultPlan,
    FaultyScorer,
    ScorerFault,
    SleepFault,
    SwapFault,
)
from repro.launch.router import Router
from repro.launch.serve import AdaCURService, RetrievalRequest

N_Q, N_ITEMS = 60, 100
CFG = AdaCURConfig(
    k_anchor=4, n_rounds=4, budget_ce=12, k_retrieve=8, loop_mode="fori"
)


@pytest.fixture(autouse=True, scope="module")
def _watchdog():
    import faulthandler

    watchdog_s = float(os.environ.get("SERVE_WATCHDOG_S", "480"))
    faulthandler.dump_traceback_later(watchdog_s, exit=True)
    # injected scorer faults log loudly from inside the callback machinery;
    # they are the *point* of this suite, not noise worth printing
    logging.getLogger("jax._src.callback").setLevel(logging.CRITICAL)
    yield
    faulthandler.cancel_dump_traceback_later()


@pytest.fixture(scope="module")
def m():
    rng = np.random.default_rng(0)
    return rng.standard_normal((N_Q, N_ITEMS)).astype(np.float32)


def _service(m, *, plan=None, replica=None, item_offset=0, deterministic=False,
             max_batch=None, batch_buckets=None, record_pairs=False):
    if max_batch is None:
        max_batch = max(batch_buckets) if batch_buckets else 4
    wide = np.zeros((N_Q, item_offset + N_ITEMS), dtype=np.float32)
    wide[:, item_offset:] = m
    scorer = TabulatedScorer(wide, record_pairs=record_pairs)
    if plan is not None:
        scorer = FaultyScorer(scorer, plan, replica=replica)
    index = AnchorIndex.from_r_anc(
        jnp.asarray(m[:40]),
        item_ids=jnp.arange(item_offset, item_offset + N_ITEMS),
    )
    retriever = AdaCURRetriever.from_index(index, scorer, CFG, anytime=True)
    return AdaCURService(
        retriever=retriever, max_batch=max_batch, max_wait_s=60.0,
        batch_buckets=batch_buckets, deterministic=deterministic,
    )


class TestAnytimeDeadline:
    def test_degraded_response_is_prefix_consistent(self, m):
        """An expired budget returns the provisional top-k of the rounds
        completed — and that answer is *exactly* the answer an explicit
        ``n_rounds=rounds_completed`` run produces (same key, same batch
        shape): degradation truncates the trajectory, it never invents a
        different one."""
        svc = _service(m, deterministic=True, batch_buckets=[1])
        (r,) = svc.submit(RetrievalRequest(
            query_id=45, deadline_t=time.monotonic() - 1.0)) or svc.flush()
        assert r.status == "ok" and r.degraded
        assert r.rounds_completed == 1          # round 0 always completes
        assert r.measured_ce_calls == ce_call_plan(CFG, 1)
        ref = svc.retriever.search(
            jnp.asarray([45]), svc._key, n_rounds=r.rounds_completed
        )
        ref_ids = np.asarray(svc.index.gather_item_ids(ref.topk_idx))[0]
        np.testing.assert_array_equal(r.item_ids, ref_ids)
        np.testing.assert_array_equal(r.scores, np.asarray(ref.topk_scores[0]))

    def test_generous_deadline_serves_full_search(self, m):
        svc = _service(m, deterministic=True, batch_buckets=[1])
        (r,) = svc.submit(RetrievalRequest(
            query_id=45, deadline_t=time.monotonic() + 60.0)) or svc.flush()
        assert not r.degraded and r.rounds_completed == CFG.n_rounds
        assert r.measured_ce_calls == ce_call_plan(CFG)

    def test_deadline_requires_anytime_retriever(self, m):
        scorer = TabulatedScorer(m)
        index = AnchorIndex.from_r_anc(jnp.asarray(m[:40]))
        retr = AdaCURRetriever.from_index(index, scorer, CFG)  # not anytime
        with pytest.raises(ValueError, match="anytime"):
            retr.search(jnp.asarray([3]), deadline_t=time.monotonic())


class TestFlushErrorBoundary:
    def test_scorer_exception_fails_batch_not_loop(self, m):
        """A scorer raising on call k fails exactly the in-flight batch
        (per-request error responses); the queue and the compiled engine
        stay serviceable for the next batch."""
        plan = FaultPlan(scorer_faults=[ScorerFault(call_k=1)])
        svc = _service(m, plan=plan, batch_buckets=[1, 2, 4])
        svc.submit(RetrievalRequest(query_id=3))
        svc.submit(RetrievalRequest(query_id=7))
        out = svc.flush()
        assert [r.query_id for r in out] == [3, 7]
        assert all(r.status == "error" for r in out)
        assert all("FaultInjectedError" in r.error for r in out)
        assert all(r.item_ids is None for r in out)
        # the very next batch (call counter past the fault) serves cleanly
        svc.submit(RetrievalRequest(query_id=3))
        (ok,) = svc.flush()
        assert ok.status == "ok" and ok.error is None
        assert (0 <= ok.item_ids).all() and (ok.item_ids < N_ITEMS).all()

    def test_all_error_run_exits_nonzero(self, m):
        """The serve CLI exits non-zero when every response is an error,
        and not when at least one request was served."""
        from repro.launch import serve

        plan = FaultPlan(scorer_faults=[ScorerFault(call_k=1)])
        svc = _service(m, plan=plan, batch_buckets=[1, 2, 4])
        svc.submit(RetrievalRequest(query_id=3))
        svc.submit(RetrievalRequest(query_id=7))
        failed = svc.flush()
        with pytest.raises(SystemExit, match="all 2 responses were errors"):
            serve.exit_if_all_failed(failed)
        svc.submit(RetrievalRequest(query_id=3))
        serve.exit_if_all_failed(failed + svc.flush())

    @pytest.mark.parametrize("mesh,tpu", [("1x2", False), (None, True)])
    def test_real_ce_cache_refused_where_it_cannot_run(self, monkeypatch,
                                                       mesh, tpu):
        """--cache's host-callback CE is refused under --mesh and on a TPU
        before anything is built; on one CPU device it is allowed."""
        from repro.launch import serve

        monkeypatch.setattr(serve, "on_tpu", lambda: tpu)
        args = serve.build_arg_parser().parse_args(
            ["--scorer", "real-ce", "--cache"]
            + (["--mesh", mesh] if mesh else [])
        )
        with pytest.raises(SystemExit, match="drop --cache"):
            serve.refuse_real_ce_cache(args)
        args.mesh = None
        monkeypatch.setattr(serve, "on_tpu", lambda: False)
        serve.refuse_real_ce_cache(args)

    def test_fault_raises_at_exact_call(self, m):
        plan = FaultPlan(scorer_faults=[ScorerFault(call_k=3)])
        scorer = FaultyScorer(TabulatedScorer(m), plan)
        scorer._host_entry(np.asarray([0]), np.asarray([[1, 2]]))
        scorer._host_entry(np.asarray([0]), np.asarray([[1, 2]]))
        with pytest.raises(FaultInjectedError):
            scorer._host_entry(np.asarray([0]), np.asarray([[1, 2]]))
        # stats stayed on the inner scorer and counted only served calls
        assert scorer.stats.ce_calls == 4


class TestSwapUnderLiveSubmissions:
    def test_concurrent_swap_and_submit(self, m):
        """submit()/flush() from worker threads racing swap_index() from
        the main thread: every response's ids come wholly from one index's
        namespace (never a mix), and responses drained *by* the swap are
        answered against the admitting (old) index."""
        svc = _service(m, item_offset=1000, max_batch=2,
                       batch_buckets=[1, 2])
        # widen the scorer so both namespaces stay addressable
        wide = np.zeros((N_Q, 2000 + N_ITEMS), dtype=np.float32)
        wide[:, 1000:1000 + N_ITEMS] = m
        wide[:, 2000:] = m
        svc._scorer.matrix = wide
        new_index = AnchorIndex.from_r_anc(
            jnp.asarray(m[:40]), item_ids=jnp.arange(2000, 2000 + N_ITEMS)
        )
        svc.retriever.search(jnp.asarray([0, 1]))   # warm the compile

        responses, stop = [], threading.Event()
        out_lock = threading.Lock()

        def submitter(seed):
            rng = np.random.default_rng(seed)
            while not stop.is_set():
                got = svc.submit(
                    RetrievalRequest(query_id=int(rng.integers(0, N_Q)))
                ) or []
                got += svc.flush()
                with out_lock:
                    responses.extend(got)

        threads = [threading.Thread(target=submitter, args=(s,))
                   for s in range(3)]
        for t in threads:
            t.start()
        time.sleep(0.1)
        drained = svc.swap_index(new_index)
        time.sleep(0.1)
        stop.set()
        for t in threads:
            t.join(timeout=30)
            assert not t.is_alive()
        responses.extend(svc.flush())

        for r in drained:
            # swap-drained responses answer against their admitting index
            assert (r.item_ids >= 1000).all() and (r.item_ids < 2000).all()
        assert responses, "submitter threads served nothing"
        for r in responses:
            assert r.status == "ok"
            old = (r.item_ids >= 1000) & (r.item_ids < 2000)
            new = r.item_ids >= 2000
            assert old.all() or new.all(), "mixed-namespace response"
        # traffic after the swap point lands on the new index
        svc.submit(RetrievalRequest(query_id=5))
        (after,) = svc.flush()
        assert (after.item_ids >= 2000).all()


def _router(m, n_replicas=2, plan=None, record_pairs=False, **kw):
    services = [
        _service(m, plan=plan, replica=rid, batch_buckets=[1, 2, 4],
                 record_pairs=record_pairs)
        for rid in range(n_replicas)
    ]
    return Router(services, plan=plan, **kw)


def _warm(router, m):
    """Compile every replica's engine batch buckets before timing-sensitive
    phases (a cold jit compile dwarfs any injected stall otherwise)."""
    for rep in router.replicas:
        for b in rep.service.batch_buckets:
            rep.service.retriever.search(jnp.arange(b))


class TestRouterChaos:
    def test_hedged_pair_yields_exactly_one_response(self, m):
        """Replica 0 stalls every batch; hedging re-dispatches to replica 1.
        Each ticket resolves exactly once (CAS), and the winning attempt
        scored each of its CE pairs at most once."""
        plan = FaultPlan(sleep_faults=[SleepFault(replica=0, seconds=0.6)])
        router = _router(m, plan=plan, queue_limit=64, hedge_after_s=0.05,
                         record_pairs=True)
        try:
            _warm(router, m)
            qids = list(range(10, 18))           # distinct per ticket
            tickets = [router.submit(q) for q in qids]
            outs = [router.result(t, timeout=120) for t in tickets]
            assert all(o is not None for o in outs), "lost request"
            assert all(o.status == "ok" for o in outs)
            assert router.stats["hedges"] >= 1
            for t, o in zip(tickets, outs):
                # one terminal outcome; a replica never serves the same
                # ticket twice (hedge/retry dispatch excludes replicas
                # already tried), so with the engine's per-search
                # exactly-once pair invariant, no attempt double-scores
                assert o.attempts <= 2           # original + at most 1 hedge
                assert len(t.replicas_tried) == len(set(t.replicas_tried))
            # and within every scorer callback, a request's pair rows are
            # duplicate-free on both replicas
            for rep in router.replicas:
                for qarr, iarr in rep.service._scorer.call_log:
                    for qr, row in zip(np.asarray(qarr), np.asarray(iarr)):
                        assert len(row) == len(set(row.tolist())), (
                            "duplicate pair inside one scorer call"
                        )
        finally:
            router.close()

    def test_error_quarantine_drains_to_peers(self, m):
        """A replica whose every batch errors is quarantined after
        ``max_consecutive_errors`` and its queue drained: all requests
        still end OK via the healthy peer — zero lost."""
        plan = FaultPlan(scorer_faults=[
            ScorerFault(call_k=k, replica=0) for k in range(1, 500)
        ])
        router = _router(m, plan=plan, queue_limit=64, max_retries=2,
                         max_consecutive_errors=2)
        try:
            tickets = [router.submit(i % N_Q) for i in range(16)]
            outs = [router.result(t, timeout=120) for t in tickets]
            assert all(o is not None for o in outs), "lost request"
            assert all(o.status == "ok" for o in outs)
            assert router.quarantined == [0]
            assert not router.replicas[0].healthy
            assert router.replicas[1].healthy
            # post-quarantine traffic routes around the dead replica
            t = router.submit(9)
            out = router.result(t, timeout=120)
            assert out.status == "ok" and out.replica == 1
        finally:
            router.close()

    def test_straggler_watchdog_quarantines_slow_replica(self, m):
        """The StragglerWatchdog is the router's health signal: with the
        fleet baseline warmed by the healthy peer, a persistently slow
        replica is flagged against the *shared* median and quarantined
        after ``patience`` straggler batches."""
        # patience=1: hedging steals the stalled replica's repeat traffic,
        # so it only observes a batch or two before traffic routes away
        # margins: warmed CPU batches run well under ~0.4s even with GIL
        # noise, the flag level is 8 x 0.2s = 1.6s, and the injected stall
        # is 2s+ — healthy noise cannot flag, the stall cannot miss
        plan = FaultPlan(sleep_faults=[SleepFault(replica=0, seconds=2.0)])
        router = _router(m, plan=plan, queue_limit=64, hedge_after_s=0.05,
                         watchdog_threshold=8.0, watchdog_patience=1)
        try:
            _warm(router, m)
            # fleet-wide baseline (the shared deque means replica 0 is
            # judged against its peers' median, not its own stalled history)
            router.replicas[1].watchdog.window.extend([0.2] * 8)
            tickets = [router.submit(i % N_Q) for i in range(12)]
            outs = [router.result(t, timeout=120) for t in tickets]
            assert all(o is not None and o.status == "ok" for o in outs)
            # hedging answers long before the stalled replica's batch even
            # completes — wait for that batch to land and be flagged
            t_end = time.monotonic() + 30.0
            while 0 not in router.quarantined and time.monotonic() < t_end:
                time.sleep(0.05)
            assert 0 in router.quarantined
            # shared-baseline invariant: both watchdogs see one deque
            assert (router.replicas[0].watchdog.window
                    is router.replicas[1].watchdog.window)
        finally:
            router.close()

    def test_admission_rejection_ordering(self, m):
        """Load shedding is immediate and explicit: once ``queue_limit``
        tickets are in flight, the next submit resolves REJECTED before
        any in-flight ticket completes — never queued, never lost."""
        plan = FaultPlan(sleep_faults=[SleepFault(replica=0, seconds=0.5)])
        router = _router(m, n_replicas=1, plan=plan, queue_limit=2)
        try:
            _warm(router, m)
            admitted = [router.submit(i) for i in range(2)]
            shed = [router.submit(i) for i in range(2, 5)]
            # rejections are terminal at submit-return time, while the
            # admitted tickets are still in flight behind the stall
            for t in shed:
                assert t.resolved and t.outcome.status == "rejected"
                assert t.outcome.attempts == 0
            assert not any(t.resolved for t in admitted)
            outs = [router.result(t, timeout=120) for t in admitted]
            assert all(o is not None and o.status == "ok" for o in outs)
            assert router.stats["rejected"] == 3
            assert router.stats["admitted"] == 2
        finally:
            router.close()

    def test_midflight_swap_preserves_namespace_consistency(self, m):
        """A FaultPlan-scheduled swap at admission n: every response's ids
        are wholly from one index namespace and nothing is lost."""
        new_index = AnchorIndex.from_r_anc(
            jnp.asarray(m[:40]), item_ids=jnp.arange(2000, 2000 + N_ITEMS)
        )
        plan = FaultPlan(swap_faults=[SwapFault(at_seq=6)])
        services = []
        for rid in range(2):
            wide = np.zeros((N_Q, 2000 + N_ITEMS), dtype=np.float32)
            wide[:, 1000:1000 + N_ITEMS] = m
            wide[:, 2000:] = m
            scorer = TabulatedScorer(wide)
            index = AnchorIndex.from_r_anc(
                jnp.asarray(m[:40]),
                item_ids=jnp.arange(1000, 1000 + N_ITEMS),
            )
            retriever = AdaCURRetriever.from_index(
                index, scorer, CFG, anytime=True
            )
            services.append(AdaCURService(
                retriever=retriever, max_batch=4, max_wait_s=60.0,
                batch_buckets=[1, 2, 4],
            ))
        router = Router(services, plan=plan, queue_limit=64,
                        swap_index_fn=lambda: new_index)
        try:
            tickets = [router.submit(i % N_Q) for i in range(12)]
            outs = [router.result(t, timeout=120) for t in tickets]
            assert all(o is not None for o in outs), "lost request"
            assert all(o.status == "ok" for o in outs)
            assert router.stats["swaps"] == 1
            seen_new = False
            for o in outs:
                ids = o.response.item_ids
                old = ((ids >= 1000) & (ids < 2000)).all()
                new = (ids >= 2000).all()
                assert old or new, "mixed-namespace response"
                seen_new = seen_new or new
            assert seen_new, "swap never took effect"
        finally:
            router.close()

    def test_close_resolves_stragglers(self, m):
        """Shutdown with tickets still in flight: close() resolves them as
        errors — even teardown cannot lose a request."""
        plan = FaultPlan(sleep_faults=[SleepFault(replica=0, seconds=2.0)])
        router = _router(m, n_replicas=1, plan=plan, queue_limit=8)
        _warm(router, m)
        tickets = [router.submit(i) for i in range(3)]
        router.close(timeout=0.2)
        for t in tickets:
            out = router.result(t, timeout=120)
            assert out is not None
            assert out.status in ("ok", "error")
