"""The continuous-batching tick loop.

One ``tick`` runs up to three stage-boundary steps, in the order that
maximizes slot utilization:

  1. **finalize** — pop a group of retired slots (grain-sized, or partial
     when no slot is active or a retiree's deadline is close), run pool
     selection + stage 2 + rerank for just those rows, resolve their
     futures, free the slots;
  2. **refill**  — pop the most-urgent pending window from the admission
     queue, predict classes for the whole window, admit the grain-sized
     subset with the least class spread around the most urgent request
     (which always ships), hand the rest back;
  3. **chunk**   — advance every active slot one posting chunk; slots
     whose budget (``min(predicted rho, stream length)`` — or the full
     stream on the k knob) is spent retire immediately and wait for the
     next finalize group.

All device work goes through ``engine.SchedPrograms``'s four fixed-shape
executables, so any admit/retire churn pattern compiles nothing after
warmup.  Host bookkeeping (``SlotTable``) is the only source of stream
positions; the d2h points are the admission-time stream lengths and the
finalize results — the same boundaries the batch-once path vets.

Threading contract: ``tick`` (and therefore all device state) belongs to
one thread at a time; ``_lock`` guards the slot table and counters so
``stats``/``abort`` can run from the service's control thread.  ``abort``
must only be called from the tick thread or after it has quiesced.
"""

from __future__ import annotations

import collections
import threading
import time

import numpy as np

from repro.obs import NULL_OBS
from repro.serving import bucketing
from repro.serving.engine import SchedPrograms
from repro.serving.sched.slots import SlotTable

__all__ = ["ContinuousScheduler"]


class ContinuousScheduler:
    """Slot-based in-flight scheduler over a ``RetrievalServer``.

    fixed_param: serve every request at this parameter without the
    cascade (the dynamic-vs-fixed race's baseline arm — identical
    machinery, fixed budget).
    """

    def __init__(self, server, queue, *, slots: int = 32,
                 grain: int | None = None, chunk_p: int | None = None,
                 query_len: int | None = None, window: int | None = None,
                 co_group: bool = True, fixed_param: int | None = None,
                 on_results=None, clock=time.perf_counter):
        engine = server.engine
        self.server = server
        self.queue = queue
        self.grain = int(grain) if grain else engine.batch_multiple
        self.slots = int(slots)
        if self.grain > self.slots:
            raise ValueError(
                f"grain={self.grain} exceeds slots={self.slots}: a full "
                "retire group must fit the table or finalize can starve")
        # for_engine picks the sharded program set on a mesh engine; the
        # fixed arm's budget joins the static candidate-width grid so its
        # local retire bounds are in the admission meta like any cutoff
        self.prog = SchedPrograms.for_engine(
            engine, grain=self.grain, chunk_p=chunk_p,
            extra_widths=(() if fixed_param is None
                          else (int(fixed_param),)))
        self.window = int(window) if window else 2 * self.grain
        self.co_group = bool(co_group)
        self.fixed_param = (None if fixed_param is None
                            else int(fixed_param))
        self.on_results = on_results
        self.clock = clock
        self.knob = server.cfg.knob
        # the depth knob retires each slot at its predicted reranking
        # depth; the fixed arm and depth-off configs use the static pool
        # width (a no-op mask — bit-identical to the depth-free program)
        self.full_depth = int(server.cfg.depth_pool_width)
        self.use_depth = (fixed_param is None
                          and getattr(server, "has_depth_knob", False))
        self.query_len = query_len
        self._est = queue.cfg.service_estimate_ms / 1e3
        self._state = None             # SchedState; tick-thread only
        self._lock = threading.Lock()
        self.table = SlotTable(self.slots)
        self._retired = []             # retire-ordered, awaiting finalize
        self.retire_reasons = collections.Counter()
        self.n_admitted = 0
        self.n_retired = 0
        self.n_refill_calls = 0
        self.n_chunk_calls = 0
        self.n_finalize_calls = 0
        # stage-2 work accounting under the depth knob: candidate-pool
        # rows admitted into the rerank vs the depth-free pool rows.
        # Pure host arithmetic over admission-time predictions, so the
        # counters are deterministic across runs and platforms.
        self.n_rows_scored = 0
        self.n_rows_full = 0
        # tick-thread only (like _state): the monotone tick id stamped
        # on tick/step/slot spans; not under _lock by the same
        # single-owner contract
        self._tick_id = 0
        #: when the last working tick finished (scheduler clock); the
        #: service's stall watchdog reads it from its own thread
        self.t_last_tick = self.clock()
        self.bind_obs(NULL_OBS)

    def bind_obs(self, obs) -> None:
        """Attach an observability handle and pre-bind the hot-path
        metric objects (obs locks are leaves: recording while holding
        ``_lock`` is within the global order)."""
        self.obs = obs
        self._m_ticks = obs.metrics.counter("sched.ticks")
        self._m_retired = {
            r: obs.metrics.counter("sched.retired." + r)
            for r in ("rho_exhausted", "stream_exhausted",
                      "pool_complete")}
        # per finalized request, registered on either knob so that a 0
        # says the scheduler ran: its predicted k (k knob only) and the
        # chunk ticks its slot ran
        self._m_finalized = obs.metrics.counter("sched.finalized")
        self._m_k_width = obs.metrics.counter("sched.k_width")
        self._m_chunk_ticks = obs.metrics.counter("sched.chunk_ticks")

    # -------------------------------------------------------------- tick --
    def tick(self, now: float | None = None) -> int:
        """One scheduling step: finalize, refill, chunk.  Returns the
        number of work units (dispatches + resolutions) performed —
        0 means the scheduler is idle and the queue is empty."""
        t = self.clock() if now is None else now
        ev = self._finalize_step(t)
        ev += self._refill_step()
        ev += self._chunk_step()
        if ev:
            # working ticks only: idle polls would flood the span ring
            # and make the deterministic tick count load-dependent
            t1 = self.clock()
            self.obs.trace.record("tick", t, t1, tick=self._tick_id, ev=ev)
            self.t_last_tick = t1
            self._m_ticks.inc()
            self._tick_id += 1
        return ev

    @property
    def device_ready(self) -> bool | None:
        """Whether the last dispatched slot-table state has been computed
        (None before the first admission).  Safe from any thread."""
        state = self._state
        return None if state is None else bool(state.acc.is_ready())

    @property
    def idle(self) -> bool:
        with self._lock:
            return self.table.n_occupied == 0

    def stats(self) -> dict:
        with self._lock:
            return {
                "n_admitted": self.n_admitted,
                "n_retired": self.n_retired,
                "n_refill_calls": self.n_refill_calls,
                "n_chunk_calls": self.n_chunk_calls,
                "n_finalize_calls": self.n_finalize_calls,
                "n_rows_scored": self.n_rows_scored,
                "n_rows_full": self.n_rows_full,
                "retire_reasons": dict(self.retire_reasons),
                "chunks_max": self.prog.n_chunks,
                "slots": self.slots,
                "grain": self.grain,
                "chunk_p": self.prog.chunk_p,
                "sharded": self.prog.sharded,
            }

    # ---------------------------------------------------------- finalize --
    def _finalize_step(self, t: float) -> int:
        with self._lock:
            g = self._pop_group(t)
        if not g:
            return 0
        with self.obs.trace.span("tick.finalize", tick=self._tick_id,
                                 n=len(g)) as sp:
            self._finalize_group(g, sp.t0)
        with self._lock:
            for s in g:
                # pool rows the rerank actually scored for this slot vs
                # the depth-free pool (k: the predicted pool width,
                # clamped to the static pool; rho: the static depth)
                full = (min(s.width, self.full_depth)
                        if self.knob == "k" else self.full_depth)
                self.n_rows_scored += min(s.depth, full)
                self.n_rows_full += full
                self._m_chunk_ticks.inc(s.chunks)
                if self.knob == "k":
                    self._m_k_width.inc(s.width)
                self.table.release(s)
            self._m_finalized.inc(len(g))
            self.n_finalize_calls += 1
        return len(g)

    def _finalize_group(self, g, t0: float) -> None:
        """Stages 1b-3 for the retired group ``g``: resolve its futures
        and report them (``t0``: when the finalize step began)."""
        pad = len(g)
        idx = np.full(self.grain, g[0].idx, np.int32)
        pvec = np.ones(self.grain, np.int32)
        dvec = np.ones(self.grain, np.int32)
        qids = np.full(self.grain, g[0].qid, np.int32)
        idx[:pad] = [s.idx for s in g]
        pvec[:pad] = [s.width for s in g]
        dvec[:pad] = [s.depth for s in g]
        qids[:pad] = [s.qid for s in g]
        ranked = self.prog.finalize(self._state, idx, pvec, dvec, qids)
        t_done = self.clock()
        reqs, results = [], []
        for i, s in enumerate(g):
            r = s.req
            results.append({
                "ranked": ranked[i],
                "class": (None if self.fixed_param is not None
                          else int(s.pred_class)),
                "width": float(s.width),
                "depth": float(s.depth),
                "depth_class": (int(s.depth_class) if self.use_depth
                                else None),
                "predictor_version": s.version,
                "queue_ms": (s.t_admit - r.t_submit) * 1e3,
                "predict_ms": s.predict_ms,
                "service_ms": (t_done - s.t_admit) * 1e3,
                "total_ms": (t_done - r.t_submit) * 1e3,
                "deadline_met": t_done <= r.deadline,
                "retire_reason": s.retire_reason,
                "chunks_executed": s.chunks,
                "chunks_max": self.prog.n_chunks,
                "slot_occupancy": s.occupancy,
                "trace_id": int(r.seq),
            })
            reqs.append(r)
        trace = self.obs.trace
        for i, s in enumerate(g):
            # slot occupancy window, admission to retirement
            trace.record("slot", s.t_admit, s.t_retire, qid=s.qid,
                         slot=s.idx, width=int(s.width),
                         depth=int(s.depth), chunks=int(s.chunks),
                         retire_reason=s.retire_reason,
                         occupancy=round(float(s.occupancy), 4))
        for r, res in zip(reqs, results):
            if not r.future.done():
                r.future.set_result(res)
            trace.end(r.span, retire_reason=res["retire_reason"],
                      deadline_met=bool(res["deadline_met"]))
        if self.on_results is not None:
            self.on_results(reqs, results, t_done,
                            service_ms=(t_done - t0) * 1e3)

    def _pop_group(self, t: float):
        # caller holds the lock.  Fire on: a full grain of retirees; no
        # active slot left to overlap with (drain / trickle traffic); or
        # a retiree's deadline within the service estimate (deadline-
        # aware slotting's output side).
        if not self._retired:
            return None
        full = len(self._retired) >= self.grain
        starved = not self.table.active()
        urgent = (min(s.req.deadline for s in self._retired) - t
                  <= self._est)
        if not (full or starved or urgent):
            return None
        g = self._retired[: self.grain]
        del self._retired[: len(g)]
        return g

    # ------------------------------------------------------------ refill --
    def _refill_step(self) -> int:
        ev = 0
        trace = self.obs.trace
        while True:
            with self._lock:
                free = self.table.n_free
            if free == 0:
                break
            cand = self.queue.take_urgent(self.window)
            # admission: the moment the requests left the pending set
            t_admit = self.clock()
            cand = [r for r in cand if self._fits(r)]
            if not cand:
                break
            n = min(free, self.grain, len(cand))
            with trace.span("tick.refill", tick=self._tick_id):
                with trace.span("predict", tick=self._tick_id,
                                n=len(cand)) as psp:
                    classes, ver = self._predict(cand)
                keep, back = self._select(cand, classes, n)
                if back.size:
                    self.queue.requeue([cand[i] for i in back])
                self._admit([cand[i] for i in keep], classes[keep], ver,
                            psp.dur_ms, t_admit)
            ev += 1
            if len(keep) < self.grain:
                break                  # queue drained below a full grain
        return ev

    def _fits(self, req) -> bool:
        # adopt the first request's width as the slot row width; longer
        # queries can't ride this table and fail fast instead of hanging
        p = np.asarray(req.payload, np.int32).ravel()
        if self.query_len is None:
            self.query_len = max(int(p.shape[0]), 1)
        if p.shape[0] <= self.query_len:
            return True
        if not req.future.done():
            req.future.set_exception(ValueError(
                f"query length {p.shape[0]} exceeds the scheduler's slot "
                f"width {self.query_len} (set query_len at construction)"))
        return False

    def _rows(self, reqs) -> np.ndarray:
        qt = np.full((self.grain, self.query_len), -1, np.int32)
        for i, r in enumerate(reqs):
            p = np.asarray(r.payload, np.int32).ravel()
            qt[i, : p.shape[0]] = p
        return qt

    def _predict(self, cand):
        if self.fixed_param is not None:
            # the fixed arm runs no cascade: every query at one budget
            return (np.zeros(len(cand), np.int64),
                    getattr(self.server, "predictor_version", 0))
        qt = np.full((len(cand), self.query_len), -1, np.int32)
        for i, r in enumerate(cand):
            p = np.asarray(r.payload, np.int32).ravel()
            qt[i, : p.shape[0]] = p
        ver = getattr(self.server, "predictor_version", 0)
        return np.asarray(self.server.predict_classes(qt)), ver

    def _select(self, cand, classes, n: int):
        """Refill-group choice: the most urgent request (cand[0]) always
        ships; the remaining seats go to the candidates whose predicted
        class is nearest its class (stable by urgency), so a group's
        padded maxima track its members instead of the global worst case."""
        if len(cand) <= n:
            return np.arange(len(cand)), np.array([], np.int64)
        order = np.arange(1, len(cand))
        if self.co_group and self.fixed_param is None:
            spread = np.abs(classes[1:] - classes[0])
            order = order[np.argsort(spread, kind="stable")]
        keep = np.concatenate(([0], order[: n - 1]))
        back = np.setdiff1d(np.arange(len(cand)), keep)
        return np.sort(keep), back

    def _admit(self, group, classes, ver, predict_ms: float,
               t_admit: float) -> None:
        if not group:
            return
        if self._state is None:
            self._state = self.prog.init_state(self.slots, self.query_len)
        qt = self._rows(group)
        rows, slen, lend = self.prog.gather(qt)
        with self._lock:
            taken = [self.table.acquire() for _ in group]
            self.n_refill_calls += 1
        idx = np.full(self.grain, self.slots, np.int32)  # pad rows drop
        idx[: len(group)] = [s.idx for s in taken]
        self._state = self.prog.refill(self._state, idx, rows)
        if self.fixed_param is not None:
            widths = np.full(len(group), self.fixed_param, np.int64)
            if self.knob == "rho":
                widths = np.minimum(widths,
                                    self.server.cfg.stream_cap)
        else:
            widths = np.asarray(self.server.params_of(classes))
        if self.use_depth:
            dclasses, depths = self.server.predict_depths(
                qt[: len(group)])
        else:
            dclasses, depths = None, None
        with self._lock:
            occ = self.table.n_occupied / self.slots
            for i, (s, r) in enumerate(zip(taken, group)):
                s.req = r
                s.qid = int(r.seq)
                s.pred_class = int(classes[i])
                s.width = int(widths[i])
                s.depth = (int(depths[i]) if depths is not None
                           else self.full_depth)
                s.depth_class = (int(dclasses[i])
                                 if dclasses is not None else -1)
                s.version = int(ver)
                s.predict_ms = predict_ms
                s.t_admit = t_admit
                s.pos = 0
                s.chunks = 0
                sl = int(slen[i])
                s.end = min(s.width, sl) if self.knob == "rho" else sl
                if self.prog.sharded:
                    # the worst shard's local stream end for this slot's
                    # budget, precomputed in the admission meta; the
                    # local cursor retires against it (lend == 0 exactly
                    # when end == 0 — global position 0 is owned by some
                    # shard whenever any posting is admitted)
                    col = self.prog.lend_col(
                        s.width if self.knob == "rho"
                        else self.server.cfg.stream_cap)
                    s.lpos = 0
                    s.lend = int(lend[i, col])
                    done = s.lpos >= s.lend
                else:
                    done = s.pos >= s.end
                self.n_admitted += 1
                # the request's wait in the pending set (take_urgent
                # bypasses batch formation, so the queue span lands here)
                self.obs.trace.record("queue", r.t_submit, t_admit,
                                      qid=s.qid, slot=s.idx)
                if done:               # empty stream: retire immediately
                    self._retire(s, t_admit, occ)

    # ------------------------------------------------------------- chunk --
    def _chunk_step(self) -> int:
        with self._lock:
            act = self.table.active()
            if not act:
                return 0
            sharded = self.prog.sharded
            pos = np.zeros(self.slots, np.int32)
            end = np.zeros(self.slots, np.int32)
            for s in act:
                # sharded programs window the *local* partitioned stream;
                # the device mask still applies the global rho budget
                pos[s.idx] = s.lpos if sharded else s.pos
                end[s.idx] = s.end
            self.n_chunk_calls += 1
        # the chunk step's window; the sched.chunk span inside prog.chunk
        # covers the dispatch itself
        with self.obs.trace.span("tick.chunk", tick=self._tick_id,
                                 n=len(act)) as sp:
            self._state = self.prog.chunk(self._state, pos, end)
            with self._lock:
                occ = self.table.n_occupied / self.slots
                cp = self.prog.chunk_p
                for s in act:
                    s.chunks += 1
                    if sharded:
                        s.lpos = min(s.lpos + cp, s.lend)
                        done = s.lpos >= s.lend
                    else:
                        s.pos = min(s.pos + cp, s.end)
                        done = s.pos >= s.end
                    if done:
                        # stamped when this step began, which follows
                        # every admission of the tick
                        self._retire(s, sp.t0, occ)
        return 1

    def _retire(self, s, t: float, occupancy: float) -> None:
        # caller holds the lock
        if self.knob == "rho":
            reason = ("rho_exhausted" if s.width <= s.end
                      else "stream_exhausted")
        else:
            reason = "pool_complete"
        s.retire_reason = reason
        s.t_retire = t
        s.occupancy = occupancy
        self._retired.append(s)
        self.retire_reasons[reason] += 1
        self.n_retired += 1
        self._m_retired[reason].inc()

    # ----------------------------------------------------------- control --
    def abort(self, exc: BaseException | None = None) -> None:
        """Fail (or cancel) every in-flight request and reset the table.
        Only call from the tick thread, or after it has quiesced."""
        with self._lock:
            live = self.table.occupied()
            self._retired.clear()
            for s in live:
                r = s.req
                if r is not None and not r.future.done():
                    if exc is not None:
                        r.future.set_exception(exc)
                    else:
                        r.future.cancel()
                if r is not None:
                    self.obs.trace.end(r.span, aborted=True)
                self.table.release(s)

    def warmup(self, query_len: int | None = None) -> int | None:
        """Compile the four scheduler programs plus the cascade at every
        padded candidate-window width.  Returns fresh executables, or
        None when the query width is still unknown."""
        ql = query_len or self.query_len
        if not ql:
            return None
        self.query_len = ql
        engine = self.server.engine
        with engine._cache_lock:
            before = engine.n_compiles
        self.prog.warmup(self.slots, ql)
        if (self.fixed_param is None
                and getattr(self.server, "cascade", None) is not None):
            m = engine.batch_multiple
            top = bucketing.pad_length(self.window, m)
            for w in range(m, top + 1, m):
                self.server.predict_classes(np.full((w, ql), -1,
                                                    np.int32))
        if self.use_depth and "depth" in getattr(self.server,
                                                 "_predict_fns", {}):
            # the depth cascade runs on admitted groups (<= grain rows,
            # padded to the batch grid) — one extra predict executable
            m = engine.batch_multiple
            w = bucketing.pad_length(self.grain, m)
            self.server.predict_classes(np.full((w, ql), -1, np.int32),
                                        knob="depth")
        with engine._cache_lock:
            return engine.n_compiles - before
