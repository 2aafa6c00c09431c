"""Multi-device sharded dataflow serving — the mesh-pipelined runtime.

H2PIPE's die pipelines every layer engine concurrently, each fed by its
own HBM pseudo-channel; the distribution-level analogue runs the SAME
compiled schedule as a pipeline over mesh devices.  The compiler cuts
the placed layer order into balanced device-local stage programs
(:meth:`CompiledPipeline.partition`), and this engine executes them:

  * **mesh pipeline**: one stage per device over the ``axis`` ring —
    each tick every stage runs ITS slice of the compiled engine table
    (heterogeneous ``lax.switch`` programs inside one ``shard_map``)
    and hands its boundary activation to the next stage via
    ``lax.ppermute`` (``core/dataflow.py::staged_pipeline_apply``); a
    round of M microbatches drains in M + S - 1 stage times (the §V-A
    static schedule: one admission per tick, at most S resident);
  * **shard-local producers**: ``submit(images, shard=...)`` feeds one
    of S bounded shard queues (round-robin by default) — each shard
    packs its own microbatches with the SAME
    :class:`~repro.runtime.cnn_serving.MicrobatchPacker` the host-queue
    engine uses, and the dispatcher drains shards fairly into rounds
    instead of funneling every producer through one host queue;
  * **cross-device credits**: the §V-A in-flight bound is the shared
    :class:`~repro.core.admission.AdmissionController` — UNCHANGED —
    counting dispatched-not-delivered microbatches across the whole
    mesh (``credits >= round_microbatches`` so a full round fits;
    ``2x`` double-buffers rounds).  Its invariant hooks prove the bound
    held, exactly as for the single-device engine;
  * **per-stage Eq. 2**: start() hard-fails unless every stage's
    ``ExecutionReport.verify()`` passes on the partitioned plan AND the
    staged trace's executed per-stage word counters equal the stage
    plans — splitting the graph never loosens the plan-vs-dispatch
    cross-check.

Results are bit-identical to sequential ``run()`` per request: stages
compute the same engine programs on the same activations (the ring only
moves int8 boundary buffers), padded rows/microbatches are sliced away
before delivery.
"""
from __future__ import annotations

import queue
import threading
from collections import deque
from dataclasses import dataclass, field
from typing import (Any, Callable, Dict, List, Optional, Sequence, Tuple)

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.admission import AdmissionController, AdmissionError
from repro.core.dataflow import staged_pipeline_apply
from repro.kernels.pallas_compat import resolve_interpret
from repro.models.cnn import cnn_input_shape
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import NULL_TRACER, monotonic_clock
from repro.runtime.cnn_serving import (_STOP, METRIC_WINDOW,
                                       REQUEST_ROW_WINDOW, CnnRequest,
                                       MicrobatchPacker, ServingObsMixin,
                                       ServingReport, stamp_launch)

__all__ = ["ShardedCnnServingEngine", "ShardedServingReport"]


@dataclass
class ShardedServingReport(ServingReport):
    """The :class:`ServingReport` fields plus the staged-topology view:
    how the rounds filled, what each stage streamed, and the mesh
    shape the numbers were produced on."""

    n_stages: int = 1
    rounds: int = 0
    round_microbatches: int = 0
    empty_microbatches: int = 0       # whole-padding slots in short rounds
    stage_hbm_words_per_image: Tuple[int, ...] = ()
    shard_requests: Tuple[int, ...] = ()

    @property
    def round_fill_fraction(self) -> float:
        total = self.rounds * self.round_microbatches
        return self.microbatches / total if total else 0.0


class ShardedCnnServingEngine(ServingObsMixin):
    """Credit-bounded serving over a compiled pipeline partitioned
    across a device mesh (see module docstring).

    ``microbatch`` is the per-stage activation batch (one ring slot);
    ``round_microbatches`` (default ``8 * n_stages``) is how many
    microbatches one staged dispatch carries — larger rounds amortize
    the S - 1 fill bubble (``pipeline_stats``).  ``credits`` bounds
    dispatched-not-delivered microbatches across the mesh (default
    ``2 * round_microbatches``: one round in flight, one filling).

    Use as a context manager (``with cp.serve_sharded(params, mesh=m)
    as eng``) or call :meth:`start`/:meth:`stop`; :meth:`submit` is
    thread-safe, with an optional explicit target shard.
    """

    def __init__(self, compiled, params, *, mesh, axis: str = "model",
                 microbatch: int = 4,
                 round_microbatches: Optional[int] = None,
                 credits: Optional[int] = None, queue_depth: int = 64,
                 interpret: Optional[bool] = None, act_scale: float = 0.05,
                 tracer=None, metrics: Optional[MetricsRegistry] = None,
                 clock: Optional[Callable[[], float]] = None,
                 metric_window: int = METRIC_WINDOW,
                 request_row_window: int = REQUEST_ROW_WINDOW):
        if microbatch <= 0:
            raise ValueError("microbatch must be positive")
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        if axis not in sizes:
            raise ValueError(
                f"mesh has no axis {axis!r}; available axes: {sizes}")
        self.compiled = compiled
        self.params = params
        self.mesh = mesh
        self.axis = axis
        self.n_stages = sizes[axis]
        self.microbatch = microbatch
        self.act_scale = act_scale
        if interpret is None and compiled.target is not None:
            interpret = compiled.target.interpret
        self.interpret = resolve_interpret(interpret)
        self.partition = compiled.partition(self.n_stages)
        M = (8 * self.n_stages if round_microbatches is None
             else round_microbatches)
        if M < 1:
            raise ValueError("round_microbatches must be >= 1")
        self.round_microbatches = M
        credits = 2 * M if credits is None else credits
        if credits < M:
            raise ValueError(
                f"credits ({credits}) must cover one full round of "
                f"{M} microbatches — a smaller bound would deadlock the "
                f"round dispatcher")
        self.tracer = NULL_TRACER if tracer is None else tracer
        self.metrics = MetricsRegistry() if metrics is None else metrics
        if clock is None:
            clock = self.tracer.clock if self.tracer.enabled \
                else monotonic_clock
        self._clock = clock
        self.admission = AdmissionController(credits,
                                             name="sharded-serving",
                                             clock=clock)
        self._in_shape = cnn_input_shape(compiled.plan.cfg, microbatch)
        self._round_shape = (M,) + self._in_shape
        self.words_per_image = sum(
            compiled.plan.hbm_words_per_image().values())

        # shard-local producers: one bounded queue + packer per stage
        self._queues = [queue.Queue(maxsize=queue_depth)
                        for _ in range(self.n_stages)]
        self._packers = [MicrobatchPacker(q, microbatch, self.tracer)
                         for q in self._queues]
        self._shard_requests = [0] * self.n_stages
        self._rr_submit = 0           # round-robin producer assignment
        self._rr_drain = 0            # round-robin dispatcher fairness
        self._work = threading.Condition()   # "a shard queue has work"

        self._fn = None
        self._inflight: "queue.Queue" = queue.Queue()
        self._threads: List[threading.Thread] = []
        self._started = False
        self._stopped = False
        self._error: Optional[BaseException] = None

        self._lock = threading.Condition()
        self._submit_lock = threading.Lock()
        self._accepting = False
        self._rid = 0
        self._outstanding = 0
        self._latencies: deque = deque(maxlen=metric_window)
        self._request_rows: deque = deque(maxlen=request_row_window)
        self._images_done = 0
        self._requests_done = 0
        self._mb_count = 0
        self._round_count = 0
        self._padded_rows = 0
        self._empty_microbatches = 0
        self._depth_samples: deque = deque(maxlen=metric_window)
        self._t0: Optional[float] = None
        self._t_last: Optional[float] = None
        # stall attribution (see ServingObsMixin): round-dispatcher idle
        # time between rounds; admission waits live on the controller
        self._gap_s = 0.0
        self._modelled = False        # False = not yet computed (lazy)

    # -- lifecycle -----------------------------------------------------------

    def start(self) -> "ShardedCnnServingEngine":
        if self._started:
            return self
        if self._stopped:
            raise RuntimeError(
                "sharded serving engine is single-use; create a new one "
                "(CompiledPipeline.serve_sharded) instead of restarting")
        from repro.compiler.partition import stage_forward_fns
        part = self.partition
        S = self.n_stages
        mb = self.microbatch
        # trace-time stats sinks: one per stage, filled while lowering
        collect: List[list] = [[] for _ in range(S)]
        fns = stage_forward_fns(part, interpret=self.interpret,
                                act_scale=self.act_scale, collect=collect)
        bshapes = [None] + [part.boundary_shape(s, mb)
                            for s in range(1, S)]

        def round_forward(p, x_round):
            return staged_pipeline_apply(
                fns, p, x_round, mesh=self.mesh, axis=self.axis,
                boundary_shapes=bshapes, out_shape=part.out_shape(mb),
                out_dtype=jnp.float32)

        # params and every round's packed buffer live replicated on the
        # mesh, placed once / per round — not left on device 0 for the
        # staged program to copy out on every dispatch
        self._replicated = NamedSharding(self.mesh, P())
        self.params = jax.device_put(self.params, self._replicated)
        zeros = jax.device_put(np.zeros(self._round_shape, np.int8),
                               self._replicated)
        self._fn = jax.jit(round_forward).lower(self.params,
                                                zeros).compile()

        # the split-graph Eq. 2 guarantee, both directions: the sliced
        # plan verifies against the sliced stats template per stage...
        part.verify_eq2(batch=mb)
        # ...and the staged trace's EXECUTED per-stage counters agree
        # with each stage program's plan-side words
        n_nodes = sum(len(c) for c in collect)
        L = len(self.compiled.plan.schedules)
        if n_nodes != L:
            raise RuntimeError(
                f"staged trace dispatched {n_nodes} node(s), plan has {L}")
        for s, sp in enumerate(part.stages):
            traced = sum(st.hbm_words for st in collect[s])
            want = sp.hbm_words_per_image * mb
            if traced != want:
                raise RuntimeError(
                    f"stage {s} traced Eq. 2 words ({traced}) disagree "
                    f"with its stage plan ({sp.hbm_words_per_image} "
                    f"words/image x {mb})")

        self._threads = [
            threading.Thread(target=self._dispatch_loop, daemon=True,
                             name="sharded-serving-dispatch"),
            threading.Thread(target=self._complete_loop, daemon=True,
                             name="sharded-serving-complete"),
        ]
        for t in self._threads:
            t.start()
        self._started = True
        self._accepting = True
        return self

    def stop(self) -> None:
        """Drain everything already submitted, then shut down and verify
        the admission accounting is quiescent.  Single-use."""
        if not self._started:
            return
        with self._submit_lock:
            self._accepting = False
            for q in self._queues:
                q.put(_STOP)
        with self._work:
            self._work.notify_all()
        for t in self._threads:
            t.join()
        self._started = False
        self._stopped = True
        if self._error is None:
            self.admission.assert_quiescent()

    def __enter__(self) -> "ShardedCnnServingEngine":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # -- submission ----------------------------------------------------------

    def submit(self, images, shard: Optional[int] = None) -> CnnRequest:
        """Enqueue ``images`` ([n,H,W,C] int8) on a shard-local producer
        queue — ``shard`` picks the queue explicitly (a producer local
        to that device's host slice), default round-robins.  Blocks when
        the target shard's bounded queue is full."""
        if not self._started:
            raise RuntimeError("sharded serving engine not started")
        if self._error is not None:
            raise RuntimeError("sharded serving engine failed") \
                from self._error
        arr = np.asarray(images)
        if arr.ndim == 3:
            arr = arr[None]
        want = self._in_shape[1:]
        if arr.ndim != 4 or arr.shape[1:] != want or arr.shape[0] < 1:
            raise ValueError(
                f"expected images [n,{want[0]},{want[1]},{want[2]}], "
                f"got {arr.shape}")
        if shard is not None and not 0 <= shard < self.n_stages:
            raise ValueError(
                f"shard {shard} outside [0, {self.n_stages})")
        arr = arr.astype(np.int8, copy=False)
        with self._lock:
            self._rid += 1
            req = CnnRequest(self._rid, arr, now=self._clock())
            req.hbm_words = req.n * self.words_per_image
            self._outstanding += 1
            if shard is None:
                shard = self._rr_submit % self.n_stages
                self._rr_submit += 1
        if self.tracer.enabled:
            self.tracer.begin("request", "request", req.rid,
                              images=req.n, shard=shard)
        with self._submit_lock:
            while True:
                if not self._accepting:
                    self._reject(req)
                    raise RuntimeError(
                        "sharded serving engine is stopping")
                try:
                    self._queues[shard].put(req, timeout=0.5)
                    break
                except queue.Full:
                    continue
        # only requests that actually entered a shard queue advance the
        # serving clock and the submitted counters (mirrors
        # CnnServingEngine: a submit() that lost the race against stop()
        # must skew neither wall_s nor the per-shard accounting)
        with self._lock:
            self._shard_requests[shard] += 1
            if self._t0 is None or req.t_submit < self._t0:
                self._t0 = req.t_submit
        self.metrics.counter("serving_requests_submitted",
                             shard=shard).inc()
        with self._work:
            self._work.notify_all()
        if self._error is not None:
            self._sweep_queues(self._error)
        return req

    def drain(self, timeout: Optional[float] = None) -> None:
        """Block until every submitted request has been delivered."""
        with self._lock:
            if not self._lock.wait_for(
                    lambda: self._outstanding == 0
                    or self._error is not None, timeout):
                raise TimeoutError(
                    f"{self._outstanding} request(s) still outstanding")
        if self._error is not None:
            raise RuntimeError("sharded serving engine failed") \
                from self._error

    def serve(self, batches: Sequence[Any]
              ) -> Tuple[List[np.ndarray], ShardedServingReport]:
        """Closed-loop convenience: submit all ``batches`` (round-robin
        over shards), drain, return ([logits per batch], report)."""
        reqs = [self.submit(b) for b in batches]
        self.drain()
        return [r.result() for r in reqs], self.report()

    # -- reporting -----------------------------------------------------------

    def report(self) -> ShardedServingReport:
        import math
        metrics = self._metrics_snapshot()
        with self._lock:
            lat = sorted(self._latencies)
            wall = (self._t_last - self._t0) \
                if (self._t0 is not None and self._t_last is not None) \
                else 0.0
            images = self._images_done

            def pct(p: float) -> float:
                if not lat:
                    return 0.0
                return 1e3 * lat[max(0, math.ceil(p * len(lat)) - 1)]

            return ShardedServingReport(
                requests=self._requests_done,
                images=images,
                microbatches=self._mb_count,
                microbatch_size=self.microbatch,
                padded_rows=self._padded_rows,
                credits=self.admission.capacity,
                max_in_flight=self.admission.max_in_flight_seen,
                wall_s=wall,
                images_per_s=images / wall if wall > 0 else 0.0,
                p50_ms=pct(0.50), p95_ms=pct(0.95), p99_ms=pct(0.99),
                hbm_words_per_image=self.words_per_image,
                hbm_words_useful=images * self.words_per_image,
                hbm_words_executed=(self._mb_count
                                    + self._empty_microbatches)
                * self.microbatch * self.words_per_image,
                queue_depth=list(self._depth_samples),
                request_rows=list(self._request_rows),
                dispatched_rows=(self._mb_count + self._empty_microbatches)
                * self.microbatch,
                microbatch_shapes={str(self.microbatch): self._mb_count}
                if self._mb_count else {},
                trace_cache=self.compiled.trace_cache_stats(),
                metrics=metrics,
                bandwidth_efficiency=self._stall_report(wall),
                n_stages=self.n_stages,
                rounds=self._round_count,
                round_microbatches=self.round_microbatches,
                empty_microbatches=self._empty_microbatches,
                stage_hbm_words_per_image=tuple(
                    s.hbm_words_per_image for s in self.partition.stages),
                shard_requests=tuple(self._shard_requests),
            )

    # -- worker threads ------------------------------------------------------

    def _dispatch_loop(self) -> None:
        try:
            while True:
                # dispatch-gap attribution: time between rounds with
                # nothing to pack (counted once serving has begun)
                t_idle = self._clock()
                packs = self._collect_round()
                if self._round_count > 0:
                    self._gap_s += self._clock() - t_idle
                if packs is None:
                    break
                self._dispatch_round(packs)
        except BaseException as exc:                 # pragma: no cover
            self._fail(exc)
        finally:
            self._inflight.put(None)                 # completer sentinel

    def _next_pack(self, *, block: bool):
        """One packed microbatch from the first shard (round-robin from
        the fairness cursor) with work available; ``block=True`` waits
        for any shard to produce, returning None only when every shard's
        stop sentinel has been drained."""
        while True:
            for k in range(self.n_stages):
                p = self._packers[(self._rr_drain + k) % self.n_stages]
                got = p.collect(block=False)
                if got is not None:
                    self._rr_drain = (self._rr_drain + k + 1) \
                        % self.n_stages
                    return got
            if all(p.saw_stop for p in self._packers):
                return None
            if not block:
                return None
            with self._work:
                self._work.wait(0.02)

    def _collect_round(self):
        """Fill a round: block for the first microbatch, then greedily
        take whatever the shards have, never waiting once at least one
        microbatch is held (the packer's latency-over-occupancy policy,
        lifted to rounds).  Short rounds are padded with empty slots.
        Each microbatch's ``pack`` span is the packer's: the wait for
        the first row lies outside every span."""
        packs: List[Tuple[list, int]] = []
        while len(packs) < self.round_microbatches:
            got = self._next_pack(block=not packs)
            if got is None:
                break
            packs.append(got)
        return packs or None

    def _dispatch_round(self, packs) -> None:
        tracer = self.tracer
        k = len(packs)
        buf = np.zeros(self._round_shape, np.int8)
        for m, (rows, _filled) in enumerate(packs):
            for req, roff, moff, take in rows:
                buf[m, moff:moff + take] = req.images[roff:roff + take]
        # the §V-A cross-device credit: one per microbatch between
        # dispatch and delivery, across the whole mesh
        # (admission.wait_seconds_total accrues the blocked time)
        with tracer.span("credit_wait", "admission", microbatches=k):
            for _ in range(k):
                if not self.admission.acquire():
                    raise AdmissionError(
                        "admission controller closed mid-serve")
        x = jax.device_put(buf, self._replicated)
        with tracer.span("dispatch", "dispatch", microbatches=k):
            logits = self._fn(self.params, x)
        t = self._clock()
        seq = self._round_count + 1  # only this thread advances the count
        for rows, _filled in packs:
            stamp_launch(rows, t, seq)
        with self._lock:
            self._round_count = seq
            self._mb_count += k
            self._padded_rows += sum(
                self.microbatch - filled for _rows, filled in packs)
            self._empty_microbatches += self.round_microbatches - k
            depth = sum(p.depth_hint for p in self._packers)
            # rebase on `is not None` (an injected clock can start at
            # 0.0) — mirrors the CnnServingEngine depth-sampling fix
            self._depth_samples.append(
                (t - self._t0 if self._t0 is not None else 0.0, depth))
        if tracer.enabled:
            # the sharded in-flight/round view: one async round span plus
            # a per-stage round annotation (stage programs run inside ONE
            # staged dispatch, so per-stage host timing does not exist —
            # the args carry the per-stage plan words instead)
            tracer.begin("round", "in_flight", seq, microbatches=k)
            tracer.instant(
                "stage_round", "round", round=seq, microbatches=k,
                stage_hbm_words_per_image=[
                    s.hbm_words_per_image for s in self.partition.stages])
            tracer.counter("queue_depth", depth)
        self.metrics.counter("serving_rounds").inc()
        self.metrics.counter("serving_microbatches").inc(k)
        self.metrics.counter("serving_empty_microbatches").inc(
            self.round_microbatches - k)
        self._inflight.put((logits, packs, k, seq))

    def _complete_loop(self) -> None:
        try:
            while True:
                item = self._inflight.get()
                if item is None:
                    break
                logits, packs, k, seq = item
                arr = np.asarray(jax.block_until_ready(logits))
                self.admission.release(k)
                now = self._clock()
                if self.tracer.enabled:
                    self.tracer.end("round", "in_flight", seq)
                finished: List[CnnRequest] = []
                with self.tracer.span("deliver", "delivery", seq=seq):
                    for m, (rows, _filled) in enumerate(packs):
                        for req, roff, moff, take in rows:
                            if req._deliver(roff, arr[m, moff:moff + take],
                                            now):
                                finished.append(req)
                if finished:
                    lat_hist = self.metrics.histogram("serving_latency_ms")
                    with self._lock:
                        for req in finished:
                            self._latencies.append(req.latency_s)
                            self._images_done += req.n
                            self._requests_done += 1
                            self._request_rows.append({
                                "rid": req.rid, "images": req.n,
                                "latency_ms": 1e3 * req.latency_s,
                                "hbm_words": req.hbm_words,
                            })
                        self._t_last = now
                        self._outstanding -= len(finished)
                        self._lock.notify_all()
                    for req in finished:
                        lat_hist.observe(1e3 * req.latency_s)
                        self.metrics.counter("serving_requests_done").inc()
                        self.metrics.counter(
                            "serving_images_done").inc(req.n)
                        if self.tracer.enabled:
                            self.tracer.end("request", "request", req.rid,
                                            launch_seq=req.launch_seq)
        except BaseException as exc:                 # pragma: no cover
            self._fail(exc)

    # -- failure plumbing (mirrors CnnServingEngine) -------------------------

    def _reject(self, req: CnnRequest) -> None:
        """Back out a request that never entered a shard queue (wall_s,
        shard counts and the submitted counter were not yet advanced —
        they move post-enqueue); close its trace span."""
        with self._lock:
            self._outstanding -= 1
            self._lock.notify_all()
        if self.tracer.enabled:
            self.tracer.end("request", "request", req.rid, rejected=True)

    def _fail(self, exc: BaseException) -> None:
        self._accepting = False
        with self._lock:
            if self._error is None:
                self._error = exc
            self._lock.notify_all()
        self.admission.close()
        with self._work:
            self._work.notify_all()
        self._sweep_queues(exc)
        for p in self._packers:
            p.fail_cursor(exc)

    def _sweep_queues(self, exc: BaseException) -> None:
        for q in list(self._queues) + [self._inflight]:
            while True:
                try:
                    item = q.get_nowait()
                except queue.Empty:
                    break
                if isinstance(item, CnnRequest):
                    item._fail(exc)
                elif isinstance(item, tuple):
                    for rows, _filled in item[1]:
                        for req, *_ in rows:
                            req._fail(exc)
                else:
                    q.put(item)
                    break
