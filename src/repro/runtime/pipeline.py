"""Layer-pipelined CNN inference executor — the running H2PIPE system.

``repro.compiler.compile(cfg, target)`` decides, per layer (or per fused
residual block), which registered
:class:`~repro.compiler.engines.LayerEngine` runs it and whether its
weight buffer lives on chip or streams from HBM; this module *executes*
a CNN under that :class:`CompiledPipeline`, through one of two backends:

``backend="fused"`` (default)
    The stage-6 path: the whole engine table is closed over
    ``models.cnn.cnn_forward`` and compiled into ONE ``jax.jit`` program
    per (input shape, dtype) — a warm ``run()`` is a single XLA
    dispatch, the software analogue of the paper's point that the whole
    network IS one pipelined circuit.  Traces are cached on the
    ``CompiledPipeline`` (shared across executors and threads).  Stats
    come from the trace: engines return shape-static
    :class:`LayerExecStats` instead of mutating a context, so the single
    trace yields the exact template every warm run's
    :class:`ExecutionReport` replays.

``backend="eager"``
    The per-layer walk: each engine dispatched from Python, one jit
    boundary per engine call.  Bit-identical to the fused path (golden
    test) and handy for debugging a single engine; this is what every
    ``run()`` was before the fused path existed.

The topology is owned end to end by the compiler: maxpool and
global-average-pool are first-class graph nodes bound to their own pool
engines, and residual blocks (basic and bottleneck) fuse to
``res_block_int8`` units — ``models.cnn.cnn_forward`` only walks
``cfg.layers`` and offers every node to the ``engine``/``block_engine``
hooks both backends plug in, so the pipelined execution is the SAME
network the functional reference runs — outputs are bit-identical, and
100% of the graph appears in the engine table and the reports.

The report cross-checks three views of the weight path that the paper
keeps consistent by construction:
  * executed:   streamed words from the traced dispatch counters
                (Eq. 2 traffic);
  * analytic:   the plan's ``weight_words_per_image`` (Eq. 2 formula);
  * simulated:  ``fifo_sim`` credit-mode delivery + tail-stall prediction
                over the same per-row word demands (§V-A).

Re-entrancy: per-run state is confined to the run's own
:class:`ExecutionReport`; the engine context is frozen and engines are
stateless, so concurrent ``run()``\\ s on one executor (or one compiled
pipeline) cannot corrupt each other's accounting.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple, Union

import jax.numpy as jnp

from repro.compiler.engines import EngineContext, LayerExecStats
from repro.compiler.pipeline import (CompiledPipeline, ExecutionReport,
                                     finalize, make_dispatchers)
from repro.core.schedule import PipelinePlan
from repro.kernels.pallas_compat import resolve_interpret
from repro.models.cnn import cnn_forward, init_cnn_params

__all__ = ["PipelineExecutor", "ExecutionReport", "LayerExecStats",
           "execute_cnn"]

Params = Dict[str, Any]

BACKENDS = ("fused", "eager")


class PipelineExecutor:
    """Executes a CNN end-to-end under a :class:`CompiledPipeline`.

    ``interpret=None`` defers to the compiled target's backend (and from
    there to pallas_compat auto-detection), so the same executor runs on
    CPU CI and real TPUs.  A bare :class:`PipelinePlan` (the deprecated
    ``build_pipeline_plan`` output) is accepted and gets engines bound on
    the fly, without target budget enforcement.

    ``backend`` picks the execution strategy: ``"fused"`` (one jitted
    XLA program per input shape, cached on the compiled pipeline) or
    ``"eager"`` (the per-layer dispatch walk) — bit-identical by
    contract.
    """

    def __init__(self, compiled: Union[CompiledPipeline, PipelinePlan], *,
                 interpret: Optional[bool] = None, act_scale: float = 0.05,
                 backend: str = "fused"):
        if isinstance(compiled, PipelinePlan):
            compiled = finalize(compiled, target=None)
        if backend not in BACKENDS:
            raise ValueError(f"unknown backend {backend!r}; "
                             f"expected one of {BACKENDS}")
        self.compiled = compiled
        if interpret is None and compiled.target is not None:
            interpret = compiled.target.interpret
        self.interpret = resolve_interpret(interpret)
        self.act_scale = act_scale
        self.backend = backend

    @property
    def plan(self) -> PipelinePlan:
        return self.compiled.plan

    # -- params -------------------------------------------------------------

    def init_params(self, key) -> Params:
        return init_cnn_params(key, self.plan.cfg)

    # -- execution ----------------------------------------------------------

    def run(self, params: Params, images
            ) -> Tuple[jnp.ndarray, ExecutionReport]:
        """images: [B,H,W,C] int8 -> (logits [B,classes], report)."""
        report = ExecutionReport(plan=self.plan, images=int(images.shape[0]),
                                 block_assignments=self.compiled
                                 .block_assignments,
                                 scan_assignments=self.compiled
                                 .scan_assignments)
        if self.backend == "fused":
            trace = self.compiled.fused_trace(
                params, images, interpret=self.interpret,
                act_scale=self.act_scale)
            logits = trace.fn(params, images)
            report.layers.extend(trace.stats)      # post-hoc aggregation
            return logits, report

        ctx = EngineContext(interpret=self.interpret,
                            act_scale=self.act_scale)
        dispatch, block_dispatch, scan_dispatch = make_dispatchers(
            self.compiled, ctx, report.layers)
        logits = cnn_forward(params, self.plan.cfg, images, engine=dispatch,
                             block_engine=block_dispatch,
                             scan_engine=scan_dispatch)
        return logits, report

    def __call__(self, params: Params, images) -> jnp.ndarray:
        return self.run(params, images)[0]


def execute_cnn(plan: Union[CompiledPipeline, PipelinePlan], params: Params,
                images, *, interpret: Optional[bool] = None,
                backend: str = "fused"
                ) -> Tuple[jnp.ndarray, ExecutionReport]:
    """One-shot convenience: run ``images`` through ``plan``."""
    return PipelineExecutor(plan, interpret=interpret,
                            backend=backend).run(params, images)
