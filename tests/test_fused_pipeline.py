"""The stage-6 fused execution path: one jitted XLA dispatch per run.

Contract under test (compiler stage 6 + runtime/pipeline.py):

  * ``backend="fused"`` executes the whole pipeline as ONE compiled
    program and is bit-identical to the ``backend="eager"`` per-layer
    walk — on a net with genuinely mixed bindings, including
    ``res_block_int8``-fused residual blocks and streamed weight tiers;
  * the trace's stats template makes fused reports equal eager reports
    (post-hoc aggregation — engines return shape-static stats);
  * traces are cached per input shape on the CompiledPipeline: a warm
    shape never retraces, a new batch size retraces exactly once;
  * concurrent ``run()``\\ s on one pipeline keep their reports separate
    (per-run ExecutionReport, frozen EngineContext, stateless engines).
"""
import threading

import jax
import jax.numpy as jnp
import pytest

from repro import compiler
from repro.compiler import TPU_INTERPRET
from repro.configs.cnn import mini_resnet18
from repro.models.cnn import cnn_forward, cnn_input_shape, init_cnn_params
from repro.runtime.pipeline import PipelineExecutor

MINI = mini_resnet18(hw=16, width=32)


@pytest.fixture(scope="module")
def setup():
    cp = compiler.compile(MINI, TPU_INTERPRET)
    params = init_cnn_params(jax.random.PRNGKey(0), MINI)
    x = jax.random.randint(jax.random.PRNGKey(1), cnn_input_shape(MINI, 2),
                           -127, 128, jnp.int8)
    return cp, params, x


def test_fused_is_bit_identical_to_eager_and_reference(setup):
    """The golden contract: fusing the dispatch into one XLA program
    changes performance, never a single output bit — checked against
    both the eager walk and the functional jnp reference, on a plan
    that binds fused residual blocks AND streams several layers."""
    cp, params, x = setup
    assert cp.block_assignments            # res_block_int8 genuinely bound
    assert cp.streamed_names               # and weights genuinely stream
    ref = cnn_forward(params, MINI, x)
    fused, rf = cp.run(params, x, backend="fused")
    eager, re_ = cp.run(params, x, backend="eager")
    assert bool(jnp.all(fused == eager))
    assert bool(jnp.all(fused == ref))
    # and the reports agree entry-for-entry (same stats, same order)
    assert rf.layers == re_.layers
    assert rf.total_hbm_words == re_.total_hbm_words > 0


def test_fused_trace_cache_one_retrace_per_shape(setup):
    """Stage-6 traces are cached per (shape, dtype): warm shapes reuse
    the compiled program; a second batch size retraces exactly once."""
    cp, params, x = setup
    cp2 = compiler.compile(MINI, TPU_INTERPRET)    # fresh, empty cache
    assert cp2.trace_count == 0
    ex = PipelineExecutor(cp2)
    ex.run(params, x)
    assert cp2.trace_count == 1
    ex.run(params, x)                              # warm: no retrace
    ex.run(params, x)
    assert cp2.trace_count == 1
    ex.run(params, x[:1])                          # new batch: one retrace
    assert cp2.trace_count == 2
    ex.run(params, x[:1])
    assert cp2.trace_count == 2
    # executors share the pipeline's cache — a new executor never
    # recompiles a shape the pipeline has already traced
    PipelineExecutor(cp2).run(params, x)
    assert cp2.trace_count == 2


def test_fused_reports_scale_with_batch(setup):
    """Each shape's trace carries its own stats template: Eq. 2 words
    scale with the traced batch, never leak across shapes."""
    cp, params, x = setup
    per_image = sum(cp.plan.hbm_words_per_image().values())
    _, r2 = cp.run(params, x)
    _, r1 = cp.run(params, x[:1])
    assert r2.total_hbm_words == 2 * per_image
    assert r1.total_hbm_words == 1 * per_image


def test_concurrent_runs_do_not_cross_reports(setup):
    """Re-entrancy under the fused path: interleaved runs on ONE
    compiled pipeline from multiple threads produce independent,
    correct reports (the batched-serving prerequisite)."""
    cp, params, x = setup
    per_image = sum(cp.plan.hbm_words_per_image().values())
    ex = PipelineExecutor(cp)
    ex.run(params, x)                   # pre-trace batch 2
    ex.run(params, x[:1])               # pre-trace batch 1
    results = {}

    def worker(name, images):
        logits, report = ex.run(params, images)
        results[name] = (logits, report)

    threads = [threading.Thread(target=worker, args=(f"b2-{i}", x))
               for i in range(2)]
    threads += [threading.Thread(target=worker, args=(f"b1-{i}", x[:1]))
                for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    ref2 = cnn_forward(params, MINI, x)
    for name, (logits, report) in results.items():
        batch = 2 if name.startswith("b2") else 1
        assert report.images == batch, name
        assert len(report.layers) == len(cp.plan.schedules), name
        assert report.total_hbm_words == batch * per_image, name
        assert bool(jnp.all(logits == ref2[:batch])), name


def test_block_granular_eq2_rows(setup):
    """Fused ``res_block_int8`` units are first-class in the traffic
    cross-check: the report carries one Eq. 2 row per block unit, whose
    executed streamed words equal the plan-side
    ``BlockAssignment.hbm_words_per_image`` times the batch — on both
    backends."""
    cp, params, x = setup
    batch = int(x.shape[0])
    for backend in ("fused", "eager"):
        _, rep = cp.run(params, x, backend=backend)
        assert rep.block_assignments == cp.block_assignments
        rows = rep.block_rows()
        assert {r["block"] for r in rows} == set(cp.block_table())
        for row in rows:
            b = cp.block_for(row["block"])
            assert row["engine"] == b.engine
            assert row["members"] == list(b.members)
            assert row["hbm_words"] == batch * b.hbm_words_per_image
            assert row["hbm_words_per_image"] == b.hbm_words_per_image
            assert row["plan_hbm_words_per_image"] == b.hbm_words_per_image
        assert rep.hbm_block_words == {
            b.block: batch * b.hbm_words_per_image
            for b in cp.block_assignments}
        # block words are a subset of (not additional to) the layer total
        assert sum(rep.hbm_block_words.values()) <= rep.total_hbm_words
    # at least one block genuinely streams on this plan, or the test
    # proves nothing
    assert any(b.hbm_words_per_image for b in cp.block_assignments)


def test_unknown_backend_rejected(setup):
    cp, params, x = setup
    with pytest.raises(ValueError, match="backend"):
        PipelineExecutor(cp, backend="rtl")


def test_fused_engine_override_traces_once(setup):
    """A user engine override is traced exactly once per shape — the
    fused program embeds its computation, and warm runs never re-enter
    Python engine code."""
    cp, params, x = setup
    calls = []
    builtin = compiler.get_engine("stream_matmul")

    @compiler.register_engine("fc_probe", priority=99)
    class ProbeFCEngine:
        def supports(self, spec):
            return builtin.supports(spec)

        def vmem_bytes(self, spec, sched):
            return builtin.vmem_bytes(spec, sched)

        def run(self, ctx, sched, p, xx, act):
            calls.append(sched.spec.name)
            return builtin.run(ctx, sched, p, xx, act)

    try:
        probed = compiler.compile(MINI, TPU_INTERPRET)
        assert probed.engine_table()["fc"] == "fc_probe"
        out1, _ = probed.run(params, x)
        out2, _ = probed.run(params, x)
        assert calls == ["fc"]                 # one trace, zero re-entries
        assert bool(jnp.all(out1 == out2))
    finally:
        assert compiler.unregister_engine("fc_probe") is not None
