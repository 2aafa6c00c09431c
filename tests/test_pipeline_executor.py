"""End-to-end tests for the compiled HBM-streaming pipeline.

The contract under test (compiler + runtime/pipeline.py): executing a CNN
under a compiled pipeline — any mix of pinned and HBM-streamed weight
buffers, each layer bound to a registered engine — is bit-identical to
the functional jnp reference, and the executor's Eq. 2 traffic accounting
agrees with the plan analytics and the §V-A fifo_sim prediction machinery.
"""
import jax
import jax.numpy as jnp
import pytest

from repro import compiler
from repro.compiler import TPU_INTERPRET
from repro.configs.cnn import mini_resnet18
from repro.core import fifo_sim
from repro.models.cnn import cnn_forward, cnn_input_shape, init_cnn_params
from repro.runtime.pipeline import PipelineExecutor, execute_cnn

MINI = mini_resnet18(hw=32, width=32)
# the TPU_INTERPRET target models a smaller device -> Algorithm 1 must
# offload (the old tb_budget=500, bram_m20ks=40 keyword defaults)
COMPILED = compiler.compile(MINI, TPU_INTERPRET)
PLAN = COMPILED.plan


@pytest.fixture(scope="module")
def mini_setup():
    params = init_cnn_params(jax.random.PRNGKey(0), MINI)
    x = jax.random.randint(jax.random.PRNGKey(1), cnn_input_shape(MINI, 2),
                           -127, 128, jnp.int8)
    ref = cnn_forward(params, MINI, x)
    return params, x, ref


def test_algorithm1_offloads_mini():
    """Eq. 1 scores go positive on multi-M20K buffers: the mini net on the
    TPU_INTERPRET target must genuinely stream several layers."""
    assert len(PLAN.streamed) >= 3
    assert len(PLAN.pinned) >= 1                  # and it stays hybrid
    for s in PLAN.streamed:
        assert s.pc is not None


def test_streamed_execution_bit_identical(mini_setup):
    params, x, ref = mini_setup
    out, report = COMPILED.run(params, x)
    assert bool(jnp.all(out == ref))
    assert report.streamed_layer_count == len(PLAN.streamed)


def test_pinned_execution_bit_identical(mini_setup):
    params, x, ref = mini_setup
    pinned = COMPILED.with_offload([])
    out, report = pinned.run(params, x)
    assert bool(jnp.all(out == ref))
    assert report.total_hbm_words == 0


def test_pinned_and_streamed_agree(mini_setup):
    """The tier decision is performance-only: flipping layers between
    M20K and HBM tiers never changes a single output bit."""
    params, x, _ = mini_setup
    a, _ = COMPILED.with_offload([]).run(params, x)
    names = list(PLAN.streamed_names) + ["fc"]    # exercise fc fifo path
    b, rep = COMPILED.with_offload(names).run(params, x)
    assert bool(jnp.all(a == b))
    assert "fc" in rep.hbm_weight_words


def test_traffic_accounting_matches_plan(mini_setup):
    """Executed Eq. 2 traffic == plan analytics: words_per_row * out_h
    per image, for every streamed layer."""
    params, x, _ = mini_setup
    batch = int(x.shape[0])
    _, report = COMPILED.run(params, x)
    expected = {name: words * batch
                for name, words in PLAN.hbm_words_per_image().items()}
    assert report.hbm_weight_words == expected


def test_engines_ran_as_compiled(mini_setup):
    """The compile-time engine table IS what executes: every dispatched
    layer ran on exactly the engine it was bound to — no dispatch-time
    fallbacks."""
    params, x, _ = mini_setup
    _, report = COMPILED.run(params, x)
    table = COMPILED.engine_table()
    used = report.engines_used()
    assert used == {name: table[name] for name in used}
    assert set(used) == set(table)                # every layer dispatched


def test_executor_is_reentrant(mini_setup):
    """Per-run EngineContext threading: interleaved runs on ONE executor
    never cross-contaminate reports (the batched-serving prerequisite)."""
    params, x, _ = mini_setup
    ex = PipelineExecutor(COMPILED)
    _, r1 = ex.run(params, x)
    _, r2 = ex.run(params, x[:1])
    assert r1.images == 2 and r2.images == 1
    assert len(r1.layers) == len(r2.layers) == len(PLAN.schedules)
    assert r1.total_hbm_words == 2 * sum(PLAN.hbm_words_per_image().values())
    assert r2.total_hbm_words == sum(PLAN.hbm_words_per_image().values())


def test_stalls_match_fifo_sim(mini_setup):
    """The report's stall prediction is exactly the §V-A credit-mode
    discrete-event sim over the plan's per-row word demands."""
    params, x, _ = mini_setup
    _, report = COMPILED.run(params, x)
    predicted = report.fifo_prediction(outputs_needed=8)
    cfg, scale = PLAN.sim_config(outputs_needed=8)
    direct = fifo_sim.simulate(cfg, "credit")
    assert predicted.stall_cycles == direct.stall_cycles
    assert predicted.completed and not predicted.deadlocked
    # tail engine consumed exactly its demand when the run completed
    tail_wpa = cfg.weights_per_act[-1]
    assert direct.per_layer_weight_words[-1] == tail_wpa * cfg.outputs_needed
    # sim word demands are the plan's Eq. 2 per-row words (scaled)
    wpr = [s.weight_words_per_row for s in PLAN.streamed]
    assert cfg.weights_per_act == tuple(max(1, w // scale) for w in wpr)


def test_fifo_sim_exact_mode_matches_scaled_verdict():
    """fifo_sim fidelity regression: simulating the FULL Eq. 2 word
    streams (word_scale=1, no downscaling) reaches the same completion +
    stall verdict as the auto-scaled fast path, on a small streamed
    config."""
    small = compiler.compile(mini_resnet18(hw=16, width=32), TPU_INTERPRET)
    assert small.streamed_names                   # genuinely streams
    scaled = small.predict_stalls(outputs_needed=4)
    exact = small.predict_stalls(outputs_needed=4, word_scale=1)
    _, auto_scale = small.plan.sim_config(outputs_needed=4)
    assert auto_scale > 1                         # the fast path DID scale
    assert exact.completed and scaled.completed
    assert not exact.deadlocked and not scaled.deadlocked
    assert (exact.stall_cycles > 0) == (scaled.stall_cycles > 0)


def test_fifo_sim_exact_mode_full_resnet18():
    """fifo_sim fidelity at FULL scale: the complete ResNet-18 Eq. 2
    word streams (word_scale=1 — up to ~236k words per activation, no
    downscaling) simulate exactly on the burst-aggregated credit path,
    reaching the same completion/stall verdict as the auto-scaled fast
    path.  This is the run the per-word reference loop cannot finish in
    CI time (~10^7 simulated cycles)."""
    from repro.configs import CNN_CONFIGS
    target = compiler.NX2100.replace(bram_m20ks=3000)   # forces streaming
    cp = compiler.compile(CNN_CONFIGS["resnet18"], target)
    assert len(cp.streamed_names) >= 3
    wpr = [s.weight_words_per_row for s in cp.plan.streamed]
    assert max(wpr) > 100_000                     # genuinely full streams
    exact = cp.predict_stalls(outputs_needed=2, word_scale=1)
    scaled = cp.predict_stalls(outputs_needed=2)
    _, auto_scale = cp.plan.sim_config(outputs_needed=2)
    assert auto_scale > 1                         # the fast path DID scale
    assert exact.completed and scaled.completed
    assert not exact.deadlocked and not scaled.deadlocked
    assert (exact.stall_cycles > 0) == (scaled.stall_cycles > 0)
    # every layer consumed its full exact demand: wpr * 2 activations
    assert exact.per_layer_weight_words == [w * 2 for w in wpr]


def test_executor_runs_full_family_reduced():
    """The compiled pipeline handles the paper's other topologies (reduced
    scale) — including MobileNet, whose depthwise layers now run through
    the registered dwconv engine instead of silently falling back."""
    from repro.configs import CNN_CONFIGS
    target = TPU_INTERPRET.replace(tb_budget=200, bram_m20ks=10_000)
    for name in ("resnet18", "vgg16", "mobilenetv1"):
        cfg = CNN_CONFIGS[name].reduced()
        cp = compiler.compile(cfg, target)
        params = init_cnn_params(jax.random.PRNGKey(0), cfg)
        x = jax.random.randint(jax.random.PRNGKey(1),
                               cnn_input_shape(cfg, 2), -127, 128, jnp.int8)
        ref = cnn_forward(params, cfg, x)
        out, _ = execute_cnn(cp, params, x)
        assert bool(jnp.all(out == ref)), name


def test_fc_wide_k_int8_exact(rng_key):
    """Wide fc heads (c_in >= 2048, as resnet50/vgg16 stream) must stay
    exact: int8 dot-product sums exceed f32's 2^24 integer range, so the
    matmul kernels have to accumulate in int32 (regression for the fc
    bit-identity contract)."""
    from repro.kernels.stream_matmul.ops import stream_matmul
    from repro.kernels.stream_matmul.ref import stream_matmul_ref
    k1, k2 = jax.random.split(rng_key)
    # adversarial magnitudes: |sum| ~ 2048*127*127 >> 2^24
    x = jax.random.choice(k1, jnp.array([-127, 127], jnp.int8), (8, 2048))
    w = jax.random.choice(k2, jnp.array([-127, 127], jnp.int8), (2048, 128))
    ref = stream_matmul_ref(x, w)
    for mode in ("stream", "fifo", "pinned"):
        out = stream_matmul(x, w, mode=mode, bk=512, interpret=True)
        assert out.dtype == jnp.int32
        assert bool(jnp.all(out == ref)), mode


def test_single_streamed_conv_matches_oracle(rng_key):
    """The HBM-streamed conv kernel is exact against the jnp oracle for
    every double-buffer depth."""
    from repro.kernels.conv2d_int8.ops import conv2d_int8
    from repro.kernels.conv2d_int8.ref import conv2d_int8_ref
    x = jax.random.randint(rng_key, (2, 12, 12, 8), -127, 128, jnp.int8)
    w = jax.random.randint(rng_key, (3, 3, 8, 16), -20, 21, jnp.int8)
    ref = conv2d_int8_ref(x, w, stride=1)
    for nb in (1, 2, 4):
        out = conv2d_int8(x, w, stride=1, stream=True, n_buffers=nb,
                          interpret=True)
        assert bool(jnp.all(out == ref)), nb


def test_depthwise_kernel_matches_reference(rng_key):
    """The grouped depthwise Pallas engine (pinned + streamed tiers) is
    exact against the jnp feature-group reference, for both strides."""
    k1, k2 = jax.random.split(rng_key)
    for stride in (1, 2):
        x = jax.random.randint(k1, (2, 12, 12, 8), -127, 128, jnp.int8)
        w = jax.random.randint(k2, (3, 3, 1, 8), -20, 21, jnp.int8)
        ref = jax.lax.conv_general_dilated(
            x, w, window_strides=(stride, stride), padding="SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=8, preferred_element_type=jnp.int32)
        from repro.kernels.conv2d_int8.ops import dwconv_int8
        for stream in (False, True):
            out = dwconv_int8(x, w, stride=stride, stream=stream,
                              interpret=True)
            assert bool(jnp.all(out == ref)), (stride, stream)
