"""Tests for the staged compile() API: Target descriptors, the LayerEngine
registry, VMEM budget validation/re-placement, and the engine table.

The contract: ``compile(cfg, target)`` binds every layer to a registered
engine BEFORE execution (the table is inspectable and is exactly what
runs), validates every binding against the target's VMEM budget —
re-placing pinned layers to the HBM tier when only their streamed working
set fits, raising with a per-layer report when neither tier fits — and
the registry is the extension surface: user engines plug in (and out)
without touching the compiler.
"""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro import compiler
from repro.compiler import NX2100, TPU_INTERPRET, Target, TargetBudgetError
from repro.configs import CNN_CONFIGS
from repro.configs.cnn import mini_resnet18
from repro.models.cnn import cnn_forward, cnn_input_shape, init_cnn_params

MINI = mini_resnet18(hw=32, width=32)


# ---------------------------------------------------------------------------
# Target descriptors
# ---------------------------------------------------------------------------


def test_target_presets():
    assert NX2100.tb_budget == 1980 and NX2100.bram_m20ks == 6847
    assert NX2100.interpret is None                # auto backend
    assert TPU_INTERPRET.interpret is True         # forced interpreter
    assert compiler.get_target("nx2100") is NX2100
    with pytest.raises(KeyError):
        compiler.get_target("gpu3000")


def test_target_validation():
    with pytest.raises(ValueError):
        Target(name="bad", tb_budget=100, bram_m20ks=100, backend="vhdl")
    with pytest.raises(ValueError):
        Target(name="bad", tb_budget=0, bram_m20ks=100)


def test_target_replace_derives_variant():
    t = NX2100.replace(burst=16)
    assert t.burst == 16 and t.name == "nx2100*"
    assert NX2100.burst == 8                       # frozen original


# ---------------------------------------------------------------------------
# engine table: bindings are decided (and visible) at compile time
# ---------------------------------------------------------------------------


def test_engine_table_covers_every_layer():
    cp = compiler.compile(MINI, TPU_INTERPRET)
    table = cp.engine_table()
    assert set(table) == {l.name for l in MINI.layers}
    assert table["fc"] == "stream_matmul"
    # the stem conv + following maxpool fuse into ONE schedulable unit
    assert table["stem"] == "stem_pool_int8"
    assert table["maxpool"] == "stem_pool_int8"
    # the standalone pooling topology node keeps its own engine binding
    assert table["gap"] == "global_avgpool_int8"
    # every residual-block member is bound at BLOCK granularity (the
    # fused res_block_int8 unit — or the scanned run engine when the
    # block sits in a homogeneous run); the stem pair is a unit too
    in_blocks = {m for b in cp.block_assignments for m in b.members}
    assert in_blocks == set(table) - {"gap", "fc"}
    res_members = in_blocks - {"stem", "maxpool"}
    assert all(table[name] in ("res_block_int8", "scanned_res_block_int8")
               for name in res_members)
    # vmem report covers the same layers, all within budget
    report = cp.vmem_report()
    assert set(report) == set(table)
    assert all(0 < v <= TPU_INTERPRET.vmem_bytes for v in report.values())
    assert "engine" in cp.describe() and "stream_matmul" in cp.describe()


def test_block_units_bound_and_costed():
    """Stage 4 groups each residual block into one schedulable unit: the
    block table covers exactly the s{i}b{j} groups, each unit's VMEM
    cost is the sum of its members plus the identity buffer plus the
    widest intermediate activation map, and its Eq. 2 words are the
    streamed members' plan analytics."""
    from repro.configs.cnn import residual_blocks, stem_unit
    cp = compiler.compile(MINI, TPU_INTERPRET)
    blocks = {b.name: b for b in residual_blocks(MINI)}
    su = stem_unit(MINI)
    assert set(cp.block_table()) == set(blocks) | {su.name}
    eng = compiler.get_engine("conv2d_int8")
    for ba in cp.block_assignments:
        if ba.block == su.name:            # the stem pair: costed below
            continue
        blk = blocks[ba.block]
        assert ba.members == tuple(m.name for m in blk.members)
        scheds = cp.plan.schedules_for(ba.members)
        member_sum = sum(eng.vmem_bytes(s.spec, s) for s in scheds)
        first = blk.convs[0]
        widest = max(m.out_h * m.out_w * m.c_out for m in blk.members)
        assert ba.vmem_bytes == member_sum + first.in_h * first.in_w \
            * first.c_in + widest
        assert ba.vmem_bytes <= TPU_INTERPRET.vmem_bytes
        assert ba.hbm_words_per_image == sum(
            s.weight_words_per_image for s in scheds if s.streamed)
    # block_for resolves by block name and by member name
    ba = cp.block_for("s1b0")
    assert ba is not None and cp.block_for("s1b0c1") is ba
    # the stem conv + maxpool pair binds as its own fused unit
    sa = cp.block_for(su.name)
    assert sa is not None and sa.engine == "stem_pool_int8"
    assert sa.members == ("stem", "maxpool")
    assert cp.block_for("maxpool") is sa


def test_block_unit_over_vmem_falls_back_to_per_layer():
    """A block whose summed working set exceeds the target's VMEM budget
    is NOT bound as a unit — its layers keep their per-layer bindings
    (and per-layer validation still governs them)."""
    cp = compiler.compile(MINI, REPLACE_TARGET)
    assert cp.block_assignments == ()
    assert "res_block_int8" not in cp.engine_table().values()


def test_dwconv_layers_bind_to_registered_engine():
    """MobileNet depthwise layers get the grouped Pallas engine — no
    silent jnp fallback anywhere in the table — and execution is
    bit-identical to the reference."""
    cfg = CNN_CONFIGS["mobilenetv1"].reduced()
    cp = compiler.compile(cfg, TPU_INTERPRET.replace(bram_m20ks=10_000))
    table = cp.engine_table()
    dw = [l.name for l in cfg.layers if l.kind == "dwconv"]
    assert dw and all(table[name] == "dwconv_int8" for name in dw)
    assert "jnp_ref" not in table.values()

    params = init_cnn_params(jax.random.PRNGKey(0), cfg)
    x = jax.random.randint(jax.random.PRNGKey(1), cnn_input_shape(cfg, 2),
                           -127, 128, jnp.int8)
    out, report = cp.run(params, x)
    assert bool(jnp.all(out == cnn_forward(params, cfg, x)))
    assert all(report.engines_used()[name] == "dwconv_int8" for name in dw)


def test_streamed_dwconv_accounts_eq2_traffic():
    """A depthwise layer forced onto the HBM tier streams through the
    grouped kernel's DMA ring and its Eq. 2 words hit the report."""
    cfg = CNN_CONFIGS["mobilenetv1"].reduced()
    cp = compiler.compile(cfg, TPU_INTERPRET.replace(bram_m20ks=10_000))
    dw = next(l.name for l in cfg.layers if l.kind == "dwconv")
    streamed = cp.with_offload([dw])
    params = init_cnn_params(jax.random.PRNGKey(0), cfg)
    x = jax.random.randint(jax.random.PRNGKey(1), cnn_input_shape(cfg, 2),
                           -127, 128, jnp.int8)
    ref = cnn_forward(params, cfg, x)
    out, report = streamed.run(params, x)
    assert bool(jnp.all(out == ref))
    sched = streamed.plan.schedule_for(dw)
    expected = sched.weight_words_per_image * int(x.shape[0])
    assert report.hbm_weight_words == {dw: expected}


# ---------------------------------------------------------------------------
# VMEM budget: re-placement and rejection
# ---------------------------------------------------------------------------


# A target whose BRAM is big enough that Algorithm 1 streams nothing
# (leaving the full chain pool free), but whose VMEM ceiling the four
# widest conv layers of stage 1 (90112 / 83968 B pinned, 32768 / 26624 B
# streamed — the stride-2 lead conv holds two column phases in its line
# buffer) only clear in the HBM tier, while every other layer (at most
# 60416 B pinned) fits and no block unit does (the cheapest, the fully
# streamed s1b1, needs 61440 B) — the canonical stage-5 re-placement
# scenario.
REPLACE_TARGET = TPU_INTERPRET.replace(bram_m20ks=10_000, vmem_bytes=61_000)
WIDE_LAYERS = ("s1b0c0", "s1b0c1", "s1b1c0", "s1b1c1")


def test_compile_replaces_overbudget_pinned_layers():
    """Pinned layers whose working set only fits when streamed are moved
    to the HBM tier by stage 5 — and the pipeline still executes
    bit-identically."""
    cp = compiler.compile(MINI, REPLACE_TARGET)
    assert cp.replaced == WIDE_LAYERS
    for name in WIDE_LAYERS:
        assert cp.assignment_for(name).mode == "hbm"
    assert max(cp.vmem_report().values()) <= REPLACE_TARGET.vmem_bytes

    params = init_cnn_params(jax.random.PRNGKey(0), MINI)
    x = jax.random.randint(jax.random.PRNGKey(1), cnn_input_shape(MINI, 2),
                           -127, 128, jnp.int8)
    out, report = cp.run(params, x)
    assert bool(jnp.all(out == cnn_forward(params, MINI, x)))
    assert set(report.hbm_weight_words) == set(WIDE_LAYERS)


def test_with_offload_is_strict_no_silent_replacement():
    """A caller-forced offload set is honored verbatim: stage 5 must NOT
    quietly re-stream forced-pinned layers — on a target where a pinned
    layer cannot fit, the recompile fails loudly instead (the pinned-vs-
    hybrid benchmark comparison depends on this)."""
    cp = compiler.compile(MINI, REPLACE_TARGET)   # compile() may re-place...
    assert cp.replaced == WIDE_LAYERS
    with pytest.raises(TargetBudgetError) as ei:
        cp.with_offload([])                       # ...with_offload may not
    assert set(WIDE_LAYERS) <= set(ei.value.offenders)
    assert "forced weight tier" in str(ei.value)
    # and where everything fits pinned, the forced set IS the result
    roomy = compiler.compile(MINI, TPU_INTERPRET).with_offload([])
    assert roomy.streamed_names == () and roomy.replaced == ()


def test_compile_rejects_impossible_vmem_budget():
    """When a layer fits in NEITHER tier, compile() fails up front with
    the full per-layer VMEM report — not at dispatch time."""
    tiny = TPU_INTERPRET.replace(vmem_bytes=1024)
    with pytest.raises(TargetBudgetError) as ei:
        compiler.compile(MINI, tiny)
    err = ei.value
    assert err.offenders                            # names the layers
    assert set(err.vmem_report) == {l.name for l in MINI.layers}
    assert str(err.target.vmem_bytes) in str(err)


def test_pinned_tier_costs_more_vmem_than_streamed():
    """The accounting the re-placement pass relies on: for a conv layer,
    the pinned working set dominates the streamed one (whole kernel vs
    n_buffers ring)."""
    cp = compiler.compile(MINI, TPU_INTERPRET)
    sched = cp.plan.schedule_for("s1b1c1")
    eng = compiler.get_engine("conv2d_int8")
    pinned = dataclasses.replace(sched, mode="pinned")
    streamed = dataclasses.replace(sched, mode="hbm")
    assert eng.vmem_bytes(sched.spec, pinned) \
        > eng.vmem_bytes(sched.spec, streamed)


# ---------------------------------------------------------------------------
# registry: the extension surface round-trips
# ---------------------------------------------------------------------------


def test_engine_registry_override_round_trips():
    """A user engine registered at higher priority takes over the layers
    it claims; unregistering restores the built-in binding — no compiler
    edits either way."""
    calls = []
    builtin = compiler.get_engine("stream_matmul")

    @compiler.register_engine("fc_spy", priority=99)
    class SpyFCEngine:
        def supports(self, spec):
            return builtin.supports(spec)

        def vmem_bytes(self, spec, sched):
            return builtin.vmem_bytes(spec, sched)

        def run(self, ctx, sched, params, x, act):
            calls.append(sched.spec.name)
            return builtin.run(ctx, sched, params, x, act)

    try:
        cp = compiler.compile(MINI, TPU_INTERPRET)
        assert cp.engine_table()["fc"] == "fc_spy"
        params = init_cnn_params(jax.random.PRNGKey(0), MINI)
        x = jax.random.randint(jax.random.PRNGKey(1),
                               cnn_input_shape(MINI, 1), -127, 128, jnp.int8)
        out, _ = cp.run(params, x)
        assert calls == ["fc"]                     # the spy actually ran
        assert bool(jnp.all(out == cnn_forward(params, MINI, x)))
    finally:
        assert compiler.unregister_engine("fc_spy") is not None

    cp = compiler.compile(MINI, TPU_INTERPRET)
    assert cp.engine_table()["fc"] == "stream_matmul"


def test_same_name_override_restores_builtin_on_unregister():
    """Shadowing a built-in under its own name and popping the override
    restores the built-in — the registry is a stack per name, so user
    overrides cannot permanently delete shipped engines."""
    builtin = compiler.get_engine("conv2d_int8")

    @compiler.register_engine("conv2d_int8", priority=50)
    class ShadowEngine:
        def supports(self, spec):
            return builtin.supports(spec)

        def vmem_bytes(self, spec, sched):
            return builtin.vmem_bytes(spec, sched)

        def run(self, ctx, sched, params, x, act):
            return builtin.run(ctx, sched, params, x, act)

    try:
        assert compiler.get_engine("conv2d_int8") is not builtin
    finally:
        popped = compiler.unregister_engine("conv2d_int8")
    assert isinstance(popped, ShadowEngine)
    assert compiler.get_engine("conv2d_int8") is builtin
    table = compiler.compile(MINI, TPU_INTERPRET).engine_table()
    assert table["stem"] == "stem_pool_int8"


def test_replacement_respects_chain_bandwidth():
    """Stage-5 re-placement is bounded by Algorithm 1's hard constraint:
    moving a layer to HBM consumes its p_i*p_o chain feeds from the
    target's pseudo-channel pool.  On a 1-PC target the pool (3 chains)
    cannot feed the over-VMEM layers, so compile() must reject the
    mapping rather than silently oversubscribe the bandwidth the
    throughput model assumes."""
    starved = REPLACE_TARGET.replace(n_pc=1)
    with pytest.raises(TargetBudgetError) as ei:
        compiler.compile(MINI, starved)
    assert "bandwidth" in str(ei.value)
    # the same budgets with the full PC pool compile via re-placement
    assert compiler.compile(MINI, REPLACE_TARGET).replaced == WIDE_LAYERS


def test_fc_as_conv_binding_requires_valid_equivalence():
    """The conv engine SAME-pads while the reference applies fc layers
    VALID: it may only claim fc-as-conv heads whose SAME padding is zero
    (VGG's fc0: 7x7 kernel, 7x7 map, stride 7).  Other fc geometries
    bind to the explicit jnp_ref engine — visible in the table, never a
    wrong-padding execution."""
    from repro.configs.cnn import ConvLayerSpec
    fc0 = next(l for l in CNN_CONFIGS["vgg16"].layers if l.name == "fc0")
    assert compiler.select_engine(fc0).name == "conv2d_int8"
    odd = ConvLayerSpec("fcx", "fc", 3, 3, 8, 16, 1, 7, 7)  # SAME != VALID
    assert compiler.select_engine(odd).name == "jnp_ref"


def test_jnp_bound_layers_never_occupy_hbm_tier():
    """A layer bound to the reference engine (can_stream=False) must not
    hold the HBM tier — plan analytics and fifo_sim would charge Eq. 2
    traffic the engine never executes.  Compile-chosen placements are
    demoted to pinned; caller-forced ones are rejected loudly."""
    from repro.configs.cnn import CNNConfig, ConvLayerSpec
    cfg = CNNConfig("tiny-oddfc", (
        ConvLayerSpec("c0", "conv", 3, 3, 3, 8, 1, 8, 8),
        ConvLayerSpec("fcx", "fc", 3, 3, 8, 16, 1, 8, 8),  # SAME != VALID
    ), num_classes=16)
    plan = compiler.plan_pipeline(cfg, TPU_INTERPRET).with_offload(["fcx"])
    demoted = compiler.finalize(plan, TPU_INTERPRET)
    assert demoted.engine_table()["fcx"] == "jnp_ref"
    assert demoted.assignment_for("fcx").mode == "pinned"
    assert "fcx" not in demoted.streamed_names
    with pytest.raises(compiler.CompileError, match="cannot stream"):
        compiler.finalize(plan, TPU_INTERPRET, replace=False)


def test_unknown_engine_lookup_raises():
    with pytest.raises(KeyError):
        compiler.get_engine("winograd9000")


def test_selection_order_is_priority_then_age():
    names = list(compiler.registered_engines())
    assert names.index("jnp_ref") == len(names) - 1   # the safety net last


# ---------------------------------------------------------------------------
# plan data model
# ---------------------------------------------------------------------------


def test_schedule_for_dict_lookup():
    cp = compiler.compile(MINI, TPU_INTERPRET)
    for s in cp.schedules:
        assert cp.plan.schedule_for(s.spec.name) is s
    with pytest.raises(KeyError):
        cp.plan.schedule_for("nonexistent")
    # derived plans get fresh, correct indices
    flipped = cp.plan.with_offload(["fc"])
    assert flipped.schedule_for("fc").streamed
    assert not cp.plan.schedule_for("fc").streamed
