"""JAX CNN models built from the H2PIPE per-layer descriptors.

The paper's accelerator is generated layer-by-layer from ``ConvLayerSpec``s;
we mirror that: ``init_cnn_params`` / ``cnn_forward`` consume the same specs
that drive the placement algorithm (Eq. 1), the memory table (Table I) and
the traffic bound (Eq. 2), so the numbers in the benchmarks refer to the
exact network that runs.

Numerics follow the paper: int8 weights with per-output-channel scales
(int8 fine-tune of an fp32 model); activations int8 with per-tensor scale.
Compute accumulates in int32 on the MXU (jnp path: int8 x int8 -> int32
via preferred_element_type), then requantizes — the Pallas ``conv2d_int8``
kernel implements the same contract.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.cnn import (POOL_KINDS, RELU, CNNConfig, ConvLayerSpec,
                               ResBlockSpec, layer_activations,
                               residual_blocks, stem_unit)
from repro.kernels.pool_int8.ref import (global_avgpool_int8_ref,
                                         maxpool_int8_ref)
from repro.kernels.quant import requant_epilogue, residual_join
from repro.models.layers import maybe_axis, MODEL_AXIS

Params = Dict[str, Any]


def _qscale(key, shape):
    return jnp.full(shape, 0.05, jnp.float32)


def init_conv_layer(key, spec: ConvLayerSpec) -> Params:
    kw, kh = spec.k_w, spec.k_h
    if spec.kind == "dwconv":
        w_shape = (kh, kw, 1, spec.c_in)                    # HWIO depthwise
        c_out = spec.c_in
    else:
        w_shape = (kh, kw, spec.c_in, spec.c_out)
        c_out = spec.c_out
    w = jax.random.randint(key, w_shape, -127, 128, jnp.int8)
    return {
        "w": w,
        "w_scale": _qscale(key, (c_out,)),
        "bias": jnp.zeros((c_out,), jnp.float32),
    }


def conv_layer_specs(spec: ConvLayerSpec) -> Params:
    if spec.kind == "dwconv":
        ax = maybe_axis(spec.c_in, MODEL_AXIS)
        return {"w": P(None, None, None, ax), "w_scale": P(ax), "bias": P(ax)}
    ax = maybe_axis(spec.c_out, MODEL_AXIS)
    return {"w": P(None, None, None, ax), "w_scale": P(ax), "bias": P(ax)}


@functools.partial(jax.jit, static_argnames=("spec", "act_scale", "act"))
def conv_layer_forward(params: Params, spec: ConvLayerSpec, x,
                       act_scale: float = 0.05, act: str = RELU):
    """x: [B,H,W,C] int8.  Returns int8 activations (requantized) after
    the activation ``act`` (``configs.cnn.layer_activations``).

    Jitted (spec is a hashable frozen dataclass) so the dequant/requant
    epilogue compiles to the same fused float ops as the Pallas engines —
    keeping the model path and the kernel path bit-identical around
    round-to-nearest ties."""
    feature_group_count = spec.c_in if spec.kind == "dwconv" else 1
    pad = "SAME" if spec.kind != "fc" else "VALID"
    y = jax.lax.conv_general_dilated(
        x.astype(jnp.int8), params["w"].astype(jnp.int8),
        window_strides=(spec.stride, spec.stride),
        padding=pad,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=feature_group_count,
        preferred_element_type=jnp.int32)
    # requantize to int8 for the next layer engine (the shared epilogue)
    return requant_epilogue(y, params["w_scale"], params["bias"],
                            act_scale=act_scale, act=act)


def init_cnn_params(key, cfg: CNNConfig) -> Params:
    """Parameters for every weighted node; pool nodes (maxpool / GAP) are
    weightless topology engines and get no entry."""
    ks = jax.random.split(key, len(cfg.layers))
    return {l.name: init_conv_layer(k, l)
            for k, l in zip(ks, cfg.layers) if not l.is_pool}


def cnn_param_specs(cfg: CNNConfig) -> Params:
    return {l.name: conv_layer_specs(l) for l in cfg.layers if not l.is_pool}


def pool_forward(spec: ConvLayerSpec, x, act_scale: float = 0.05):
    """The jnp reference for one pooling topology node — the same
    numerics the Pallas pool engines are differential-tested against."""
    if spec.kind == "maxpool":
        return maxpool_int8_ref(x, k=spec.k_h, stride=spec.stride)
    assert spec.kind == "gap", spec.kind
    return global_avgpool_int8_ref(x, act_scale=act_scale)


# engine(spec, layer_params, x, act) -> Optional[(y_q, y_float)].  The
# per-layer dispatch hook the pipeline executor plugs in: it routes each
# layer to its compile-time LayerEngine binding (repro.compiler.engines);
# returning None falls back to the jnp reference path here.
EngineHook = Callable[[ConvLayerSpec, Params, jnp.ndarray, str],
                      Optional[Tuple[jnp.ndarray, Optional[jnp.ndarray]]]]

# block_engine(block, params, x) -> Optional[y_q].  The block-granular
# dispatch hook: a whole residual block (conv chain + downsample + add +
# join activation) offered as ONE unit, for pipelines that bound it to a
# fused block engine (res_block_int8).  Returning None falls back to
# per-layer execution below.
BlockEngineHook = Callable[[ResBlockSpec, Params, jnp.ndarray],
                           Optional[jnp.ndarray]]

# scan_engine(lead_block, params, x, limit) -> Optional[(y_q, consumed)].
# The scan-group dispatch hook: offered at the LEAD block of each residual
# block, BEFORE the block hook.  Accepting means the hook executed a whole
# homogeneous run of blocks starting there (one lax.scan body over stacked
# per-block params) and consumed ``consumed`` member layers; ``limit`` is
# how many layers remain in the active layer_range, so the hook declines
# runs that would cross a stage boundary (per-block execution then covers
# them).  Returning None falls through to ``block_engine``.
ScanEngineHook = Callable[[ResBlockSpec, Params, jnp.ndarray, int],
                          Optional[Tuple[jnp.ndarray, int]]]


def cnn_forward(params: Params, cfg: CNNConfig, images,
                engine: Optional[EngineHook] = None,
                block_engine: Optional[BlockEngineHook] = None,
                scan_engine: Optional[ScanEngineHook] = None,
                layer_range: Optional[Tuple[int, int]] = None
                ) -> jnp.ndarray:
    """Plain feed-forward execution (the functional reference; the pipeline
    executor in runtime/pipeline.py runs the same layers through the Pallas
    engines by passing ``engine``/``block_engine``).

    images: [B,224,224,3] (or reduced) int8.  Returns logits [B,classes].
    Residual/downsample wiring (ResNet blocks, MobileNetV2's inverted
    residuals) and every layer's activation come from
    ``configs.cnn.residual_blocks`` / ``layer_activations`` — the same
    grouping the compiler's block binding uses, so the topology and the
    bindings cannot drift.
    Pooling is NOT wired here: maxpool and global-average-pool are
    first-class graph nodes in ``cfg.layers``, offered to the engine hook
    like any conv (the compiler binds them to the pool engines) — nothing
    about the topology is implicit anymore.

    ``engine``: per-layer dispatch hook.  When provided, each node
    is offered to the hook first (the pipeline executor routes it to its
    compile-time engine binding — pinned or HBM-streamed Pallas kernels,
    including the grouped depthwise and the pooling engines); nodes the
    hook declines (returns None for — e.g. layers unknown to the plan)
    run the jnp path, so every node executes exactly once either way.

    ``block_engine``: block-granular hook, offered each residual block
    BEFORE its layers run individually; declining falls back to the
    per-layer wiring here (which itself offers each layer to ``engine``).
    The same hook is offered the config's :class:`StemUnitSpec` (stem
    conv + following maxpool as one fused unit) at the stem, when the
    config has one.

    ``scan_engine``: scan-group hook, offered at each residual block's
    lead conv BEFORE ``block_engine`` with the count of layers remaining
    in the active range; accepting executes a whole homogeneous block
    run as one scanned body and skips its member layers (see
    :data:`ScanEngineHook`).

    ``layer_range``: ``(start, stop)`` indices into ``cfg.layers`` — run
    only that contiguous slice (the sharded pipeline executor walks one
    stage's slice per device).  ``images`` is then the slice's input
    activation; when the slice stops before the final layer the return
    value is the int8 activation feeding layer ``stop`` (the stage
    boundary), not logits.  A range may not start or stop inside a
    residual block: the identity add spans the whole block, so a cut
    there would silently drop the skip connection.
    """

    acts = layer_activations(cfg)

    def apply_layer(spec: ConvLayerSpec, x):
        act = acts[spec.name]
        if engine is not None:
            out = engine(spec, params.get(spec.name, {}), x, act)
            if out is not None:
                return out
        if spec.is_pool:
            return pool_forward(spec, x), None
        return conv_layer_forward(params[spec.name], spec, x, act=act)

    x = images
    layers = list(cfg.layers)
    blocks = {b.convs[0].name: b for b in residual_blocks(cfg)}
    start, stop = (0, len(layers)) if layer_range is None else layer_range
    if not 0 <= start < stop <= len(layers):
        raise ValueError(
            f"layer_range {layer_range} outside [0, {len(layers)})")
    member_head = {m.name: b.convs[0].name
                   for b in residual_blocks(cfg) for m in b.members}
    for cut, where in ((start, "start"), (stop, "stop")):
        if cut < len(layers):
            name = layers[cut].name
            if name in member_head and member_head[name] != name:
                raise ValueError(
                    f"layer_range {where}={cut} cuts residual block "
                    f"{member_head[name]!r} open at member {name!r}; "
                    f"stage cuts must treat blocks as atomic units")
    stem = stem_unit(cfg)
    i = start
    while i < stop:
        spec = layers[i]
        name = spec.name
        if (stem is not None and name == stem.conv.name and i + 2 <= stop
                and block_engine is not None):
            # the stem conv + maxpool pair as one fused unit; declining
            # (or a range that cuts the pair) falls through to the
            # per-layer walk below, bit-identically
            out = block_engine(stem, params, x)
            if out is not None:
                x = out
                i += 2
                continue
        if spec.is_pool:
            x, _ = apply_layer(spec, x)
            i += 1
            continue
        if name in blocks:
            blk = blocks[name]
            if scan_engine is not None:
                out = scan_engine(blk, params, x, stop - i)
                if out is not None:
                    x, consumed = out
                    i += consumed
                    continue
            if block_engine is not None:
                out = block_engine(blk, params, x)
                if out is not None:
                    x = out
                    i += len(blk.members)
                    continue
            identity = x
            h = x
            for cspec in blk.convs:
                h, _ = apply_layer(cspec, h)
            if blk.ds is not None:
                identity, _ = apply_layer(blk.ds, identity)
            x = residual_join(h, identity, blk.join)
            i += len(blk.members)
            continue
        x, y_f = apply_layer(spec, x)
        if i == len(layers) - 1:
            # the last layer's float pre-quantization output: the logits
            return y_f.reshape(y_f.shape[0], -1)
        i += 1
    if stop < len(layers):
        return x                  # int8 stage-boundary activation
    # no explicit fc tail (shouldn't happen) — pool and return
    return jnp.mean(x.astype(jnp.float32), axis=(1, 2))


def cnn_input_shape(cfg: CNNConfig, batch: int) -> Tuple[int, int, int, int]:
    l0 = cfg.layers[0]
    return (batch, l0.in_h, l0.in_w, l0.c_in)
