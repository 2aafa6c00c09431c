"""Layer-pipelined dataflow executor — H2PIPE's architecture on the mesh.

The paper's accelerator assigns consecutive CNN layers to specialized
engines placed around the die, with activations flowing through small FIFOs
between them.  On the TPU mesh the analogue is pipeline parallelism over the
``model`` axis: each device group owns a contiguous group of layers (a
*stage*), and activations move stage-to-stage with ``lax.ppermute`` inside a
``shard_map`` while every stage computes on a different microbatch — all
stages busy in parallel, exactly Fig. 1.

Key H2PIPE semantics carried over:
  * **continuous streaming** (serving): the static schedule admits one
    microbatch per tick with at most ``n_stages`` in flight — the credit
    bound of §V-A (a static schedule cannot head-of-line block, which is
    the program-level proof of the credit property ``fifo_sim`` checks
    dynamically);
  * **pipeline order = placement order** (§V-B): stage s holds layers
    [s*L/S, (s+1)*L/S) — the clockwise pseudo-channel assignment becomes
    the identity stage mapping;
  * **GPipe-style training**: microbatch gradients accumulate; the bubble
    fraction (S-1)/(M+S-1) is reported by ``pipeline_stats``.

The executor is generic over the per-stage function so the CNN engines, the
transformer layers and the tests' toy layers all use the same machinery.
"""
from __future__ import annotations

import math
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def _validate_mesh_axis(mesh: Mesh, axis: str) -> int:
    """The pipeline axis must actually exist on the mesh — shard_map's
    own error for a missing axis name is an opaque tracer failure, so
    check up front and say what was available."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    if axis not in sizes:
        raise ValueError(
            f"mesh has no axis {axis!r}; available axes: {sizes} "
            f"(pass axis=<name> matching the mesh the pipeline runs on)")
    return sizes[axis]


def split_stages(stacked_params, n_stages: int):
    """[L, ...] stacked layer params -> [S, L/S, ...]."""
    if n_stages < 1:
        raise ValueError(f"n_stages must be >= 1, got {n_stages}")

    def re(x):
        L = x.shape[0]
        if L % n_stages != 0:
            raise ValueError(
                f"cannot split {L} stacked layers into {n_stages} equal "
                f"stages ({L} % {n_stages} != 0); pad the stack or pick a "
                f"stage count that divides the layer count")
        return x.reshape((n_stages, L // n_stages) + x.shape[1:])
    return jax.tree.map(re, stacked_params)


def pipeline_stats(n_stages: int, n_microbatches: int) -> Dict[str, float]:
    total = n_microbatches + n_stages - 1
    return {
        "ticks": total,
        "bubble_fraction": (n_stages - 1) / total,
        "in_flight_credits": n_stages,
    }


def pipeline_apply(layer_fn: Callable, params_staged, x_mb, *, mesh: Mesh,
                   axis: str = "model"):
    """Run microbatches through the stage pipeline.

    layer_fn(stage_params, x) -> x   applies one stage's layer group; it is
        called with the [L/S, ...] slice owned by the local stage.
    params_staged: [S, L/S, ...] pytree (see ``split_stages``).
    x_mb: [M, mb, ...] microbatched input (replicated over ``axis``).

    Returns [M, mb, ...] outputs, valid on every device (the last stage's
    results are broadcast back, like the paper's output DMA).
    """
    n_stages = _validate_mesh_axis(mesh, axis)
    if x_mb.ndim < 2 or x_mb.shape[0] < 1:
        raise ValueError(
            f"x_mb must be [M, mb, ...] with M >= 1 microbatches, got "
            f"shape {tuple(x_mb.shape)}")
    bad = [tuple(a.shape) for a in jax.tree.leaves(params_staged)
           if a.shape[:1] != (n_stages,)]
    if bad:
        raise ValueError(
            f"params_staged leaves must carry a leading stage dimension of "
            f"{n_stages} (the {axis!r} mesh axis size); got leading dims "
            f"{sorted({s[0] if s else None for s in bad})} — build them "
            f"with split_stages(params, {n_stages})")
    M = x_mb.shape[0]
    S = n_stages

    def stage_body(params_local, x_local):
        p = jax.tree.map(lambda a: a[0], params_local)   # drop stage dim
        idx = jax.lax.axis_index(axis)
        zero = jnp.zeros_like(x_local[0])

        def tick(buf, t):
            # stage 0 admits microbatch t (one credit per tick; at most S
            # microbatches live at once by the static schedule)
            mb_in = jax.lax.dynamic_index_in_dim(
                x_local, jnp.clip(t, 0, M - 1), keepdims=False)
            my_in = jnp.where(idx == 0, mb_in, buf)
            out = layer_fn(p, my_in)
            # hand off to the next stage around the ring
            perm = [(i, (i + 1) % S) for i in range(S)]
            nxt = jax.lax.ppermute(out, axis, perm)
            # the last stage's output this tick is a finished microbatch
            done = jnp.where(idx == S - 1, out, jnp.zeros_like(out))
            return nxt, done

        _, outs = jax.lax.scan(tick, zero, jnp.arange(M + S - 1))
        outs = outs[S - 1:]                  # microbatch m done at tick m+S-1
        # broadcast the last stage's results to every device
        outs = jax.lax.psum(
            jnp.where(idx == S - 1, outs, jnp.zeros_like(outs)), axis)
        return outs

    p_specs = jax.tree.map(lambda _: P(axis), params_staged)
    fn = jax.shard_map(stage_body, mesh=mesh,
                       in_specs=(p_specs, P()), out_specs=P(),
                       check_vma=False)
    return fn(params_staged, x_mb)


def staged_pipeline_apply(stage_fns: Sequence[Callable], params, x_mb, *,
                          mesh: Mesh, axis: str = "model",
                          boundary_shapes: Sequence[Optional[Tuple[int, ...]]],
                          out_shape: Tuple[int, ...],
                          out_dtype=jnp.float32,
                          carry_dtype=jnp.int8):
    """``pipeline_apply`` generalized to HETEROGENEOUS stages.

    ``pipeline_apply`` requires every stage to run the same ``layer_fn``
    over a same-shaped activation; a partitioned CNN has neither — stage
    boundaries change the activation geometry (stride-2 transitions,
    GAP) and each stage runs a different slice of the compiled engine
    table.  Here every device runs its OWN program, selected by
    ``lax.switch`` on the stage index, and the ring still moves
    activations with ``lax.ppermute``: boundary activations are
    flattened into one fixed-size ``carry_dtype`` buffer (sized to the
    widest stage boundary) so the carry has a single static shape even
    though each hop reshapes to a different geometry.

    stage_fns[s](params, x) -> y   runs stage ``s``'s layer slice;
        ``params`` is the full (replicated) parameter pytree — each
        stage program reads only its own layers' entries.
    x_mb: [M, mb, ...] microbatched input, replicated over ``axis``.
    boundary_shapes[s]: the per-microbatch activation shape ENTERING
        stage ``s`` (``boundary_shapes[0]`` is unused — stage 0 reads
        ``x_mb`` directly — and may be None).  Inter-stage activations
        must be ``carry_dtype`` (int8 for the quantized CNN pipeline).
    out_shape/out_dtype: the last stage's per-microbatch output.

    Returns [M, *out_shape] outputs, valid on every device (the last
    stage's results are summed back over the axis, like the paper's
    output DMA).  Admission follows the same static schedule as
    ``pipeline_apply``: one microbatch per tick, at most S in flight
    (§V-A), microbatch m completing at tick m + S - 1.
    """
    S = _validate_mesh_axis(mesh, axis)
    if len(stage_fns) != S:
        raise ValueError(
            f"{len(stage_fns)} stage programs for a {S}-device {axis!r} "
            f"axis; the partition's n_stages must equal the mesh axis size")
    if len(boundary_shapes) != S:
        raise ValueError(
            f"boundary_shapes must carry one entry per stage "
            f"({S}), got {len(boundary_shapes)}")
    if x_mb.ndim < 2 or x_mb.shape[0] < 1:
        raise ValueError(
            f"x_mb must be [M, mb, ...] with M >= 1 microbatches, got "
            f"shape {tuple(x_mb.shape)}")
    M = x_mb.shape[0]
    flat = max([math.prod(boundary_shapes[s]) for s in range(1, S)],
               default=1)
    out_shape = tuple(out_shape)

    def stage_body(p, x_local):
        idx = jax.lax.axis_index(axis)
        zero_carry = jnp.zeros((flat,), carry_dtype)
        zero_out = jnp.zeros(out_shape, out_dtype)

        def make_branch(s):
            fn = stage_fns[s]

            def branch(buf, mb_in):
                if s == 0:
                    xin = mb_in
                else:
                    shape = tuple(boundary_shapes[s])
                    xin = buf[:math.prod(shape)].reshape(shape)
                y = fn(p, xin)
                if s == S - 1:
                    if tuple(y.shape) != out_shape:
                        raise ValueError(
                            f"stage {s} produced {tuple(y.shape)}, "
                            f"expected out_shape {out_shape}")
                    return zero_carry, y.astype(out_dtype)
                want = tuple(boundary_shapes[s + 1])
                if tuple(y.shape) != want:
                    raise ValueError(
                        f"stage {s} produced {tuple(y.shape)}, but stage "
                        f"{s + 1} declares boundary shape {want}")
                f = y.astype(carry_dtype).reshape(-1)
                return jnp.pad(f, (0, flat - f.size)), zero_out
            return branch

        branches = [make_branch(s) for s in range(S)]

        def tick(buf, t):
            mb_in = jax.lax.dynamic_index_in_dim(
                x_local, jnp.clip(t, 0, M - 1), keepdims=False)
            nxt, done = jax.lax.switch(idx, branches, buf, mb_in)
            perm = [(i, (i + 1) % S) for i in range(S)]
            nxt = jax.lax.ppermute(nxt, axis, perm)
            return nxt, done

        _, outs = jax.lax.scan(tick, zero_carry, jnp.arange(M + S - 1))
        outs = outs[S - 1:]                  # microbatch m done at tick m+S-1
        # non-last stages emitted zeros, so the sum IS the last stage's
        # results, broadcast to every device
        return jax.lax.psum(outs, axis)

    p_specs = jax.tree.map(lambda _: P(), params)
    fn = jax.shard_map(stage_body, mesh=mesh,
                       in_specs=(p_specs, P()), out_specs=P(),
                       check_vma=False)
    return fn(params, x_mb)


def gpipe_train_step(layer_fn: Callable, loss_fn: Callable, params_staged,
                     x_mb, y_mb, *, mesh: Mesh, axis: str = "model"):
    """GPipe: forward all microbatches through the pipeline, mean loss over
    microbatches, grads by autodiff through the ppermute schedule (XLA
    overlaps the stage-boundary collectives with compute — the paper's
    prefetch-overlap trick applied to activations)."""
    def mean_loss(params):
        outs = pipeline_apply(layer_fn, params, x_mb, mesh=mesh, axis=axis)
        return jnp.mean(jax.vmap(loss_fn)(outs, y_mb))

    return jax.value_and_grad(mean_loss)(params_staged)
