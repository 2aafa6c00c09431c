"""Pipeline executor tests.  Multi-stage tests need >1 device, so they run
in a subprocess with forced host devices (tests themselves keep seeing the
real single device, per the dry-run isolation rule)."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.admission import replay_staged_schedule
from repro.core.dataflow import (pipeline_apply, pipeline_stats,
                                 split_stages, staged_pipeline_apply)
from repro.launch.mesh import compat_make_mesh


def test_split_stages():
    p = {"w": jnp.zeros((16, 4, 4))}
    s = split_stages(p, 8)
    assert s["w"].shape == (8, 2, 4, 4)
    with pytest.raises(ValueError, match="cannot split 15"):
        split_stages({"w": jnp.zeros((15, 4))}, 8)
    with pytest.raises(ValueError, match="n_stages"):
        split_stages(p, 0)


def test_pipeline_stats_credits():
    st = pipeline_stats(n_stages=8, n_microbatches=24)
    assert st["ticks"] == 31
    assert st["in_flight_credits"] == 8       # the §V-A credit bound
    assert 0 < st["bubble_fraction"] < 0.25


def test_fill_law_matches_staged_replay():
    """pipeline_stats' M + S - 1 tick count IS the staged admission
    replay's makespan, for a sweep of shapes — and the replay proves
    per-stage occupancy never exceeded one."""
    for S in (1, 2, 3, 5, 8):
        for M in (1, 2, 7, 24):
            st = pipeline_stats(n_stages=S, n_microbatches=M)
            tr = replay_staged_schedule(M, n_stages=S)
            assert tr.makespan == st["ticks"] == M + S - 1
            assert tr.max_in_flight <= st["in_flight_credits"]
            assert tr.max_stage_occupancy <= 1


def _toy(key, L, d):
    Ws = jax.random.normal(key, (L, d, d)) * 0.1

    def layer_fn(p, x):
        def body(x, w):
            return jnp.tanh(x @ w), None
        return jax.lax.scan(body, x, p["w"])[0]

    def ref(x):
        for i in range(L):
            x = jnp.tanh(x @ Ws[i])
        return x
    return Ws, layer_fn, ref


def test_pipeline_apply_validates_inputs():
    mesh = compat_make_mesh((1,), ("model",))
    Ws, layer_fn, _ = _toy(jax.random.PRNGKey(0), 4, 4)
    x_mb = jnp.zeros((3, 2, 4))
    with pytest.raises(ValueError, match="no axis 'data'"):
        pipeline_apply(layer_fn, split_stages({"w": Ws}, 1), x_mb,
                       mesh=mesh, axis="data")
    with pytest.raises(ValueError, match="split_stages"):
        # leading dim 4 != the 1-device axis size
        pipeline_apply(layer_fn, {"w": Ws}, x_mb, mesh=mesh)
    with pytest.raises(ValueError, match=r"\[M, mb, \.\.\.\]"):
        pipeline_apply(layer_fn, split_stages({"w": Ws}, 1),
                       jnp.zeros((3,)), mesh=mesh)


def test_pipeline_single_stage_matches_sequential():
    """Property (satellite of the sharded-serving PR): a 1-stage mesh
    pipeline is bit-identical to the sequential apply for every
    microbatch count — the pipeline machinery adds scheduling, never
    arithmetic."""
    mesh = compat_make_mesh((1,), ("model",))
    for i, (L, d, M, mb) in enumerate(
            [(4, 4, 1, 2), (6, 8, 3, 2), (2, 4, 5, 1)]):
        Ws, layer_fn, ref = _toy(jax.random.PRNGKey(i), L, d)
        x_mb = jax.random.normal(jax.random.PRNGKey(100 + i), (M, mb, d))
        with mesh:
            out = pipeline_apply(layer_fn, split_stages({"w": Ws}, 1),
                                 x_mb, mesh=mesh)
        np.testing.assert_array_equal(np.asarray(out),
                                      np.asarray(jax.vmap(ref)(x_mb)))


def test_staged_pipeline_validates_inputs():
    mesh = compat_make_mesh((1,), ("model",))
    fn = lambda p, x: x
    x_mb = jnp.zeros((2, 3, 4))
    with pytest.raises(ValueError, match="stage programs"):
        staged_pipeline_apply([fn, fn], {}, x_mb, mesh=mesh,
                              boundary_shapes=[None, (3, 4)],
                              out_shape=(3, 4))
    with pytest.raises(ValueError, match="boundary_shapes"):
        staged_pipeline_apply([fn], {}, x_mb, mesh=mesh,
                              boundary_shapes=[], out_shape=(3, 4))


def _int8_toy(key, L, d):
    """Integer layers (int8 in/out, exact int32 matmul + shift + clip):
    the quantized CNN's carry contract, where bit identity holds under
    any reduction or fusion order."""
    Ws = jax.random.randint(key, (L, d, d), -127, 128, jnp.int8)

    def layer(x, w):
        y = jnp.dot(x.astype(jnp.int32), w.astype(jnp.int32)) >> 7
        return jnp.clip(y, -127, 127).astype(jnp.int8)

    def layer_fn(p, x):
        return jax.lax.scan(lambda x, w: (layer(x, w), None), x, p["w"])[0]

    def ref(x):
        for i in range(L):
            x = layer(x, Ws[i])
        return x
    return Ws, layer_fn, ref


def test_staged_pipeline_single_stage_matches_sequential():
    """staged_pipeline_apply with ONE heterogeneous stage == the stage
    function applied per microbatch, bit for bit, on the int8 carry the
    CNN pipeline uses.  (An f32 stage is not held to bit identity: the
    scan inside shard_map and the vmapped loop may fuse and round their
    float reductions differently.)"""
    mesh = compat_make_mesh((1,), ("model",))
    Ws, layer_fn, ref = _int8_toy(jax.random.PRNGKey(7), 5, 4)
    params = {"w": Ws}
    x_mb = jax.random.randint(jax.random.PRNGKey(8), (4, 2, 4), -127, 128,
                              jnp.int8)
    with mesh:
        out = staged_pipeline_apply(
            [layer_fn], params, x_mb, mesh=mesh,
            boundary_shapes=[None], out_shape=(2, 4),
            out_dtype=jnp.int32, carry_dtype=jnp.int8)
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(jax.vmap(ref)(x_mb)).astype(np.int32))


MULTI_DEVICE_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.core.dataflow import split_stages, pipeline_apply, \\
        gpipe_train_step
    from repro.launch.mesh import compat_make_mesh

    mesh = compat_make_mesh((8,), ("model",))
    L, d = 16, 8
    key = jax.random.PRNGKey(0)
    Ws = jax.random.normal(key, (L, d, d)) * 0.1
    staged = split_stages({"w": Ws}, 8)

    def layer_fn(p, x):
        def body(x, w):
            return jnp.tanh(x @ w), None
        return jax.lax.scan(body, x, p["w"])[0]

    M, mb = 4, 2
    x_mb = jax.random.normal(key, (M, mb, d))
    with mesh:
        out = pipeline_apply(layer_fn, staged, x_mb, mesh=mesh)
    def ref(x):
        for i in range(L):
            x = jnp.tanh(x @ Ws[i])
        return x
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(jax.vmap(ref)(x_mb)),
                               rtol=1e-5, atol=1e-5)
    with mesh:
        loss, grads = gpipe_train_step(
            layer_fn, lambda o, y: jnp.mean((o - y) ** 2), staged, x_mb,
            jnp.ones_like(x_mb), mesh=mesh)
    gn = float(jnp.linalg.norm(grads["w"]))
    assert jnp.isfinite(loss) and gn > 0
    print("OK")
""")


def test_pipeline_matches_sequential_8stages():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", MULTI_DEVICE_SCRIPT],
                       capture_output=True, text=True, env=env,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))), timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK" in r.stdout


HETEROGENEOUS_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.core.dataflow import staged_pipeline_apply
    from repro.launch.mesh import compat_make_mesh

    # four stages with DIFFERENT programs and DIFFERENT boundary widths
    # (the shape regime pipeline_apply cannot express)
    mesh = compat_make_mesh((4,), ("model",))
    widths = [6, 10, 3, 8, 5]            # stage s maps widths[s]->widths[s+1]
    key = jax.random.PRNGKey(0)
    Ws = [jax.random.normal(jax.random.PRNGKey(s), (widths[s], widths[s+1]))
          * 0.1 for s in range(4)]
    params = {f"w{s}": Ws[s] for s in range(4)}

    def make_stage(s):
        def fn(p, x):
            return jnp.tanh(x @ p[f"w{s}"])
        return fn

    M, mb = 7, 2
    x_mb = jax.random.normal(key, (M, mb, widths[0]))
    with mesh:
        out = staged_pipeline_apply(
            [make_stage(s) for s in range(4)], params, x_mb, mesh=mesh,
            boundary_shapes=[None] + [(mb, widths[s]) for s in (1, 2, 3)],
            out_shape=(mb, widths[4]), out_dtype=jnp.float32,
            carry_dtype=jnp.float32)

    def ref(x):
        for s in range(4):
            x = jnp.tanh(x @ Ws[s])
        return x
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(jax.vmap(ref)(x_mb)),
                               rtol=1e-6, atol=1e-6)
    print("OK")
""")


def test_staged_pipeline_heterogeneous_4stages():
    """4-device staged pipeline with per-stage programs and changing
    boundary geometry matches the sequential composition."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", HETEROGENEOUS_SCRIPT],
                       capture_output=True, text=True, env=env,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))), timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK" in r.stdout
