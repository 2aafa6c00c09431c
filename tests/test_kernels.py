"""Per-kernel validation: sweep shapes/dtypes, assert_allclose against the
ref.py pure-jnp oracles (interpret=True executes kernels on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.conv2d_int8.ops import conv2d_int8, conv_tile_for
from repro.kernels.conv2d_int8.ref import conv2d_int8_ref
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.stream_matmul.ops import stream_matmul, vmem_bytes
from repro.kernels.stream_matmul.ref import stream_matmul_ref


# ---------------------------------------------------------------------------
# stream_matmul
# ---------------------------------------------------------------------------

MM_SHAPES = [(128, 256, 128), (256, 1024, 384), (128, 512, 256)]


@pytest.mark.parametrize("shape", MM_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("mode", ["stream", "fifo", "pinned"])
def test_stream_matmul(shape, dtype, mode, rng_key):
    M, K, N = shape
    k1, k2 = jax.random.split(rng_key)
    x = jax.random.normal(k1, (M, K), dtype)
    w = jax.random.normal(k2, (K, N), dtype)
    out = stream_matmul(x, w, mode=mode, bm=128, bk=128, bn=128,
                        n_buffers=3, interpret=True)
    ref = stream_matmul_ref(x, w)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=tol, atol=tol * float(jnp.max(jnp.abs(ref))))


@pytest.mark.parametrize("n_buffers", [1, 2, 4])
def test_stream_matmul_fifo_depth(n_buffers, rng_key):
    """The prefetch-window depth (the paper's FIFO depth knob) never
    changes results — only VMEM footprint."""
    x = jax.random.normal(rng_key, (128, 512), jnp.float32)
    w = jax.random.normal(rng_key, (512, 128), jnp.float32)
    ref = stream_matmul_ref(x, w)
    out = stream_matmul(x, w, mode="fifo", bk=128, n_buffers=n_buffers,
                        interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5,
                               atol=1e-4)
    assert vmem_bytes("fifo", 128, 512, 128, 4, bk=128,
                      n_buffers=n_buffers) > \
        vmem_bytes("fifo", 128, 512, 128, 4, bk=128, n_buffers=0)


def test_stream_matmul_int8(rng_key):
    x = jax.random.randint(rng_key, (128, 512), -127, 128, jnp.int8)
    w = jax.random.randint(rng_key, (512, 256), -127, 128, jnp.int8)
    ref = stream_matmul_ref(x, w)
    for mode in ("stream", "fifo"):
        out = stream_matmul(x, w, mode=mode, bk=128, interpret=True)
        assert out.dtype == jnp.int32
        assert bool(jnp.all(out == ref)), mode


# ---------------------------------------------------------------------------
# conv2d_int8
# ---------------------------------------------------------------------------

CONV_CASES = [
    (16, 16, 8, 16, 3, 1), (16, 16, 8, 16, 3, 2),
    (14, 14, 16, 32, 1, 1), (12, 12, 4, 8, 5, 2), (8, 8, 3, 16, 7, 2),
]


@pytest.mark.parametrize("case", CONV_CASES)
def test_conv2d_int8_exact(case, rng_key):
    H, W, C, Co, k, s = case
    x = jax.random.randint(rng_key, (2, H, W, C), -127, 128, jnp.int8)
    w = jax.random.randint(rng_key, (k, k, C, Co), -20, 21, jnp.int8)
    out = conv2d_int8(x, w, stride=s, interpret=True)
    ref = conv2d_int8_ref(x, w, stride=s)
    assert out.shape == ref.shape
    assert out.dtype == jnp.int32
    assert bool(jnp.all(out == ref)), case     # int math must be exact


# (B, H=W, C, C_out, k, stride, streamed, n_buffers) -> the tile the rule
# must pick for it, so each case covers the tiling it names
TILED_CONV_CASES = {
    "whole_map_7x7_bt2": ((2, 7, 16, 32, 3, 1, False, 2), (2, 7)),
    "whole_map_7x7_streamed_nb3": ((4, 7, 16, 32, 3, 1, True, 3), (4, 7)),
    "whole_map_s2_streamed": ((2, 14, 16, 32, 3, 2, True, 2), (2, 7)),
    "rows_lt_h_out": ((1, 56, 8, 16, 3, 1, False, 2), (1, 28)),
    "rows_lt_h_out_s2_b2": ((2, 112, 8, 16, 3, 2, False, 2), (1, 28)),
    "rows_lt_h_out_streamed": ((2, 96, 8, 16, 3, 2, True, 2), (1, 24)),
    "bt2_of_4": ((4, 24, 8, 16, 3, 1, False, 2), (2, 24)),
    "bt2_of_4_1x1s2_streamed_nb3": ((4, 48, 8, 16, 1, 2, True, 3), (2, 24)),
    "batch1_5x5s2": ((1, 12, 4, 8, 5, 2, False, 2), (1, 6)),
    "fc_as_conv_7x7s7_streamed": ((4, 7, 32, 64, 7, 7, True, 2), (4, 1)),
    "fc_as_conv_7x7s7_pinned": ((2, 7, 32, 64, 7, 7, False, 2), (2, 1)),
}


@pytest.mark.parametrize("case", sorted(TILED_CONV_CASES))
def test_conv2d_int8_tiled_exact(case, rng_key):
    """The tiled kernel (tiles of images x output rows, line buffer
    prefetched across grid steps, one dot per tap per tile) is
    bit-identical to the reference in both weight tiers."""
    (B, hw, C, Co, k, s, stream, nb), want = TILED_CONV_CASES[case]
    tile = conv_tile_for((B, hw, hw, C), (k, k, C, Co), stride=s,
                         stream=stream, n_buffers=nb)
    assert (tile.bt, tile.r) == want, tile
    kx, kw = jax.random.split(rng_key)
    x = jax.random.randint(kx, (B, hw, hw, C), -127, 128, jnp.int8)
    w = jax.random.randint(kw, (k, k, C, Co), -127, 128, jnp.int8)
    out = conv2d_int8(x, w, stride=s, stream=stream, n_buffers=nb,
                      interpret=True)
    ref = conv2d_int8_ref(x, w, stride=s)
    assert out.shape == ref.shape and out.dtype == jnp.int32
    assert bool(jnp.all(out == ref)), case


# ---------------------------------------------------------------------------
# flash_attention
# ---------------------------------------------------------------------------

ATTN_CASES = [
    dict(B=2, H=4, KV=4, S=256, hd=64, causal=True, window=0, softcap=0.0),
    dict(B=2, H=4, KV=2, S=256, hd=64, causal=True, window=64, softcap=0.0),
    dict(B=1, H=8, KV=2, S=128, hd=32, causal=True, window=0, softcap=50.0),
    dict(B=1, H=2, KV=2, S=128, hd=64, causal=False, window=0, softcap=0.0),
    dict(B=1, H=4, KV=1, S=128, hd=128, causal=True, window=32, softcap=30.0),
]


@pytest.mark.parametrize("case", ATTN_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(case, dtype, rng_key):
    ks = jax.random.split(rng_key, 3)
    q = jax.random.normal(ks[0], (case["B"], case["S"], case["H"],
                                  case["hd"]), dtype)
    k = jax.random.normal(ks[1], (case["B"], case["S"], case["KV"],
                                  case["hd"]), dtype)
    v = jax.random.normal(ks[2], (case["B"], case["S"], case["KV"],
                                  case["hd"]), dtype)
    out = flash_attention(q, k, v, causal=case["causal"],
                          window=case["window"], softcap=case["softcap"],
                          bq=64, bk=64, interpret=True)
    qt, kt, vt = (a.transpose(0, 2, 1, 3) for a in (q, k, v))
    ref = flash_attention_ref(qt, kt, vt, causal=case["causal"],
                              window=case["window"],
                              softcap=case["softcap"]).transpose(0, 2, 1, 3)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol * 3)


def test_flash_matches_model_oracle(rng_key):
    """The kernel agrees with models.layers.blockwise_attention (the
    XLA-path oracle used by every arch)."""
    from repro.models.layers import blockwise_attention
    ks = jax.random.split(rng_key, 3)
    B, S, H, KV, hd = 2, 128, 4, 2, 32
    q = jax.random.normal(ks[0], (B, S, H, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, KV, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, KV, hd), jnp.float32)
    out_kernel = flash_attention(q, k, v, causal=True, bq=64, bk=64,
                                 interpret=True)
    out_oracle = blockwise_attention(q, k, v, causal=True, q_block=64,
                                     kv_block=64)
    np.testing.assert_allclose(np.asarray(out_kernel),
                               np.asarray(out_oracle), rtol=2e-5, atol=2e-5)
