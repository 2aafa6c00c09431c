#!/usr/bin/env python3
"""Chip smoke test: full-size ResNet-50 through compile() -> serve() on a TPU.

The quickest proof that the system still starts on the chip.  One process,
no children:

  1. params and images from ``--seed``;
  2. ``compile(resnet50, NX2100.replace(backend="compiled"))``: every graph
     node bound to a Mosaic-compiled Pallas engine (no ``jnp_ref``), both
     weight tiers present (pinned and HBM-streamed layers);
  3. ``serve(params, microbatch=8)`` answers 6 requests of 1-8 images;
  4. every request's logits must equal ``models.cnn.cnn_forward`` (the
     XLA reference) on the same chip, bit for bit — the int8 contract.

The last line of standard output is one JSON object naming the device.
Without a TPU the script exits non-zero before any phase and prints no
result.  ``--chips 4`` runs only the sharded path instead: the pipeline
partitioned into 4 stages over a 1x4 ``model`` mesh
(``serve_sharded``), compared bit for bit with single-chip ``run()``.

    python chip_smoke.py [--seed N] [--chips 4]
"""
from __future__ import annotations

import argparse
import collections
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

#: images per request: mixed sizes, one of them a full microbatch
REQUEST_SIZES = (1, 8, 3, 5, 2, 7)
MICROBATCH = 8


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or unverifiable result."""


def make_inputs(cfg, seed: int, sizes=REQUEST_SIZES):
    """Random int8 params and one image batch per request, from ``seed``."""
    import jax
    from repro.models.cnn import cnn_input_shape, init_cnn_params
    params = init_cnn_params(jax.random.PRNGKey(seed), cfg)
    rng = np.random.default_rng(seed)
    images = [rng.integers(-127, 128, cnn_input_shape(cfg, n), np.int8)
              for n in sizes]
    return params, images


def compile_checked(cfg, target, log=print):
    """``compile()``, then the binding checks: no layer on ``jnp_ref``."""
    from repro import compiler
    t0 = time.perf_counter()
    cp = compiler.compile(cfg, target)
    seconds = time.perf_counter() - t0
    counts = collections.Counter(cp.engine_table().values())
    log(f"compile(): {cfg.name} on {target.name}: {len(cp.assignments)} "
        f"graph nodes, {seconds:.3f} s (plan + bind, informational)")
    log(f"engine table: {dict(sorted(counts.items()))}")
    log(f"HBM-streamed layers: {len(cp.streamed_names)} "
        f"{list(cp.streamed_names)}")
    if counts.get("jnp_ref"):
        raise SmokeFailure(
            f"{counts['jnp_ref']} node(s) bound to the jnp reference: "
            f"{[a.layer for a in cp.assignments if a.engine == 'jnp_ref']}")
    return cp


def check_dispatch(cp, params, batch: int, *, interpret: bool, log=print):
    """The fused program ``serve()`` and ``run()`` share, checked: every
    graph node dispatched on its compiled engine (a node a dispatch hook
    declined would run the jnp reference and leave no stats: ``verify``
    raises), none on ``jnp_ref``, and — off the interpreter — Mosaic
    kernels in the compiled program."""
    import jax.numpy as jnp
    from repro.models.cnn import cnn_input_shape
    zeros = jnp.zeros(cnn_input_shape(cp.cfg, batch), jnp.int8)
    _, report = cp.run(params, zeros, interpret=interpret)
    report.verify()
    ran = collections.Counter(st.kernel for st in report.layers)
    if ran.get("jnp_ref"):
        raise SmokeFailure(f"jnp_ref ran {ran['jnp_ref']} node(s)")
    trace = cp.fused_trace(params, zeros, interpret=interpret,
                           act_scale=0.05)
    kernels = trace.fn.as_text().count("tpu_custom_call")
    log(f"dispatch: {len(report.layers)} nodes on {dict(sorted(ran.items()))}"
        f"; interpret={interpret}; Mosaic kernels in the program: {kernels}")
    if not interpret and kernels == 0:
        raise SmokeFailure("the compiled program holds no Mosaic kernel")


def first_differing_layer(cp, params, x, *, interpret: bool):
    """Walk ``x`` through the network layer by layer, running each node
    on its engine AND on the reference from the same (reference) input:
    ``(layer, max |int8 diff|, max |float diff|)`` of the first node whose
    engine output differs, or None when every node agrees alone."""
    import jax.numpy as jnp
    from repro.compiler.engines import EngineContext, select_engine
    from repro.models.cnn import (cnn_forward, conv_layer_forward,
                                  pool_forward)
    ctx = EngineContext(interpret=interpret, act_scale=0.05)
    found = []

    def both(spec, p, xin, act):
        y_q, y_f, _ = select_engine(spec).run(
            ctx, cp.plan.schedule_for(spec.name), p, xin, act)
        if spec.is_pool:
            r_q, r_f = pool_forward(spec, xin), None
        else:
            r_q, r_f = conv_layer_forward(p, spec, xin, act=act)
        dq = int(jnp.max(jnp.abs(y_q.astype(jnp.int32)
                                 - r_q.astype(jnp.int32))))
        df = 0.0 if r_f is None else float(jnp.max(jnp.abs(y_f - r_f)))
        if (dq or df) and not found:
            found.append((spec.name, dq, df))
        return r_q, r_f

    cnn_forward(params, cp.cfg, x, engine=both)
    return found[0] if found else None


def compare_logits(got, want, *, diagnose, log=print):
    """Bit-identity of every request's logits; on a mismatch, name the
    first differing layer and the largest difference, then fail."""
    bad = [i for i, (g, w) in enumerate(zip(got, want))
           if g.shape != w.shape or not np.array_equal(g, w)]
    if not bad:
        return
    i = bad[0]
    diff = (float(np.max(np.abs(got[i] - want[i])))
            if got[i].shape == want[i].shape else float("nan"))
    where = diagnose(i)
    log(f"MISMATCH: requests {bad} differ; request {i}: max |logit diff| "
        f"{diff}; first differing layer (engine vs reference on the same "
        f"input): {where or 'none alone (the fused program differs)'}")
    raise SmokeFailure(f"{len(bad)} request(s) not bit-identical")


def smoke_one_chip(cfg, target, seed: int, *, interpret: bool,
                   log=print) -> None:
    """Compile, serve the mixed requests, and compare with the reference."""
    import jax
    from repro.models.cnn import cnn_forward
    params, images = make_inputs(cfg, seed)
    cp = compile_checked(cfg, target, log=log)

    t0 = time.perf_counter()
    with cp.serve(params, microbatch=MICROBATCH) as eng:
        if eng.interpret != interpret:
            raise SmokeFailure(f"serving engine interpret={eng.interpret}")
        t_warm = time.perf_counter()
        reqs = [eng.submit(x) for x in images]
        eng.drain()
        got = [r.result() for r in reqs]
        rep = eng.report()
    t_end = time.perf_counter()
    log(f"serve(): warm-up trace + XLA compile {t_warm - t0:.3f} s; "
        f"{rep.requests} requests / {rep.images} images in "
        f"{rep.microbatches} microbatches of {rep.microbatch_size}; "
        f"wall {t_end - t_warm:.3f} s (informational, host clock)")
    if rep.requests != len(images) or rep.images != sum(REQUEST_SIZES):
        raise SmokeFailure(f"served {rep.requests} requests / {rep.images} "
                           f"images, submitted {len(images)} / "
                           f"{sum(REQUEST_SIZES)}")
    check_dispatch(cp, params, MICROBATCH, interpret=interpret, log=log)

    # the reference over all requests' images at once (per-image network:
    # batching changes no value), split back per request
    ref = jax.jit(lambda p, x: cnn_forward(p, cfg, x))
    want = np.asarray(ref(params, np.concatenate(images)))
    want = np.split(want, np.cumsum(REQUEST_SIZES)[:-1])
    compare_logits(got, want, log=log, diagnose=lambda i: first_differing_layer(
        cp, params, images[i], interpret=interpret))
    log(f"logits: {len(got)} requests bit-identical to cnn_forward "
        f"(shape per image {got[0].shape[1:]}, finite: "
        f"{all(np.isfinite(g).all() for g in got)})")


def smoke_four_chips(cfg, target, seed: int, devices, *, interpret: bool,
                     log=print) -> None:
    """The sharded path alone: 4 pipeline stages over a 1x4 ``model``
    mesh, compared bit for bit with single-device ``run()``."""
    from jax.sharding import Mesh
    params, images = make_inputs(cfg, seed)
    cp = compile_checked(cfg, target, log=log)
    part = cp.partition(4)
    log(f"partition(4): stage layer ranges "
        f"{[s.layer_range for s in part.stages]}")
    mesh = Mesh(np.asarray(devices[:4]).reshape(1, 4), ("data", "model"))

    t0 = time.perf_counter()
    with cp.serve_sharded(params, mesh=mesh, microbatch=4,
                          round_microbatches=8) as eng:
        if eng.interpret != interpret:
            raise SmokeFailure(f"serving engine interpret={eng.interpret}")
        t_warm = time.perf_counter()
        reqs = [eng.submit(x) for x in images]
        eng.drain()
        got = [r.result() for r in reqs]
        rep = eng.report()
    t_end = time.perf_counter()
    log(f"serve_sharded(): staged compile {t_warm - t0:.3f} s; "
        f"{rep.requests} requests / {rep.images} images in {rep.rounds} "
        f"round(s) over {rep.n_stages} stages; wall {t_end - t_warm:.3f} s "
        f"(informational, host clock)")

    logits, _ = cp.run(params, np.concatenate(images), interpret=interpret)
    want = np.split(np.asarray(logits), np.cumsum(REQUEST_SIZES)[:-1])
    compare_logits(got, want, log=log, diagnose=lambda i: None)
    log(f"logits: {len(got)} requests bit-identical to single-device run()")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform}); "
              f"nothing was run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2

    from repro import compiler
    from repro.configs.cnn import CNN_CONFIGS
    from repro.launch.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}")
    print(f"device: {devices[0].device_kind} x{len(devices)}; "
          f"jax {jax.__version__}")
    cfg = CNN_CONFIGS["resnet50"]
    target = compiler.NX2100.replace(backend="compiled")
    if args.chips == 4:
        smoke_four_chips(cfg, target, args.seed, devices, interpret=False)
    else:
        smoke_one_chip(cfg, target, args.seed, interpret=False)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
