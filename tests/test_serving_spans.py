"""The serving engines' dispatcher spans and per-request launch stamps.

Contract under test (runtime/cnn_serving.py, runtime/sharded_serving.py):

  * every dispatch of :class:`CnnServingEngine` is one ``dispatch`` span
    holding, in order, ``fill`` (the packed buffer), ``credit_wait``,
    ``h2d`` (the host-to-device copy) and ``launch`` (the program call),
    and carries its ``seq`` and request ids;
  * ``pack`` opens once the first row is in hand: the dispatcher's wait
    on an empty queue lies outside every span, in both engines (the fix
    lives in the shared :class:`MicrobatchPacker`);
  * every delivered request carries ``t_submit <= t_launch <= t_done``
    on the engine clock, tracing on or off, and ``launch_seq`` names the
    dispatch that carried its last row.
"""
import queue
import threading
import time

import jax
import numpy as np
import pytest

from repro import compiler
from repro.compiler import TPU_INTERPRET
from repro.configs.cnn import mini_resnet18
from repro.launch.mesh import compat_make_mesh
from repro.models.cnn import cnn_input_shape, init_cnn_params
from repro.obs import ManualClock, Tracer
from repro.runtime.cnn_serving import CnnRequest, MicrobatchPacker

MINI = mini_resnet18(hw=8, width=16, stages=4)
DISPATCH_STEPS = ("fill", "credit_wait", "h2d", "launch")


@pytest.fixture(scope="module")
def setup():
    cp = compiler.compile(MINI, TPU_INTERPRET)
    params = init_cnn_params(jax.random.PRNGKey(0), MINI)
    return cp, params


def _requests(sizes, seed=0):
    rng = np.random.default_rng(seed)
    shape = cnn_input_shape(MINI, 1)[1:]
    return [rng.integers(-127, 128, size=(n,) + shape,
                         dtype=np.int16).astype(np.int8) for n in sizes]


def _spans(tracer, name):
    """``(start, end, args)`` of the complete spans called ``name``."""
    return [(ts, ts + dur, args or {}) for ph, n, _track, ts, dur, _id, args
            in tracer.events() if ph == "X" and n == name]


def _served(eng, sizes):
    reqs = [eng.submit(b) for b in _requests(sizes)]
    eng.drain(timeout=300)
    return reqs, eng.report()


def test_every_dispatch_holds_fill_h2d_launch(setup):
    cp, params = setup
    tr = Tracer()
    with cp.serve(params, microbatch=4, credits=2, tracer=tr) as eng:
        reqs, rep = _served(eng, [1, 3, 2, 5, 1, 6])
    parents = _spans(tr, "dispatch")
    assert sorted(a["seq"] for _, _, a in parents) == list(
        range(1, rep.microbatches + 1))
    children = {n: _spans(tr, n) for n in DISPATCH_STEPS}
    for n, spans in children.items():
        assert len(spans) == len(parents), n
    for p0, p1, args in parents:
        inside = [[(s, e) for s, e, _ in children[n] if p0 <= s and e <= p1]
                  for n in DISPATCH_STEPS]
        assert all(len(got) == 1 for got in inside), args["seq"]
        steps = [got[0] for got in inside]
        # fill, credit_wait, h2d, launch follow one another in order
        assert all(a[1] <= b[0] for a, b in zip(steps, steps[1:]))
        assert set(args["rids"]) <= {r.rid for r in reqs}


def test_packer_pack_span_opens_after_the_first_row():
    clk = ManualClock()
    tr = Tracer(clock=clk)
    q = queue.Queue()
    packer = MicrobatchPacker(q, microbatch=4, tracer=tr)
    req = CnnRequest(1, np.zeros((3, 2, 2, 1), np.int8), now=0.0)

    def late():
        time.sleep(0.05)
        clk.advance(5.0)
        q.put(req)

    t = threading.Thread(target=late)
    t.start()
    rows, filled = packer.collect()
    t.join(5.0)
    assert not t.is_alive()
    assert filled == 3 and rows == [(req, 0, 0, 3)]
    [(start, _end, _)] = _spans(tr, "pack")
    assert start >= 5.0                 # the blocked wait is not packing
    # nothing queued and not blocking: no pack at all, no span
    assert packer.collect(block=False) is None
    assert len(_spans(tr, "pack")) == 1


def _first_pack_after_idle_wait(eng, tr):
    time.sleep(0.2)                     # the dispatcher blocks, queue empty
    [req] = [eng.submit(b) for b in _requests([2])]
    eng.drain(timeout=300)
    packs = _spans(tr, "pack")
    assert packs
    assert min(s for s, _, _ in packs) >= req.t_submit
    return req


def test_pack_span_excludes_the_wait_for_the_first_request(setup):
    cp, params = setup
    tr = Tracer()
    with cp.serve(params, microbatch=4, credits=2, tracer=tr) as eng:
        _first_pack_after_idle_wait(eng, tr)


def test_sharded_pack_span_excludes_the_wait_and_stamps_launch(setup):
    cp, params = setup
    tr = Tracer()
    mesh = compat_make_mesh((1,), ("model",))
    with cp.serve_sharded(params, mesh=mesh, microbatch=4,
                          round_microbatches=2, tracer=tr) as eng:
        req = _first_pack_after_idle_wait(eng, tr)
    assert req.t_submit <= req.t_launch <= req.t_done
    assert req.launch_seq == 1


@pytest.mark.parametrize("traced", [False, True])
def test_every_request_is_stamped_at_its_launch(setup, traced):
    cp, params = setup
    tr = Tracer() if traced else None
    with cp.serve(params, microbatch=4, credits=2, tracer=tr) as eng:
        reqs, rep = _served(eng, [1, 3, 2, 5, 1, 6, 4])
    for r in reqs:
        assert r.done
        assert r.t_submit <= r.t_launch <= r.t_done
        assert 1 <= r.launch_seq <= rep.microbatches
    if traced:
        # the dispatch that launched a request is the last one carrying it
        carried = {}
        for _, _, args in _spans(tr, "dispatch"):
            for rid in args["rids"]:
                carried[rid] = max(carried.get(rid, 0), args["seq"])
        assert {r.rid: r.launch_seq for r in reqs} == carried
        ends = {i: (a or {}).get("launch_seq")
                for ph, n, _, _, _, i, a in tr.events()
                if ph == "e" and n == "request"}
        assert ends == {r.rid: r.launch_seq for r in reqs}
