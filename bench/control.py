#!/usr/bin/env python3
"""The control of a cell's comparison: the reference in the program's place,
in the nearest lower precision (int4 weights for the int8 configurations).
The reference, its weights and its layers are the configuration's own
(``spec.reference_of``).

    python3 bench/control.py --workload <cell> --seeds 1,2,3

For each seed it draws what a run's check compares (the cell's number of
requests, sized by its traffic mix, from the cell's image pool and
weights), computes the int8 reference and the int4 control over the same
images, and prints ``logit_gap`` beside the cell's limit: one JSON line
per seed.  The control has to come out as not correct on every seed;
its smallest reading is the upper end a limit may be set below.  The
benchmark's own runs do not run this.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

from benchlib import check, model, spec, traffic  # noqa: E402


def control(cell, seed: int, weight_bits: int = 4) -> dict:
    import numpy as np
    conf, wl = cell.config, cell.workload
    rng = np.random.default_rng([seed, 3])
    n = wl["check"]["requests"]
    sizes = traffic._sizes(rng, cell.mix["images"], n)
    offs = rng.integers(0, wl["pool_images"] - sizes + 1)
    pool = model.image_pool(seed, wl["pool_images"], conf["image"])
    images = np.concatenate([pool[o:o + s] for o, s in zip(offs, sizes)])
    ref = spec.reference_of(conf)
    params = ref.make_params(model.seed_key(seed), conf["layers"],
                             conf["act_scale"])
    kw = dict(act_scale=conf["act_scale"], block=wl["check"]["block"])
    want = ref.logits_in_blocks(params, conf["layers"], images, **kw)
    got = ref.logits_in_blocks(params, conf["layers"], images,
                               weight_bits=weight_bits, **kw)
    ok, checks = check.compare(got, want, 0, wl["check"]["limits"])
    return {"seed": seed, "images": int(len(images)),
            "weight_bits": weight_bits, "correct": ok,
            "logit_gap": checks["logit_gap"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    import jax
    dev = jax.devices()[0]
    for seed in (int(s) for s in args.seeds.split(",")):
        out = control(cell, seed)
        out["device"] = dev.device_kind
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
