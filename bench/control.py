#!/usr/bin/env python3
"""The control for ``correct``: the reference at one bit less of Vmem.

    python3 bench/control.py --workload gesture-poisson --seeds 1,2,3

For each seed, builds the cell's clip pool and integer weights exactly as
``bench/run.py`` does, answers every pool clip with the plain reference at
``control_vmem_bits`` (readout, and the chip model's cycles and energy
from its spike counts), puts those answers in the program's place and
judges them with ``run.check_clips``, the comparison that decides
``correct`` in a run.  It prints one JSON line per seed with ``correct``
and the numbers compared beside their limits.  A served clip's result
depends on the clip alone (slots never interact), so these are the
readings the control would give in the program's place over a window
that serves every pool clip, which the cells' windows do.  The
benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import types

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import numpy as np  # noqa: E402

from bench import generator, reference  # noqa: E402
from bench.record import Clip  # noqa: E402
from bench.run import NoChip, cell_plan, check_clips, find_devices, \
    load_benchmark, make_weights  # noqa: E402


def control_result(cfg: dict, weights: list, pool: np.ndarray,
                   chunk_T: int) -> dict:
    """``correct`` and the numbers compared, with the control's answer to
    every pool clip in the program's place."""
    got, counts = reference.reference_run(
        cfg, weights, pool, vmem_bits=cfg["control_vmem_bits"])
    clips = []
    for i in range(len(pool)):
        cycles, energy = reference.chip_cost(cfg, counts[i], chunk_T)
        request = types.SimpleNamespace(readout=got[i], cycles=cycles,
                                        energy_uj=energy)
        clips.append(Clip(rid=i, pool_index=i, due=0.0, submitted=0.0,
                          first_reply=0.0, done=0.0,
                          handle=types.SimpleNamespace(request=request)))
    checks = check_clips(cfg, weights, pool, clips, chunk_T)
    return {"correct": all(v <= lim for v, lim in checks.values()),
            "clips": len(pool),
            "checks": {k: {"value": v, "limit": lim}
                       for k, (v, lim) in checks.items()}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    args = ap.parse_args(argv)
    plan = cell_plan(load_benchmark(ROOT), ROOT, args.workload)
    try:
        devices = find_devices(plan, True)
    except NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    cfg, traffic = plan["cfg"], plan["traffic"]
    qs, _ = make_weights(cfg)
    weights = [np.asarray(q) for q in qs]
    for seed in (int(s) for s in args.seeds.split(",")):
        pool = generator.clip_pool(traffic, cfg, seed, ROOT)
        line = control_result(cfg, weights, pool, cfg["deploy"]["chunk_T"])
        line.update(workload=args.workload, seed=seed,
                    device=f"{devices[0].device_kind} x{len(devices)}")
        for name, c in line["checks"].items():
            print(f"control seed {seed} check {name}: {c['value']} "
                  f"(limit {c['limit']})", file=sys.stderr)
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
