#!/usr/bin/env python3
"""Readings that set a cell's limits for ``correct`` (``bench/limits/``).

    python3 bench/calibrate.py --workload <cell> --seeds 101 102 ... \
        [--out calib.json]

For each seed: the cell's set-up and check rounds on the program, then the
reference (float32, ``highest``) over the same cohorts from the same
start, and in the program's place:

  program     the program's own readings (sound runs: the lower reading)
  bf16        the reference computed in bfloat16 (the control)
  half        the reference with half of every cohort left out
  altered     the reference with one client's labels shifted by a class
  misassign   the reference with each round's first newcomer sent to its
              most dissimilar group (cells with newcomers)

each turned into the compared numbers of ``bench/compare.py``. A round
that returns its state unchanged reads 1 on update_gap and change_gap by
their definition and needs no run. No window is run. Needs the chip.
"""
import argparse
import gc
import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_ROOT, os.path.join(_ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np  # noqa: E402

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    harness.start_jax()
    parts = harness.load_cell(args.workload)
    harness.device_info(parts["cell"]["chips"])

    import jax.numpy as jnp
    from bench import compare
    from bench import generators as gen

    config, traffic = parts["config"], parts["traffic"]
    variants = {"bf16": {"dtype": jnp.bfloat16}, "half": {"drop_half": True},
                "altered": {"alter_client": 0}, "misassign": {"misassign": True}}
    rows = []
    for seed in args.seeds:
        t0 = time.perf_counter()
        data = gen.make_data(seed, config, traffic)
        tr = harness.build_trainer(config, traffic, seed, data)
        feed = harness.Feed(tr)
        tr.group_cold_start()
        start, prog, cohorts = harness.check_rounds(
            tr, feed, data, int(traffic["check_rounds"]))
        ids = harness.eval_ids(tr)
        tr.close()
        del tr
        gc.collect()
        t1 = time.perf_counter()
        ref = harness.reference_rounds(data, config, start, cohorts, prog, ids)
        t2 = time.perf_counter()
        row = {"seed": seed, "program_s": t1 - t0, "reference_s": t2 - t1,
               "program": compare.numbers(start, prog, ref),
               "loss": [p["loss"] for p in prog],
               "ref_loss": [r["loss"] for r in ref],
               "correct": [p["correct"] for p in prog],
               "ref_correct": [r["correct"] for r in ref],
               "membership_same": [bool(np.array_equal(
                   p["membership"], r["membership"]))
                   for p, r in zip(prog, ref)]}
        for name, kw in variants.items():
            alt = harness.reference_rounds(data, config, start, cohorts, prog,
                                           ids, follow=False, **kw)
            for a, p in zip(alt, prog):
                a["n_test"] = p["n_test"]
            # the reference follows the variant's own newcomer choices
            same = all(np.array_equal(a["membership"], r["membership"])
                       for a, r in zip(alt, ref))
            row[name] = compare.numbers(start, alt, ref if same else
                                        harness.reference_rounds(
                                            data, config, start, cohorts, alt,
                                            ids))
        print(json.dumps(row), flush=True)
        rows.append(row)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"workload": args.workload, "rows": rows}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
