"""Readings that set the limits of ``checks.py``, in one process.

    python benchmarks/chip/readings.py --workload <cell> --seconds <s>
        --seeds <n> ... [--control-seeds <n> ...]

For each of ``--seeds``: the cell's set-up and a short window through its
timed path, exactly as ``bench.py`` runs them, and the numbers ``checks.py``
compares.  For each of ``--control-seeds``: the same numbers for the control, the
reference computed in bfloat16 put in the program's place, on that seed's
tables (the program's window runs only for ``--seeds``).
The lower reading of a limit is the largest program reading, the upper
reading the smallest control reading.  One JSON line per seed.  Needs the
chip, as ``bench.py`` does; the benchmark's own runs never run the control.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import bench  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)

    cell = bench.load_cell(args.workload)
    bench.import_program()
    bench.use_checkout_dirs()
    bench.require_devices(cell.chips)
    import checks
    from tpch import datagen, queries, reference

    stream = cell.traffic["stream"]
    queries_ = list(dict.fromkeys(stream))
    for seed in dict.fromkeys(args.seeds + args.control_seeds):
        if seed in args.seeds:
            tables, ctx, entry = bench.set_up(cell, seed)
            records, answers, _ = bench.run_window(entry, stream, args.seconds)
            del entry, ctx
            gc.collect()
        else:
            tables = datagen.generate(cell.config["scale_factor"], seed)
        wants = {q: reference.REFERENCES[q](tables, reference.REFERENCE) for q in queries_}
        if seed in args.seeds:
            found = checks.judge(answers, wants, queries.GROUP_KEYS,
                                 missing=sum(r.error is not None for r in records))
            print(json.dumps({"side": "program", "seed": seed, "answers": len(answers),
                              "checks": found}), flush=True)
        if seed in args.control_seeds:
            control = [(q, reference.REFERENCES[q](tables, reference.CONTROL))
                       for q in queries_]
            found = checks.judge(control, wants, queries.GROUP_KEYS, missing=0)
            print(json.dumps({"side": "control", "seed": seed,
                              "answers": len(control), "checks": found}), flush=True)
        del tables
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
