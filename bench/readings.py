#!/usr/bin/env python3
"""Read the compared numbers of one cell on many seeds in one process:
the program's (sound runs, the lower readings of each limit), with
``--control`` the bfloat16 reference's in its place (the upper readings),
or with ``--fault <name>`` the program's with a fault of
``yardstick/faults.py`` planted. The benchmark's own runs never run this.

    python3 bench/readings.py --workload <name> --seeds 1,2,3 \
        --seconds <s> [--control | --fault extra_step|swapped]

Each seed runs the cell's traffic at the cell's size for ``--seconds``
and prints one JSON line with the numbers compared and their limits.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
# libtpu writes its logs under /tmp unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, BENCH)
sys.path.insert(1, os.path.join(os.path.dirname(BENCH), "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--fault")
    args = ap.parse_args(argv)

    import harness

    t0 = T_START
    for seed in (int(s) for s in args.seeds.split(",")):
        try:
            res = harness.run(args.workload, seed, args.seconds, False,
                              t_start=t0, control=args.control,
                              fault=args.fault)
        except harness.Refused as e:
            print(f"readings: {e}", file=sys.stderr)
            return 2
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": args.control, "fault": args.fault,
                          "correct": res["correct"],
                          "compared": res["compared"],
                          "metrics": res["metrics"], "run": res["run"],
                          "device": res["device"]}), flush=True)
        t0 = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
