"""Run one cell of the chip benchmark and print its result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Run from the root of a checkout on a machine that holds the chips the
cell asks for. The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer metrics),
``device``, with ``--trace 1`` ``breakdown``, and last ``checks``: each
number compared with the plain reference beside its limit, which are
also the last lines of standard error. Without an accelerator, or with
fewer chips than the cell asks for, it prints no result and exits 2.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from chipbench import compare, harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True, help="cell name")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True,
                    help="length of the measured window")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="compare the plain reference in the precision "
                         "below the configuration's in place of the "
                         "program's answers; has to come out not correct")
    args = ap.parse_args(argv)
    bench = harness.load_benchmark()
    try:
        out = harness.run(bench, args.workload, args.seed, args.seconds,
                          bool(args.trace), T_START,
                          control=bool(args.control))
    except harness.NoDevice as e:
        harness.log(f"refusing to run: {e}")
        return 2
    for line in compare.lines(out["checks"]):
        print(line, file=sys.stderr)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # Leave at once: nothing the runtime prints while it tears down may
    # follow the result line and the checks.
    os._exit(code)
