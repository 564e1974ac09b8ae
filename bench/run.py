"""Run one benchmark cell on the chip and print its result line.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

``--trace 0`` reports the cell's end-to-end metrics and ``setup_s``;
``--trace 1`` traces a short steady stretch and reports the per-layer
metrics.  ``--control 1`` puts the reference, computed one precision
lower, in the program's place for the comparison: its run must come
out not correct.  Exits 3, printing no result, when JAX finds no
accelerator listed in ``bench/peaks.json`` or fewer chips than the cell
asks for.
"""
import time

T_START = time.perf_counter()   # set-up is timed from here

import argparse  # noqa: E402
import sys  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness
    harness.prepare_env()
    workload, config = harness.load_cell(args.workload)
    try:
        devices, peaks = harness.find_devices(int(workload["chips"]))
    except harness.DeviceError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    line = harness.run_cell(workload, config, seed=args.seed,
                            seconds=args.seconds, trace=args.trace,
                            t_start=T_START, devices=devices, peaks=peaks,
                            control=args.control)
    harness.emit(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
