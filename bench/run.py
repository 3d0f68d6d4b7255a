#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chips of this machine.

    python3 bench/run.py --workload cobs-idl.screen --seed 7 --seconds 10 --trace 0

The cell, its configuration and its traffic are looked up by name in
``BENCHMARK.json``. The run builds the cell's index on the device from the
seed, warms every shape the traffic uses, measures for ``--seconds``,
checks the answers against the plain reference, and prints one JSON line
last on stdout. With ``--trace 1`` it profiles the window and reports the
cell's per-layer metrics instead of its end-to-end ones. It exits non-zero,
printing no result, when JAX finds no TPU or fewer chips than the cell
asks for.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="put the reference with the configuration's "
                         "control break in the program's place (the "
                         "comparison must then fail)")
    ap.add_argument("--keep-trace", default=None, metavar="DIR",
                    help="copy the profiler's trace to DIR")
    return ap.parse_args(argv)


def main() -> int:
    args = parse()
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    # the compile cache lives at a fixed path inside the checkout, whatever
    # the environment says, so that two checkouts never share one
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    from bench import harness
    return harness.run(args, T_START)


if __name__ == "__main__":
    sys.exit(main())
