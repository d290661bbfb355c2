"""Benchmark entry: ``python3 benchmark/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>``, from the root of a checkout.

Prints one JSON result line last on stdout (``harness.run``). Exits 3 with
no result when JAX finds no TPU or fewer chips than the cell asks for:
there is no CPU fallback. JAX's persistent compilation cache lives in the
checkout, at ``.jax_cache`` (a fixed path: the path is part of the key).
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # Before JAX is imported: the program takes the cache JAX is given.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    sys.path.insert(0, ROOT)
    from benchmark import harness

    return harness.main(args, T_PROCESS)


if __name__ == "__main__":
    sys.exit(main())
