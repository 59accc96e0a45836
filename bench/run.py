"""Run one cell of the benchmark on the chip this machine holds.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Prints one JSON line last on standard output; exits non-zero, printing no
result, where JAX finds no TPU or fewer chips than the cell asks for.
"""
import time

T_START = time.perf_counter()

import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

if __name__ == "__main__":
    from bench import harness

    sys.exit(harness.main(t_start=T_START))
