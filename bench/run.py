"""Run one benchmark cell; see ``bench/harness.py``.

  python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1
"""
import pathlib
import sys
import time

T_START = time.perf_counter()
BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
# bench/ itself off the path: its module names must not shadow the stdlib's
sys.path[:] = [str(ROOT), str(ROOT / "src")] + [
    p for p in sys.path if pathlib.Path(p or ".").resolve() != BENCH]

from bench.harness import main  # noqa: E402

if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:], t_start=T_START))
