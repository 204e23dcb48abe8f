"""Run one benchmark cell and print its result as the last line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine that holds the chips the
cell asks for. With ``--trace 0`` the line carries the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics read from
a profiler trace of the window. Exits 2 without the program's sources,
3 without an accelerator.
"""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

from lib.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main())
