"""The controls of the cells' correctness checks, for the chip.

    python3 bench/control.py --workload <name> --seed <n> --seconds <s>

Runs the cell as ``bench/run.py --trace 0`` does, then puts the plain
reference, computed in the next lower precision, in the program's place
on the same sample: the cell's comparison reads the control's numbers
where it reads the program's, and the result line has to say
``"correct": false``. The smallest reading over the seeds is the upper
reading a limit is set below. The benchmark's own runs never run this.
"""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

from lib.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(control=True))
