#!/usr/bin/env python3
"""The int8 control of ``correct``, on the chip, for one cell and seed.

    python3 chipbench/control.py --workload <cell> --seed <n> \\
        --seconds 10 --trace 0

It makes one run of the cell as ``chipbench/run.py`` does, at the cell's
own load, and on the same captured calls also reads the int8 (W8A8)
reference put in the program's place. Its last line is the run's result
line with ``control_correct``, the control judged by the configuration's
own limits, which has to be false, and ``control_checks``; standard
error has the program's and the control's readings of every number
(``readings``, ``control_readings``). The benchmark's own runs never run
the control.
"""
import time

T_START = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
HERE = str(Path(__file__).resolve().parent)

if __name__ == "__main__":
    sys.path[:] = [p for p in sys.path if p != HERE]
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from chipbench.harness import main
    sys.exit(main(sys.argv[1:], t_start=T_START, root=ROOT, control=True))
