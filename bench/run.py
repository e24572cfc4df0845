"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Exits non-zero, with no result line, when JAX finds no TPU or fewer chips
than the cell asks for. `--rehearse` runs the cell on whatever JAX finds
(the CPU here) and prints counts and checks only, never a metric.
"""
import time

T_PROCESS = time.monotonic()

import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

# libtpu logs under /tmp unless told otherwise; a run writes only inside
# its checkout and the directories it is given
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_process=T_PROCESS))
