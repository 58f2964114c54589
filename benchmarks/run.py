"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run is one process: it loads, warms up, measures one window through
the program's own ``Trainer`` loop, prints one JSON line and exits. It
fails (non-zero, no metric line) off the chip. Everything it does is in
``benchmarks/harness``; this file only fixes the clock's zero and the
import path.
"""

import time

_PROCESS_T0 = time.perf_counter()  # before any heavy import: setup_s starts here

import os  # noqa: E402
import sys  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

from benchmarks.harness import cli  # noqa: E402

if __name__ == "__main__":
    sys.exit(cli.main(sys.argv[1:], root=_ROOT, process_t0=_PROCESS_T0))
