"""``python3 -m perfbench.run --workload <name> --seed <n> --seconds <s>
--trace <0|1>``: one run of one cell. See ``harness.py``."""

import os
import sys
import time

# Set-up counts from here: the interpreter's own start (some 20 ms) is the
# one part of the process's life before it.
STARTED = time.time()


if __name__ == '__main__':
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from perfbench import harness
    sys.exit(harness.run(sys.argv[1:], STARTED))
