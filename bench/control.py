#!/usr/bin/env python3
"""The benchmark's control: a whole run with every score reduction
computed in bfloat16, the precision below the kernel's float32, in the
kernel's place.  Its check must come out not correct.

    python3 bench/control.py --workload <cell> --seed <n> --seconds <s> --trace 0

It takes the arguments of ``bench/run.py``, refuses the same way, and
prints the same lines; only what runs under the three ``score_reduce*``
entry points differs.  The benchmark's own runs never run it.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import run  # noqa: E402
from bench.audit import bf16_reductions  # noqa: E402

if __name__ == "__main__":
    sys.exit(run.main(replace=bf16_reductions()))
