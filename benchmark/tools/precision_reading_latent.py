#!/usr/bin/env python3
"""Chip tool, run once when a latent configuration's tolerance is set:
the SECOND reading its limit is set from. It is
benchmark/tools/precision_reading_moe.py's reading (the float32
reference against itself on matrices rounded to float8_e4m3fn, at
positions whose router choice is no near-tie in either run), asked of
this family's reference (benchmark/reference/xing_latent.py): the
rounding reaches the latent projections, the mixing projections and
the experts alike, all of them matrices. ONE seed a process unless
`--seeds` names more: the sibling keeps a seed's weights while it draws
the next seed's, and two sets of 9.6 GB do not fit a chip (my chip run,
PR 40: the second seed ended in RESOURCE_EXHAUSTED).

    python3 benchmark/tools/precision_reading_latent.py \
        [--config xing4-29b --length 6400 --margin 0.005 --seeds N]
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.tools import precision_reading_moe  # noqa: E402

if __name__ == "__main__":
    if "--config" not in sys.argv:
        sys.argv += ["--config", "xing4-29b"]
    if "--seeds" not in sys.argv:
        sys.argv += ["--seeds", "2147484101"]
    sys.exit(precision_reading_moe.main())
