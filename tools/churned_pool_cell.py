#!/usr/bin/env python3
"""``dots3-longdoc-decode`` on a pool AFTER CHURN: ``benchmark/run.py`` as
it is, with every ``BlockManager``'s free list put in a seeded random order
whenever it is reset (the engine resets its managers once it has built
them), so that the documents the kind ingests lie in no run of
consecutive blocks and the indexer's walk (``dsa_index_scores_decode``)
copies page by page. A builder's experiment, never the driver's: the cell
ingests its documents into a FRESH pool (falling consecutive blocks, the
walk's fast path), and this is what it would read otherwise (PERF.md
section 7). Needs a TPU.

    python3 tools/churned_pool_cell.py --seed <n> [--trace 0|1]
"""
import os
import runpy
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import numpy as np                                            # noqa: E402

from paddle_tpu.serving import paging                         # noqa: E402

_reset = paging.BlockManager.reset


def churned(self):
    _reset(self)
    np.random.default_rng(len(self._free)).shuffle(self._free)


paging.BlockManager.reset = churned
sys.argv = [os.path.join(ROOT, "benchmark", "run.py"), "--workload",
            "dots3-longdoc-decode"] + sys.argv[1:]
runpy.run_path(sys.argv[0], run_name="__main__")
