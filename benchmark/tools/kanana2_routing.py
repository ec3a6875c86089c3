#!/usr/bin/env python3
"""``tools/routing_balance.py`` for the latent-attention decoder: that tool
builds ``models.Laguna`` from the configuration's keys, and this family's keys
are read by ``models.DeepseekV3Config``; its walk (a block's ``self_attn``, its
norms and its ``mlp``) fits these blocks as it is.  So this is that tool with
the two names it builds the model from pointed at the family's, on the chip at
the cell's own size.  ``init_std`` of ``configs/kanana-2-30b-a3b.json`` was
chosen from this sweep (PERF.md section 6, PR 48).

    python benchmark/tools/kanana2_routing.py --std 0.02 0.05 0.1 --seeds 4800000001 7
"""

import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(BENCH_DIR))
sys.path.insert(0, os.path.join(BENCH_DIR, "tools"))


def main():
    from apex_tpu import models
    import routing_balance
    models.Laguna, models.LagunaConfig = models.DeepseekV3, models.DeepseekV3Config
    if "--workload" not in sys.argv:
        sys.argv[1:1] = ["--workload", "kanana-2-30b-a3b.pretrain-8k"]
    routing_balance.main()


if __name__ == "__main__":
    main()
