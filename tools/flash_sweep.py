"""Sweep Pallas flash-attention block sizes at the 345M bench shapes.

r3 tuned blocks by comparing 128x128 vs 512x1024 only.  With causal
masking at S=1024, BK=1024 means every q-block computes the full
[BQ, 1024] score tile and masks ~half of it away; smaller BK lets the
`live` guard skip fully-masked blocks entirely (25% of issued work at
BK=BQ=512).  Whether that beats the per-grid-step fixed cost is a
hardware question — this sweeps it.

Usage (on the chip, through the chip tool): python tools/flash_sweep.py
"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from mxu_probe import slope_time          # noqa: E402
from step_ablation import make_flash_runners  # noqa: E402

BLOCKS = [(512, 1024), (512, 512), (256, 512), (1024, 1024), (256, 1024),
          (1024, 512)]


def main():
    print(f"{'bq':>5} {'bk':>5} {'fwd ms':>8} {'fwd+bwd ms':>11}")
    for bq, bk in BLOCKS:
        # one noisy config must not abort a scarce hardware window
        try:
            run_fwd, run_bwd, q, k, v = make_flash_runners(block_q=bq,
                                                           block_k=bk)
            t_f = slope_time(lambda n: float(run_fwd(q, k, v, n)), 10, 50)
            t_fb = slope_time(lambda n: float(run_bwd(q, k, v, n)), 10, 50)
        except RuntimeError as e:
            print(f"{bq:>5} {bk:>5}  noise/err: {e}", flush=True)
            continue
        print(f"{bq:>5} {bk:>5} {t_f*1e3:>8.3f} {t_fb*1e3:>11.3f}",
              flush=True)


if __name__ == "__main__":
    main()
