"""End-to-end driver: train the ~126M-param LM on synthetic data.

  PYTHONPATH=src python examples/train_lm.py          # short demo
  PYTHONPATH=src python -m repro.launch.train \
      --arch lm-100m --steps 300 --seq 128 --batch 4 \
      --ckpt-dir results/ckpt_100m                    # the full run

The full 300-step run's loss curve is recorded in
results/train_100m.jsonl (see EXPERIMENTS.md).
"""
import sys

from repro.launch.train import main
from repro.launch import compile_cache

if __name__ == "__main__":
    compile_cache.enable()
    sys.exit(main([
        "--arch", "lm-100m", "--steps", "8", "--seq", "128",
        "--batch", "2", "--log-every", "2",
    ]))
