"""End-to-end LM training on the framework's data/optimizer/checkpoint
substrate (any of the 10 assigned architectures via --arch; reduced configs
by default so this runs in minutes on CPU).

  PYTHONPATH=src python examples/train_lm.py --arch zamba2-2.7b --steps 120
  PYTHONPATH=src python examples/train_lm.py --arch qwen3-1.7b --layers 4 \
      --d-model 256 --steps 300 --ckpt-dir /tmp/ckpt

Fault-tolerance demo (crash + auto-resume):
  PYTHONPATH=src python examples/train_lm.py --ckpt-dir /tmp/ft --fail-at-step 60
  PYTHONPATH=src python examples/train_lm.py --ckpt-dir /tmp/ft
"""
import sys

from repro.compile_cache import enable_compile_cache
from repro.launch.train import main

if __name__ == "__main__":
    enable_compile_cache()
    main(sys.argv[1:] or ["--steps", "120", "--batch", "8", "--seq", "128"])
