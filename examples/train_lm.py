"""End-to-end driver: pretrain a (reduced) model for a few hundred steps
through the full stack — deterministic loader, TF-IDF data filter, AdamW,
checkpoint/restart runtime. Any of the 10 assigned architectures works
via --arch; default trains a ~tiny llama3.2 on CPU in a couple minutes.

Run: PYTHONPATH=src python examples/train_lm.py [--arch mamba2_2p7b]
     (full-size archs: omit --tiny on a real pod slice)
"""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

from repro.compile_cache import enable_compile_cache
from repro.launch.train import main

if __name__ == "__main__":
    enable_compile_cache()
    sys.argv += ["--tiny", "--steps", "200", "--ckpt-dir",
                 "/tmp/repro_ckpt"] if "--steps" not in sys.argv else []
    main()
