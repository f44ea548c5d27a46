"""Serving example: batched greedy decoding with the flash-hash prefix
KV cache (counting refcounts — the paper's §1 refcounting use case).

Run: PYTHONPATH=src python examples/serve_lm.py
"""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

from repro.compile_cache import enable_compile_cache
from repro.launch.serve import main

if __name__ == "__main__":
    enable_compile_cache()
    if "--arch" not in sys.argv:
        sys.argv += ["--arch", "llama32_3b", "--tiny", "--requests", "8",
                     "--prompt-len", "32", "--shared-prefix", "24",
                     "--max-new", "8"]
    main()
