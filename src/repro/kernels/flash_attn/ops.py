"""jit'd wrapper: flash attention with oracle fallback.

``flash_attention(q, k, v)`` dispatches to the Pallas kernel (the Pallas
interpreter on the CPU; compiled Mosaic on the TPU). The dense oracle lives in
ref.py; tests sweep shapes/dtypes asserting allclose.
"""
from __future__ import annotations

from .kernel import flash_attention_fwd
from .ref import sdpa_ref  # noqa: F401


def flash_attention(q, k, v, causal: bool = True, block_q: int = 128,
                    block_k: int = 128):
    return flash_attention_fwd(q, k, v, block_q=block_q, block_k=block_k,
                               causal=causal)
