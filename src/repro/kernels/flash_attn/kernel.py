"""Pallas TPU flash-attention (forward): causal GQA, online softmax.

Tiling: grid (b·kvh·g, nq); each step owns one (block_q × d) query tile and
scans KV in (block_k × d) tiles held in VMEM — running max/denominator/
accumulator live in VMEM scratch for the whole row of KV tiles, so the
only HBM traffic is Q/K/V reads and O writes (the point of the kernel;
cf. EXPERIMENTS.md §Perf granite iteration 1, where the lax.scan
formulation was refuted because XLA materializes scan carries per step).

MXU alignment: block_q/block_k multiples of 128 on real TPUs (the lane
dim); head_dim is the minor-most dim of every tile. Validated against
``ref.sdpa_ref`` in the Pallas interpreter (CPU) across shape/dtype
sweeps in tests/test_flash_attn.py; compiled on the TPU
(:func:`repro.kernels.pallas.pallas_call` decides, from the platform).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..pallas import pallas_call


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, *, block_k: int, scale: float,
                causal: bool):
    _, block_q, d = q_ref.shape
    s = k_ref.shape[1]
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale         # (bq, d) in VMEM
    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, 1), 0)

    nk = s // block_k

    def body(ki, carry):
        m, l, acc = carry
        k_tile = k_ref[0, pl.ds(ki * block_k, block_k), :].astype(
            jnp.float32)
        v_tile = v_ref[0, pl.ds(ki * block_k, block_k), :]
        scores = jax.lax.dot_general(
            q, k_tile, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)      # (bq, bk)
        if causal:
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (1, block_k), 1)
            scores = jnp.where(k_pos <= q_pos, scores, -1e30)
        m_new = jnp.maximum(m, scores.max(axis=1, keepdims=True))
        p = jnp.exp(scores - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(axis=1, keepdims=True)
        acc_new = acc * alpha + jax.lax.dot_general(
            p.astype(v_tile.dtype), v_tile, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m0 = jnp.full((block_q, 1), -1e30, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    a0 = jnp.zeros((block_q, v_ref.shape[2]), jnp.float32)
    if causal:  # skip fully-masked KV tiles (static grid bound per q tile)
        upper = jnp.minimum(
            jnp.maximum(((qi + 1) * block_q + block_k - 1) // block_k, 1),
            nk)
    else:
        upper = nk
    m, l, acc = jax.lax.fori_loop(0, upper, body, (m0, l0, a0))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("block_q", "block_k", "causal"))
def flash_attention_fwd(q, k, v, block_q: int = 128, block_k: int = 128,
                        causal: bool = True):
    """q: (b, s, h, d); k/v: (b, s, kvh, d/dv) → o: (b, s, h, dv).

    GQA: query head hq reads kv head hq // (h // kvh).
    """
    b, s, h, d = q.shape
    kvh = k.shape[2]
    dv = v.shape[3]
    g = h // kvh
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    assert s % block_q == 0 and s % block_k == 0
    scale = 1.0 / (d ** 0.5)

    # flatten (b, h) into the grid's first axis; block index maps pick the
    # right batch row / kv head for each q head
    qt = q.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    kt = k.transpose(0, 2, 1, 3).reshape(b * kvh, s, d)
    vt = v.transpose(0, 2, 1, 3).reshape(b * kvh, s, dv)

    kern = functools.partial(_fwd_kernel, block_k=block_k, scale=scale,
                             causal=causal)
    out = pallas_call(
        kern,
        grid=(b * h, s // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((1, s, d),
                         lambda bh, qi, g=g, kvh=kvh:
                         ((bh // (g * kvh)) * kvh + (bh % (g * kvh)) // g,
                          0, 0)),
            pl.BlockSpec((1, s, dv),
                         lambda bh, qi, g=g, kvh=kvh:
                         ((bh // (g * kvh)) * kvh + (bh % (g * kvh)) // g,
                          0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, dv), lambda bh, qi: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, s, dv), v.dtype),
    )(qt, kt, vt)
    return out.reshape(b, h, s, dv).transpose(0, 2, 1, 3)
