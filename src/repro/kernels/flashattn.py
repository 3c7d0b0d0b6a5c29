"""Flash attention (forward) — Pallas TPU kernel.

Beyond-paper §Perf: the roofline baseline shows attention score tiles
dominating the memory term on train/prefill cells — XLA materializes the
(qb,kb) probability tile in HBM between the two dots. This kernel keeps
the running max/denominator/accumulator in VMEM scratch and streams K/V
blocks, so HBM traffic is exactly Q+K+V+O — the flash bound.

GQA-aware: query head h reads KV head h // group_size via the BlockSpec
index map (no KV replication). Validated against ref.py's oracle in
interpret mode (tests/test_kernels.py); on a TPU backend the same call
compiles to Mosaic.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret


def _flash_fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *, kv_block, causal, seq_kv
):
    qi = pl.program_id(2)
    qb = q_ref.shape[1]
    hd = q_ref.shape[2]
    q = q_ref[0].astype(jnp.float32) * hd**-0.5  # (qb, hd)

    m_scr[...] = jnp.full_like(m_scr, -1e30)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

    nk = seq_kv // kv_block

    def body(ki, _):
        k_blk = k_ref[0, pl.ds(ki * kv_block, kv_block), :].astype(jnp.float32)
        v_blk = v_ref[0, pl.ds(ki * kv_block, kv_block), :].astype(jnp.float32)
        s = q @ k_blk.T  # (qb, kb) — VMEM-resident tile
        if causal:
            qpos = qi * qb + jax.lax.broadcasted_iota(jnp.int32, (qb, kv_block), 0)
            kpos = ki * kv_block + jax.lax.broadcasted_iota(
                jnp.int32, (qb, kv_block), 1
            )
            s = jnp.where(qpos >= kpos, s, -1e30)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_scr[...] = l_scr[...] * alpha + p.sum(-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + p @ v_blk
        m_scr[...] = m_new
        return 0

    jax.lax.fori_loop(0, nk, body, 0)
    o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def flash_attention_pallas(
    q: jnp.ndarray,  # (B, H, Sq, hd)
    k: jnp.ndarray,  # (B, KH, Skv, hd)
    v: jnp.ndarray,
    *,
    causal: bool = True,
    q_block: int = 128,
    kv_block: int = 128,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    B, H, Sq, hd = q.shape
    KH, Skv = k.shape[1], k.shape[2]
    G = H // KH
    assert Sq % q_block == 0 and Skv % kv_block == 0
    nq = Sq // q_block
    grid = (B, H, nq)
    return pl.pallas_call(
        functools.partial(
            _flash_fwd_kernel, kv_block=kv_block, causal=causal, seq_kv=Skv
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, q_block, hd), lambda b, h, qi: (b * H + h, qi, 0)),
            pl.BlockSpec((1, Skv, hd), lambda b, h, qi: (b * KH + h // G, 0, 0)),
            pl.BlockSpec((1, Skv, hd), lambda b, h, qi: (b * KH + h // G, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, q_block, hd), lambda b, h, qi: (b * H + h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((q_block, 1), jnp.float32),
            pltpu.VMEM((q_block, 1), jnp.float32),
            pltpu.VMEM((q_block, hd), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(
        q.reshape(B * H, Sq, hd),
        k.reshape(B * KH, Skv, hd),
        v.reshape(B * KH, Skv, hd),
    ).reshape(B, H, Sq, hd)


def flash_hbm_bytes(
    B, H, KH, Sq, Skv, hd, q_block: int = 128, dtype_bytes: int = 2
) -> int:
    """Exact HBM traffic of the kernel (the roofline replacement for
    materialized-tile accounting): Q read + O written once; K/V streamed
    once per query-block pass (nq passes)."""
    q_o = 2 * B * H * Sq * hd * dtype_bytes
    nq = max(1, Sq // q_block)
    kv = 2 * B * KH * Skv * hd * dtype_bytes * nq
    return q_o + kv
