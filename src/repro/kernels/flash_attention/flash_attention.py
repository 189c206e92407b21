"""Pallas TPU flash attention (blocked online-softmax, never materializes S×S).

The matrix-free dual of the paper's EBE idea applied to attention (DESIGN.md
§4): recompute/streamed tiles instead of a stored quadratic object.  Used by
the serving path (prefill) and validated in interpret mode on CPU.

Grid ``(B, Hq, nQ, nKV)`` with the KV dimension innermost/sequential;
running max/sum and the output accumulator live in VMEM scratch across KV
steps.  GQA is expressed through the k/v BlockSpec index maps
(``h // group``), so no repeated KV materialization.  Supports causal,
sliding-window (Mixtral/Gemma-2 local layers) and tanh soft-capping
(Gemma-2).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30


def _flash_kernel(
    skv_ref,  # scalar prefetch: real kv length [1]  (SMEM)
    q_ref, k_ref, v_ref, out_ref,
    acc_ref, m_ref, l_ref,
    *, scale, causal, window, softcap, tq, tk, skv_minus_sq, nkv,
):
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0]  # [TQ, dh]
    k = k_ref[0, 0]  # [TK, dh]
    v = v_ref[0, 0]  # [TK, dv]

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # [TQ, TK]
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)

    iq = pl.program_id(2)
    qpos = iq * tq + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 0) + skv_minus_sq
    kpos = j * tk + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
    mask = kpos < skv_ref[0]          # padded kv tail
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= qpos - kpos < window
    s = jnp.where(mask, s, _NEG)

    m_prev = m_ref[...]               # [TQ, 128] (col 0 live)
    m_cur = jnp.max(s, axis=1, keepdims=True)  # [TQ,1]
    m_new = jnp.maximum(m_prev, m_cur)         # broadcast over 128
    corr = jnp.exp(m_prev[:, :1] - m_new[:, :1])  # [TQ,1]
    p = jnp.exp(s - m_new[:, :1])
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    m_ref[...] = m_new
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(j == nkv - 1)
    def _final():
        out_ref[0, 0] = (acc_ref[...] / (l_ref[:, :1] + 1e-30)).astype(out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "scale", "tq", "tk", "interpret"),
)
def flash_attention_pallas(
    q: jnp.ndarray,  # [B,Hq,Sq,dh]
    k: jnp.ndarray,  # [B,Hkv,Skv,dh]
    v: jnp.ndarray,  # [B,Hkv,Skv,dv]
    *,
    causal: bool = True,
    window: int | None = None,
    softcap: float | None = None,
    scale: float | None = None,
    tq: int = 128,
    tk: int = 128,
    interpret: bool = True,
) -> jnp.ndarray:
    B, Hq, Sq, dh = q.shape
    Hkv, Skv, dv = k.shape[1], k.shape[2], v.shape[3]
    group = Hq // Hkv
    scale = float(dh**-0.5) if scale is None else float(scale)

    tq_ = min(tq, max(8, Sq))
    tk_ = min(tk, max(128, 128))
    sq_pad = -(-Sq // tq_) * tq_
    skv_pad = -(-Skv // tk_) * tk_
    dh_pad = -(-dh // 128) * 128
    dv_pad = -(-dv // 128) * 128

    qp = jnp.pad(q, ((0, 0), (0, 0), (0, sq_pad - Sq), (0, dh_pad - dh)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, skv_pad - Skv), (0, dh_pad - dh)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, skv_pad - Skv), (0, dv_pad - dv)))

    nq, nkv = sq_pad // tq_, skv_pad // tk_
    grid = (B, Hq, nq, nkv)
    kernel = functools.partial(
        _flash_kernel,
        scale=scale, causal=causal, window=window, softcap=softcap,
        tq=tq_, tk=tk_, skv_minus_sq=Skv - Sq, nkv=nkv,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, tq_, dh_pad), lambda b, h, i, j, skv: (b, h, i, 0)),
                pl.BlockSpec((1, 1, tk_, dh_pad), lambda b, h, i, j, skv: (b, h // group, j, 0)),
                pl.BlockSpec((1, 1, tk_, dv_pad), lambda b, h, i, j, skv: (b, h // group, j, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, tq_, dv_pad), lambda b, h, i, j, skv: (b, h, i, 0)),
            scratch_shapes=[
                pltpu.VMEM((tq_, dv_pad), jnp.float32),
                pltpu.VMEM((tq_, 128), jnp.float32),
                pltpu.VMEM((tq_, 128), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hq, sq_pad, dv_pad), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(jnp.array([Skv], jnp.int32), qp, kp, vp)
    return out[:, :, :Sq, :dv]
