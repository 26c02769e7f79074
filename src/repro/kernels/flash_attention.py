"""Pallas TPU kernel: flash attention (causal / GQA / sliding-window).

The backbone-forward hot spot of the AFL local stage (and of the serving
path) is attention at long sequence length — prefill_32k makes the S² logits
matrix (32768² × heads) unmaterializable, so the kernel computes attention
with the online-softmax streaming recurrence, never leaving VMEM:

  grid = (B·Hq, Sq/bq, Skv/bk) — the kv axis is the innermost, sequential
  ("arbitrary") axis; (m, l, acc) f32 running statistics live in VMEM scratch
  across the kv sweep and the output tile is normalized + flushed on the last
  kv step. GQA maps each query head's grid slot onto its kv head via the
  BlockSpec index map (b·Hkv + h//group), so kv tiles are streamed once per
  query-head group member without a gather. Causal and sliding-window masks
  are evaluated from block-local iotas; kv blocks wholly outside the
  causal/window band are skipped with ``pl.when`` (no MXU work, no mask).

Block sizes default to (bq, bk) = (256, 512) with the 128-lane head dim —
MXU-aligned; the wrapper pads S/D up to block multiples and masks padded keys.

Validated in interpret mode against ``repro.kernels.ref.mha_ref`` over a
shape/dtype/window sweep (tests/test_kernels_attention.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BLOCK_Q = 256
DEFAULT_BLOCK_K = 512
_NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
    *, scale, causal, window, q_offset, skv_valid, bq, bk,
):
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = iq * bq + q_offset      # absolute position of first query row
    kv_start = ik * bk

    # Block-level skip: entire kv block above the causal diagonal, or entirely
    # left of the sliding window, or entirely in key padding.
    relevant = kv_start < skv_valid
    if causal:
        relevant = jnp.logical_and(relevant, kv_start <= q_start + bq - 1)
    if window is not None:
        relevant = jnp.logical_and(relevant, kv_start + bk - 1 >= q_start - window + 1)

    @pl.when(relevant)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale          # (bq, D)
        k = k_ref[0].astype(jnp.float32)                  # (bk, D)
        v = v_ref[0].astype(jnp.float32)                  # (bk, D)
        s = jax.lax.dot_general(                          # (bq, bk)
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = kv_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = k_pos < skv_valid
        if causal:
            mask = jnp.logical_and(mask, k_pos <= q_pos)
        if window is not None:
            mask = jnp.logical_and(mask, k_pos > q_pos - window)
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_ref[:, 0]                               # (bq,)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:, 0] = alpha * l_ref[:, 0] + jnp.sum(p, axis=-1)
        m_ref[:, 0] = m_new
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(ik == nk - 1)
    def _flush():
        l = l_ref[:, 0]
        norm = jnp.where(l > 0.0, 1.0 / jnp.where(l > 0.0, l, 1.0), 0.0)
        o_ref[0] = (acc_ref[...] * norm[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "window", "scale", "q_offset", "block_q", "block_k", "interpret",
    ),
)
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    scale: float | None = None,
    q_offset: int = 0,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
) -> jax.Array:
    """Flash attention. Shapes as in ``ref.mha_ref`` (B, H, S, D)."""
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    assert hq % hkv == 0, (hq, hkv)
    group = hq // hkv
    scale = scale if scale is not None else d ** -0.5

    bq = min(block_q, _ceil_mult(sq, 8))
    bk = min(block_k, _ceil_mult(skv, 8))
    sq_p, skv_p, d_p = _ceil_mult(sq, bq), _ceil_mult(skv, bk), _ceil_mult(d, 128)
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, sq_p - sq), (0, d_p - d)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, skv_p - skv), (0, d_p - d)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, skv_p - skv), (0, d_p - d)))
    qp = qp.reshape(b * hq, sq_p, d_p)
    kp = kp.reshape(b * hkv, skv_p, d_p)
    vp = vp.reshape(b * hkv, skv_p, d_p)

    def kv_index(bh, iq_, ik_):
        return (bh // hq) * hkv + (bh % hq) // group, ik_, 0

    kernel = functools.partial(
        _flash_kernel,
        scale=scale, causal=causal, window=window, q_offset=q_offset,
        skv_valid=skv, bq=bq, bk=bk,
    )
    out = pl.pallas_call(
        kernel,
        grid=(b * hq, sq_p // bq, skv_p // bk),
        in_specs=[
            pl.BlockSpec((1, bq, d_p), lambda bh, iq_, ik_: (bh, iq_, 0)),
            pl.BlockSpec((1, bk, d_p), kv_index),
            pl.BlockSpec((1, bk, d_p), kv_index),
        ],
        out_specs=pl.BlockSpec((1, bq, d_p), lambda bh, iq_, ik_: (bh, iq_, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, sq_p, d_p), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, d_p), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
        name="flash_attention",
    )(qp, kp, vp)
    return out.reshape(b, hq, sq_p, d_p)[:, :, :sq, :d]


def _ceil_mult(v: int, m: int) -> int:
    return ((v + m - 1) // m) * m
