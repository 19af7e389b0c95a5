"""jit'd wrapper + ref oracle for the flash-attention kernel.

``flash_attention(q, k, v, causal=...)`` takes model-layout tensors
(B, T, H, Dh) / (B, S, Hkv, Dh), pads to block multiples, folds
batch×head planes, and dispatches to the Pallas kernels (interpret mode
on CPU).  Custom VJP: backward re-computes attention per q-chunk via
``jax.vjp`` of the reference on the chunk — O(chunk·S) memory, exact.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention_fwd_pallas
from repro.kernels.ops import _on_tpu

_LANE = 128
_SUBLANE = 8


def _round_up(v, m):
    return (v + m - 1) // m * m


def flash_attention_ref(q, k, v, *, causal=True, q_offset=0):
    """Pure-jnp GQA oracle. q: (B,T,H,Dh), k/v: (B,S,Hkv,Dh)."""
    b, tq, h, dh = q.shape
    hkv = k.shape[2]
    rep = h // hkv
    qg = q.reshape(b, tq, hkv, rep, dh)
    s = jnp.einsum("bqhrd,bkhd->bhrqk", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * (dh ** -0.5)
    if causal:
        rows = jnp.arange(tq)[:, None] + q_offset
        cols = jnp.arange(k.shape[1])[None, :]
        s = jnp.where((cols <= rows)[None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhrqk,bkhd->bqhrd", p, v.astype(jnp.float32))
    return out.reshape(b, tq, h, dh)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q, k, v, causal: bool = True, q_offset: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    bwd_chunk: int = 128):
    return _fwd_impl(q, k, v, causal, q_offset, block_q, block_k)


def _fwd_impl(q, k, v, causal, q_offset, block_q, block_k):
    b, tq, h, dh = q.shape
    s_len, hkv = k.shape[1], k.shape[2]
    rep = h // hkv
    br = min(block_q, _round_up(tq, _SUBLANE))
    bc = min(block_k, _round_up(s_len, _LANE))
    tqp = _round_up(tq, br)
    tkp = _round_up(s_len, bc)
    dhp = _round_up(dh, _LANE)

    def fold(x, heads, t_pad):
        x = jnp.pad(x.astype(jnp.float32),
                    ((0, 0), (0, t_pad - x.shape[1]), (0, 0),
                     (0, dhp - dh)))
        return x.transpose(0, 2, 1, 3).reshape(b * heads, -1, dhp)

    qf, kf, vf = fold(q, h, tqp), fold(k, hkv, tkp), fold(v, hkv, tkp)
    out = flash_attention_fwd_pallas(
        qf, kf, vf, rep=rep, scale=dh ** -0.5, q_len=tq, kv_len=s_len,
        causal=causal, q_offset=q_offset, br=br, bc=bc,
        interpret=not _on_tpu())
    out = out.reshape(b, h, tqp, dhp).transpose(0, 2, 1, 3)
    return out[:, :tq, :, :dh].astype(q.dtype)


def _fwd_rule(q, k, v, causal, q_offset, block_q, block_k, bwd_chunk):
    out = _fwd_impl(q, k, v, causal, q_offset, block_q, block_k)
    return out, (q, k, v)


def _bwd_rule(causal, q_offset, block_q, block_k, bwd_chunk, res, dout):
    q, k, v = res
    b, tq, h, dh = q.shape
    chunk = min(bwd_chunk, tq)
    tqp = _round_up(tq, chunk)
    qp = jnp.pad(q, ((0, 0), (0, tqp - tq), (0, 0), (0, 0)))
    dop = jnp.pad(dout, ((0, 0), (0, tqp - tq), (0, 0), (0, 0)))
    nblk = tqp // chunk

    def body(carry, blk_idx):
        dk_acc, dv_acc = carry
        start = blk_idx * chunk
        qb = jax.lax.dynamic_slice_in_dim(qp, start, chunk, axis=1)
        dob = jax.lax.dynamic_slice_in_dim(dop, start, chunk, axis=1)
        valid = (start + jnp.arange(chunk)) < tq

        def f(qb_, k_, v_):
            o = flash_attention_ref(qb_, k_, v_, causal=causal,
                                    q_offset=q_offset + start)
            return o * valid[None, :, None, None]

        _, vjp = jax.vjp(f, qb, k, v)
        dq_b, dk_b, dv_b = vjp(dob * valid[None, :, None, None])
        return (dk_acc + dk_b, dv_acc + dv_b), dq_b

    (dk, dv), dq_blocks = jax.lax.scan(
        body,
        (jnp.zeros_like(k, jnp.float32), jnp.zeros_like(v, jnp.float32)),
        jnp.arange(nblk))
    # scan ys: (nblk, B, chunk, H, Dh) -> (B, Tq_pad, H, Dh)
    dq = dq_blocks.transpose(1, 0, 2, 3, 4).reshape(b, tqp, h, dh)
    return (dq[:, :tq].astype(q.dtype), dk.astype(k.dtype),
            dv.astype(v.dtype))


flash_attention.defvjp(_fwd_rule, _bwd_rule)
