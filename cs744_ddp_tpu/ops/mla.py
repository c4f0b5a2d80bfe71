"""Multi-head latent attention (DeepSeek-V2 / V3's MLA, as their public
modelling code has it), the form training takes: queries and keys / values
come through two low-rank paths with an RMSNorm between their halves, and a
head's key is 128 numbers of its own beside a rotary part of 64 that ALL
heads share:

    c_q = norm(h W_qa)                    [q_nope | q_pe] = c_q W_qb   a head
    [c_kv | k_pe] = h W_kva               [k_nope | v] = norm(c_kv) W_kvb
    q = [q_nope | rot(q_pe)]   k = [k_nope | rot(k_pe)]   (192)   v (128)
    o = softmax_causal(q k^T s) v         concat(o) W_o

so key and value sizes differ and every query head has a key/value head of
its own (`ops/attention.py` `causal_attention`).  The absorbed form (one
latent head of 576 for all query heads) is serving's: it costs training
3.4x the products and is not here.

Positions are YaRN's (`yarn_inv_freq`: the rotary frequencies between
`beta_fast` and `beta_slow` rotations over the original length interpolated
by `factor`, the slow ones kept), and the softmax scale carries YaRN's
`mscale` squared (`softmax_scale`).  Rotary pairs are (i, i + d/2), as
models/sdar.py `rope`.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from . import attention


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, base: float, factor: float, original: int,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """The dim / 2 rotary frequencies: base^(-2i/dim) for the pairs that
    turn more than `beta_fast` times over `original` positions, that over
    `factor` for those that turn less than `beta_slow` times, a linear
    ramp between."""
    def correction_dim(rotations):
        return dim * math.log(original / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))
    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    freq = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    return (freq / factor * ramp + freq * (1.0 - ramp)).astype(np.float32)


def softmax_scale(qk_dim: int, factor: float, mscale_all_dim: float) -> float:
    return qk_dim ** -0.5 * yarn_mscale(factor, mscale_all_dim) ** 2


def rotary(x, positions, inv_freq, attn_factor: float = 1.0):
    """x [P, heads, d]: pair (i, i + d/2) turned by position x inv_freq[i];
    cos and sin times `attn_factor` (YaRN's mscale over its
    mscale_all_dim: 1 where the two are equal)."""
    d = x.shape[-1]
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(inv_freq)
    cos = (jnp.concatenate([jnp.cos(ang)] * 2, -1) * attn_factor)[:, None, :]
    sin = (jnp.concatenate([jnp.sin(ang)] * 2, -1) * attn_factor)[:, None, :]
    x32 = x.astype(jnp.float32)
    rot = jnp.concatenate([-x32[..., d // 2:], x32[..., :d // 2]], -1)
    return (x32 * cos + rot * sin).astype(x.dtype)


def latent_attention(h, p, *, heads: int, nope: int, rope: int, v_dim: int,
                     kv_rank: int, norm, positions, inv_freq, scale: float,
                     attn_factor: float = 1.0, kernels: bool,
                     interpret: bool = False):
    """h [P, C] (already normed) -> [P, C].  `p`: w_qa [C, q_rank], q_norm
    [q_rank], w_qb [q_rank, heads (nope + rope)], w_kva [C, kv_rank + rope],
    kv_norm [kv_rank], w_kvb [kv_rank, heads (nope + v_dim)], w_o [heads
    v_dim, C].  `norm(x, gain)`: the model's RMSNorm."""
    P = h.shape[0]
    dot = lambda x, name: jnp.dot(x, p[name].astype(x.dtype))
    with jax.named_scope("attn_mla"):
        q = dot(norm(dot(h, "w_qa"), p["q_norm"]), "w_qb")
        q = q.reshape(P, heads, nope + rope)
        kva = dot(h, "w_kva")
        kv = dot(norm(kva[:, :kv_rank], p["kv_norm"]), "w_kvb")
        kv = kv.reshape(P, heads, nope + v_dim)
        turn = lambda x: rotary(x, positions, inv_freq, attn_factor)
        # the ONE rotary key of a position, for every head
        k_pe = jnp.broadcast_to(turn(kva[:, None, kv_rank:]), (P, heads, rope))
        q = jnp.concatenate([q[..., :nope], turn(q[..., nope:])], -1) \
            * jnp.asarray(scale, q.dtype)
        k = jnp.concatenate([kv[..., :nope], k_pe], -1)
        # heads first; the kernels' operand type out here, so that what
        # sits under `mla_core` on a TPU is the three kernels alone
        core = jnp.bfloat16 if kernels else q.dtype
        lay = lambda a: a.transpose(1, 0, 2)[None].astype(core)
        q, k, v = lay(q), lay(k), lay(kv[..., nope:])
        with jax.named_scope("mla_core"):
            o = attention.causal_attention(q, k, v, kernels=kernels,
                                           interpret=interpret)
        o = o[0].transpose(1, 0, 2).astype(h.dtype)
        return dot(o.reshape(P, heads * v_dim), "w_o")
