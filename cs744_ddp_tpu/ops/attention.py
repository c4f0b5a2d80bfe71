"""Attention under the block-diffusion training mask.

A block-diffusion decoder (Arriola et al. 2025, arXiv:2503.09573) trains on
ONE pass over 2L positions ``[xt ; x0]``: the noisy copy of a sequence
followed by the clean one, cut into blocks of ``block`` tokens.  With
``blk(i) = (i mod L) // block`` a query ``i`` may see a key ``j`` iff

  * noisy -> noisy:  ``blk(i) == blk(j)``   (both directions inside a block)
  * noisy -> clean:  ``blk(j) <  blk(i)``
  * clean -> clean:  ``blk(j) <= blk(i)``
  * clean -> noisy:  never

which allows a quarter of the (2L)^2 scores.  ``blockdiff_allowed`` is that
rule, written with operators only so it runs on numpy ids (tests, the
host-side tile classification) and on traced ids inside a kernel alike.

Two ways of computing it, one result:

  * on a TPU the splash-attention Pallas kernels (forward, dq, dkv) with
    the rule handed over as a computable mask: tiles the rule forbids are
    never visited, partly allowed tiles evaluate the rule on iota ids in
    the kernel, and no [2L, 2L] array exists on the device or the host;
  * elsewhere (the CPU tests) a blocked ``jax.numpy`` formulation over
    the same tiles: query tile ``t`` (its noisy and its clean rows) meets
    the clean keys of tiles ``0..t`` and the noisy keys of tile ``t``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

KERNEL_TILE = 512       # splash tile edge (q and kv) at the real sizes


def blockdiff_allowed(q, k, seq_len: int, block: int):
    """May position ``q`` attend to position ``k``?  Ids in [0, 2L)."""
    q_noisy, k_noisy = q < seq_len, k < seq_len
    bq, bk = (q % seq_len) // block, (k % seq_len) // block
    return ((q_noisy & k_noisy & (bq == bk))
            | (q_noisy & ~k_noisy & (bk < bq))
            | (~q_noisy & ~k_noisy & (bk <= bq)))


def _tile(seq_len: int, block: int, want: int) -> int:
    """Largest tile edge <= want that divides L and is whole blocks."""
    t = min(want, seq_len)
    while seq_len % t or t % block:
        t -= 1
    return t


def _blocked(q, k, v, seq_len: int, block: int, tile: int):
    """q [S, HKV, G, 2L, D]; k, v [S, HKV, 2L, D] -> like q."""
    L, n = seq_len, seq_len // tile
    noisy, clean = [], []
    for t in range(n):
        lo, hi = t * tile, (t + 1) * tile
        q_ids = np.concatenate([np.arange(lo, hi), L + np.arange(lo, hi)])
        k_ids = np.concatenate([L + np.arange(0, hi), np.arange(lo, hi)])
        allowed = blockdiff_allowed(q_ids[:, None], k_ids[None, :], L, block)
        qs = jnp.concatenate([q[..., lo:hi, :], q[..., L + lo:L + hi, :]], -2)
        ks = jnp.concatenate([k[..., L:L + hi, :], k[..., lo:hi, :]], -2)
        vs = jnp.concatenate([v[..., L:L + hi, :], v[..., lo:hi, :]], -2)
        s = jnp.einsum("shgqd,shkd->shgqk", qs, ks,
                       preferred_element_type=jnp.float32)
        s = jnp.where(allowed, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1).astype(vs.dtype)
        o = jnp.einsum("shgqk,shkd->shgqd", p, vs,
                       preferred_element_type=jnp.float32).astype(q.dtype)
        noisy.append(o[..., :tile, :])
        clean.append(o[..., tile:, :])
    return jnp.concatenate(noisy + clean, axis=-2)


@functools.lru_cache(maxsize=8)
def _splash_kernel(seq_len: int, block: int, group: int, tile: int,
                   interpret: bool):
    """The splash MQA kernel (one key/value head, `group` query heads)
    under the block-diffusion rule.  Built once per shape, outside any
    trace: its tile tables are small concrete arrays."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk, splash_attention_mask as sm)

    rule = functools.partial(blockdiff_allowed, seq_len=seq_len, block=block)

    class BlockDiffusionMask(sm._ComputableMask):
        def __init__(self):
            super().__init__((2 * seq_len, 2 * seq_len), rule)

        def __eq__(self, other):
            return type(other) is type(self) and self.shape == other.shape

        def __hash__(self):
            return hash((type(self).__name__, self.shape, seq_len, block))

    sizes = sk.BlockSizes(
        block_q=tile, block_kv=tile, block_kv_compute=tile,
        block_q_dkv=tile, block_kv_dkv=tile, block_kv_dkv_compute=tile,
        block_q_dq=tile, block_kv_dq=tile)
    with jax.ensure_compile_time_eval():
        return sk.make_splash_mqa(
            sm.MultiHeadMask([BlockDiffusionMask() for _ in range(group)]),
            block_sizes=sizes, head_shards=1, q_seq_shards=1,
            interpret=interpret)


def _splash(q, k, v, seq_len: int, block: int, tile: int, interpret: bool):
    kernel = _splash_kernel(seq_len, block, q.shape[2], tile, interpret)
    # The MXU rounds float32 operands to bfloat16 at the default matmul
    # precision anyway; handing the kernel bfloat16 saves it the f32 passes.
    cast = lambda a: a.astype(jnp.bfloat16)
    per_head = jax.vmap(kernel)                     # over key/value heads
    out = jax.vmap(per_head)(cast(q), cast(k), cast(v))   # over sequences
    return out.astype(q.dtype)


def blockdiff_attention(q, k, v, *, seq_len: int, block: int,
                        kernels: bool, interpret: bool = False,
                        tile: int | None = None):
    """softmax(q k^T + M) v over the 2L positions of each sequence.

    q [S, HQ, 2L, D] ALREADY scaled by 1/sqrt(D); k, v [S, HKV, 2L, D];
    each key/value head serves HQ/HKV query heads.  Returns [S, HQ, 2L, D].
    `kernels`: the Pallas kernels (TPU; D and the tile multiples of 128)
    or the blocked jax.numpy formulation.  `tile`: the tile edge wanted
    (default 512 for the kernels, 128 blocked), cut to a divisor of L.
    """
    s, hq, n, d = q.shape
    hkv = k.shape[1]
    qg = q.reshape(s, hkv, hq // hkv, n, d)
    with jax.named_scope("attn_blockdiff"):
        if kernels:
            out = _splash(qg, k, v, seq_len, block,
                          _tile(seq_len, block, tile or KERNEL_TILE),
                          interpret)
        else:
            out = _blocked(qg, k, v, seq_len, block,
                           _tile(seq_len, block, tile or 128))
    return out.reshape(s, hq, n, d)
