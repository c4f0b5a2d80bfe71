"""Softmax attention under the two rules the decoders train with: the
block-diffusion training mask (`blockdiff_attention`, models/sdar.py) and
the plain causal rule at any head size (`causal_attention`,
models/qwen3next.py: a query sees the keys at and before its own position,
P (P + 1) / 2 of the P^2 scores; the library's own `CausalMask`).  One tile
table (`KERNEL_TILES`) and, a rule each, a tally of what the tiles cost,
emitted as the same gauges.

A block-diffusion decoder (Arriola et al. 2025, arXiv:2503.09573) trains on
ONE pass over 2L positions ``[xt ; x0]``: the noisy copy of a sequence
followed by the clean one, cut into blocks of ``block`` tokens.  With
``blk(i) = (i mod L) // block`` a query ``i`` may see a key ``j`` iff

  * noisy -> noisy:  ``blk(i) == blk(j)``   (both directions inside a block)
  * noisy -> clean:  ``blk(j) <  blk(i)``
  * clean -> clean:  ``blk(j) <= blk(i)``
  * clean -> noisy:  never

which allows a quarter of the (2L)^2 scores.  ``blockdiff_allowed`` is that
rule, in the form the kernels pay least for: a query's code ``u(i) =
2 blk(i) + [i clean]`` on one side, two compares of ``2 blk(j)`` with it on
the other.  It runs on numpy ids (tests, the host-side tile classification)
and on traced ids inside a kernel alike.

Two ways of computing either, one result:

  * on a TPU the splash-attention Pallas kernels (forward, dq, dkv) with
    the rule handed over as a computable mask: tiles the rule forbids are
    never visited, every visited tile (a wholly allowed one too: the
    library does not tell them apart) evaluates the key's side of the rule
    on iota ids against its rows of precomputed query codes, and no
    [2L, 2L] array exists on the device or the host;
  * elsewhere (the CPU tests) a blocked ``jax.numpy`` formulation over
    the same tiles: query tile ``t`` (its noisy and its clean rows) meets
    the clean keys of tiles ``0..t`` and the noisy keys of tile ``t``;
    under the causal rule, the keys of tiles ``0..t``.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


class Tiles(NamedTuple):
    """Splash tile shapes, a kernel each: (block_q, block_kv,
    block_kv_compute) of the forward and of the dkv kernel, (block_q,
    block_kv) of the dq kernel."""
    fwd: tuple
    dkv: tuple
    dq: tuple


# At the real sizes (2L = 8192, head 128, 8 query heads a key/value head),
# each the fastest of its kernel in the sweep on the chip (the table in
# PERF.md section 6, PR 32; ms a call of 4 key/value heads, the kernel alone):
KERNEL_TILES = Tiles(
    fwd=(1024, 1024, 256),      # 3.22 of 65 shapes; (512, 512, 512) 3.96
    dkv=(1024, 1024, 1024),     # 5.46 of 67; 5.47-5.59 at the other compute
                                # edges of 1024 x 1024; (512, 512, 512) 6.23
    dq=(512, 512))              # 4.29 of 24; (1024, 1024) 4.44


def _twice_block(ids, seq_len: int, block: int):
    """2 * ((ids mod L) // block).  Ids are never negative, so the floor
    repairs of `%` and `//` on traced integers would buy nothing: a mask
    and a shift where L and block are powers of two, else truncating
    division (plain operators on numpy ids)."""
    if not (seq_len & (seq_len - 1) or block & (block - 1)):
        shift = block.bit_length() - 2          # log2(block) - 1
        even = (seq_len // block - 1) << 1
        return (ids >> shift if shift >= 0 else ids << 1) & even
    if isinstance(ids, jax.Array):
        return lax.div(lax.rem(ids, seq_len), block) * 2
    return ids % seq_len // block * 2


def _query_code(q, seq_len: int, block: int):
    """u(q) = 2 blk(q) + [q is clean]: all the rule needs of a query."""
    return _twice_block(q, seq_len, block) + (q >= seq_len)


def _allowed(u, k, seq_len: int, block: int):
    """The four cases in two compares: a clean key is allowed iff
    2 blk(k) < u(q), a noisy one iff 2 blk(k) == u(q)."""
    w = _twice_block(k, seq_len, block)
    before, same = w < u, w == u
    # select(k noisy, same, before) in mask operations (Mosaic has no
    # select between masks)
    return before ^ ((same ^ before) & (k < seq_len))


def blockdiff_allowed(q, k, seq_len: int, block: int):
    """May position ``q`` attend to position ``k``?  Ids in [0, 2L)."""
    return _allowed(_query_code(q, seq_len, block), k, seq_len, block)


def _tile(seq_len: int, block: int, want: int) -> int:
    """Largest tile edge <= want that divides L and is whole blocks."""
    t = min(want, seq_len)
    while seq_len % t or t % block:
        t -= 1
    return t


def _fit(seq_len: int, block: int, tiles: Tiles, cap: int | None) -> Tiles:
    """Every edge cut to `cap`, to a divisor of L and to whole blocks, and
    a compute edge to a divisor of its kernel's key/value edge."""
    def cut(bq, bkv, *compute):
        bq, bkv = (_tile(seq_len, block, min(e, cap or e)) for e in (bq, bkv))
        return (bq, bkv, *(_tile(bkv, block, min(c, cap or c))
                           for c in compute))
    return Tiles(*(cut(*shape) for shape in tiles))


def tile_tally(seq_len: int, block: int, bq: int, bkv: int):
    """What tiles of bq x bkv cost a kernel, known when it is built: (tiles
    it visits, those of them partly allowed, scores visited over scores
    allowed).  The rule is constant on a block x block cell, so one id a
    block classifies a tile of whole blocks."""
    ids = np.arange(0, 2 * seq_len, block)
    cells = blockdiff_allowed(ids[:, None], ids[None, :], seq_len, block)
    n = len(ids)
    per = cells.reshape(n * block // bq, bq // block,
                        n * block // bkv, bkv // block)
    some, every = per.any((1, 3)), per.all((1, 3))
    visited = int(some.sum())
    return (visited, int((some & ~every).sum()),
            visited * bq * bkv / (int(cells.sum()) * block * block))


def _tile_gauges(tiles: Tiles, tally):
    """(name, value, {"kernel": ...}) of `tally(bq, bkv)` a kernel."""
    names = ("attn_tiles_visited", "attn_tiles_partial",
             "attn_visited_over_allowed")
    return [(name, value, {"kernel": kernel})
            for kernel, (bq, bkv, *_) in tiles._asdict().items()
            for name, value in zip(names, tally(bq, bkv))]


def kernel_tile_gauges(seq_len: int, block: int):
    """`tile_tally` for each of the kernels as `blockdiff_attention` builds
    them at this shape."""
    return _tile_gauges(_fit(seq_len, block, KERNEL_TILES, None),
                        functools.partial(tile_tally, seq_len, block))


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
def _splash_kernel(seq_len: int, block: int, group: int, tiles: Tiles,
                   interpret: bool):
    """The splash MQA kernel (one key/value head, `group` query heads)
    under the block-diffusion rule.  Built once per shape, outside any
    trace: its tile tables are small concrete arrays."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk, splash_attention_mask as sm)

    n = 2 * seq_len

    class BlockDiffusionMask(sm._ComputableMask):
        """The library evaluates a computable mask's function on every
        score of every tile it visits, wholly allowed ones too, on the
        tile's rows of `q_sequence` and on key ids: so the rows carry the
        queries' codes, worked out here once, and the function is what is
        left of the rule, two compares on a key's masked and shifted id."""

        def __init__(self):
            super().__init__((n, n), functools.partial(
                _allowed, seq_len=seq_len, block=block))
            self.q_sequence = _query_code(
                np.arange(n, dtype=np.int32), seq_len, block)

        def __eq__(self, other):
            return type(other) is type(self) and self.shape == other.shape

        def __hash__(self):
            return hash((type(self).__name__, self.shape, seq_len, block))

    with jax.ensure_compile_time_eval():
        return sk.make_splash_mqa(
            sm.MultiHeadMask([BlockDiffusionMask() for _ in range(group)]),
            block_sizes=_block_sizes(sk, tiles), head_shards=1,
            q_seq_shards=1, interpret=interpret)


def _block_sizes(sk, tiles: Tiles):
    (bq, bkv, compute), (bq_dkv, bkv_dkv, compute_dkv), (bq_dq, bkv_dq) = tiles
    return sk.BlockSizes(
        block_q=bq, block_kv=bkv, block_kv_compute=compute,
        block_q_dkv=bq_dkv, block_kv_dkv=bkv_dkv,
        block_kv_dkv_compute=compute_dkv,
        block_q_dq=bq_dq, block_kv_dq=bkv_dq)


def _splash(kernel, q, k, v):
    # The MXU rounds float32 operands to bfloat16 at the default matmul
    # precision anyway; handing the kernel bfloat16 saves it the f32 passes.
    cast = lambda a: a.astype(jnp.bfloat16)
    per_head = jax.vmap(kernel)                     # over key/value heads
    out = jax.vmap(per_head)(cast(q), cast(k), cast(v))   # over sequences
    return out.astype(q.dtype)


def blockdiff_attention(q, k, v, *, seq_len: int, block: int,
                        kernels: bool, interpret: bool = False,
                        tile: int | Tiles | None = None):
    """softmax(q k^T + M) v over the 2L positions of each sequence.

    q [S, HQ, 2L, D] ALREADY scaled by 1/sqrt(D); k, v [S, HKV, 2L, D];
    each key/value head serves HQ/HKV query heads.  Returns [S, HQ, 2L, D].
    `kernels`: the Pallas kernels (TPU; D and the tile multiples of 128)
    or the blocked jax.numpy formulation.  `tile` (tests): a cap on every
    tile edge (the default: `KERNEL_TILES` for the kernels, 128 blocked),
    or the kernels' `Tiles`; an edge is cut to a divisor of L either way.
    """
    s, hq, n, d = q.shape
    hkv = k.shape[1]
    qg = q.reshape(s, hkv, hq // hkv, n, d)
    with jax.named_scope("attn_blockdiff"):
        if kernels:
            tiles, cap = (tile, None) if isinstance(tile, Tiles) \
                else (KERNEL_TILES, tile)
            out = _splash(_splash_kernel(
                seq_len, block, qg.shape[2],
                _fit(seq_len, block, tiles, cap), interpret), qg, k, v)
        else:
            out = _blocked(qg, k, v, seq_len, block,
                           _tile(seq_len, block, tile or 128))
    return out.reshape(s, hq, n, d)


# -- the causal rule ----------------------------------------------------------

# The kernels take `KERNEL_TILES`, the block-diffusion kernels' shapes, at
# head size 256, 8 query heads a key/value head, P = 8192 (the shapes of
# models/qwen3next.py) too: the deviceless v5e compile takes them at twice
# their head size; no sweep at this head size gave others (PERF.md
# section 7).

def causal_tile_tally(n: int, bq: int, bkv: int):
    """`tile_tally` under the causal rule over n positions: a tile is
    visited if its last row reaches its first key, wholly allowed if its
    first row reaches its last key."""
    rows, keys = np.arange(0, n, bq), np.arange(0, n, bkv)
    some = (rows[:, None] + bq - 1) >= keys[None, :]
    every = rows[:, None] >= (keys[None, :] + bkv - 1)
    visited = int(some.sum())
    return (visited, int((some & ~every).sum()),
            visited * bq * bkv / (n * (n + 1) // 2))


def causal_tile_gauges(n: int):
    """`kernel_tile_gauges` for the kernels `causal_attention` builds."""
    return _tile_gauges(_fit(n, 1, KERNEL_TILES, None),
                        functools.partial(causal_tile_tally, n))


def _blocked_causal(q, k, v, tile: int):
    """q [S, HKV, G, P, D]; k, v [S, HKV, P, D] -> like q."""
    out = []
    for lo in range(0, q.shape[-2], tile):
        hi = lo + tile
        allowed = np.arange(lo, hi)[:, None] >= np.arange(hi)[None, :]
        s = jnp.einsum("shgqd,shkd->shgqk", q[..., lo:hi, :], k[..., :hi, :],
                       preferred_element_type=jnp.float32)
        p = jax.nn.softmax(jnp.where(allowed, s, -jnp.inf), axis=-1)
        out.append(jnp.einsum(
            "shgqk,shkd->shgqd", p.astype(v.dtype), v[..., :hi, :],
            preferred_element_type=jnp.float32).astype(q.dtype))
    return jnp.concatenate(out, axis=-2)


@functools.lru_cache(maxsize=8)
def _causal_kernel(n: int, group: int, tiles: Tiles, interpret: bool):
    """The splash MQA kernel under the library's causal mask."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk, splash_attention_mask as sm)
    with jax.ensure_compile_time_eval():
        return sk.make_splash_mqa(
            sm.MultiHeadMask([sm.CausalMask((n, n)) for _ in range(group)]),
            block_sizes=_block_sizes(sk, tiles), head_shards=1,
            q_seq_shards=1, interpret=interpret)


def causal_attention(q, k, v, *, kernels: bool, interpret: bool = False,
                     tile: int | None = None):
    """softmax(q k^T + causal) v over the P positions of each sequence.

    q [S, HQ, P, D] ALREADY scaled; k [S, HKV, P, D], v [S, HKV, P, DV]
    (DV may differ: ops/mla.py, 192 and 128), HQ/HKV query heads a key/value
    head.  Returns [S, HQ, P, DV].  `kernels`, `tile`: as the other rule's."""
    s, hq, n, d = q.shape
    hkv = k.shape[1]
    qg = q.reshape(s, hkv, hq // hkv, n, d)
    with jax.named_scope("attn_causal"):
        if kernels:
            out = _splash(_causal_kernel(
                n, qg.shape[2], _fit(n, 1, KERNEL_TILES, tile), interpret),
                qg, k, v)
        else:
            out = _blocked_causal(qg, k, v, _tile(n, 1, tile or 128))
    return out.reshape(s, hq, n, v.shape[-1])
