"""A residual of n streams mixed by manifold-constrained hyper-connections
(Xie et al., "mHC", arXiv:2512.24880, on Zhu et al., "Hyper-Connections",
arXiv:2409.19606): around a sublayer F, a position's streams X in R^{n x C}
are read into one input, and F's output is written back into all of them:

    u = vec(X)      m = (u / sqrt(mean(u^2) + eps)) Phi     Phi [nC, n^2 + 2n]
    H_pre  = sigmoid(a_pre m[:n] + b_pre)                       R^n
    H_post = 2 sigmoid(a_post m[n:2n] + b_post)                 R^n
    H_res  = sinkhorn(exp(clip(a_res m[2n:] + b_res)))          R^{n x n}
    y = F(sum_j H_pre[j] X[j])
    X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y

`sinkhorn`: `iters` times, every column over its sum, then every row over
its sum (eps added to each sum), which takes a positive matrix to a doubly
stochastic one: the residual mix neither grows nor shrinks the streams.

Here the streams of one sequence lie streams first, x [n, P, C], so that
every pass is over whole [P, C] slabs, and the coefficients lie positions
last ([n, P], [n, n, P]: the 20 iterations run on 16 rows of P lanes).  All
of it float32, the narrow product at the highest precision (it is 0.01% of
a layer's operations); the mixes are sums of products written out, not
contractions, so no operand is rounded on the way to a matrix unit.

Everything above is local to a position, and memory-bound.  Two forms of
it, chosen in `connect` from what the caller resolved from the backend
(`kernels`) and from the shapes (`plan`), as ops/gdn.py chooses its
kernels:

ON THE TPU, float32 streams, channels in whole lanes, positions in whole
tiles: four Pallas kernels whose grid walks tiles of `KERNEL_TILE`
positions, under one `jax.custom_vjp` that holds the sublayer between them
(`_fused`; what the sublayer closes over becomes arguments:
`jax.closure_convert`), all issued under the scope `mhc_mix`.  A tile's
block [n, T, C] of the streams stays in VMEM for as many passes as the
mathematics needs:

    mhc_read_fwd    x -> the norm's factor, m (the streams go by the matrix
                    unit, Phi^T [slots, n C] stays in it), the coefficients
                    after `iters` Sinkhorn iterations, h = sum_j H_pre[j]
                    x[j].  Writes h [P, C] and the small arrays.
    mhc_write_fwd   x, y, the coefficients -> X'.
    mhc_write_bwd   dX', H_post -> dy: what the sublayer's backward pass
                    needs first.
    mhc_read_bwd    dX', x, y, dh -> every coefficient's cotangent (row
                    sums over the channels), back through Sinkhorn (the
                    halves rebuilt from m, each taking dN to (dN - sum(dN
                    N)) d), the clamp and exp, the sigmoids, the scales
                    and the norm -> dx in one write; dPhi^T summed over the
                    grid in VMEM; the cotangent of scale m + bias leaves
                    the kernel, and its sums over the positions (dbias,
                    dalpha) are XLA's.

The small arrays have two layouts (a lane a position where the
coefficients are computed, a sublane a position where they are applied;
`_to_rows`, `_to_cols`); a sublayer reads the streams once and writes h,
reads them and y and writes X', and on the way back reads dX' for dy, then
dX', x, y, dh and writes dx: 1.25 + 2.25 + 1.25 + 3.5 stream arrays, and
the forward pair again where a checkpoint recomputes it.  No [n, n, P, C]
and no second array of the streams' size.

EVERYWHERE ELSE (the CPU tests, the benchmark's rehearsals, 64 channels,
a bfloat16 compute dtype): `_plain`, the functions below in `jax.numpy`,
XLA's fusions and autodiff's backward pass: the kernels' reference in the
tests.  There a sublayer reads the streams for the norm and the product,
for the read and for the write, and once more a coefficient on the way
back (PERF.md section 6, PR 36).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def sinkhorn(m, iters: int, eps: float):
    """m [n, n, ...] positive -> doubly stochastic over its first two axes
    (rows, then columns), to what `iters` iterations reach."""
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=0, keepdims=True) + eps)   # columns
        m = m / (jnp.sum(m, axis=1, keepdims=True) + eps)   # rows
    return m


def res_gap(h_res):
    """The largest |row sum - 1| or |column sum - 1| of any position's
    H_res: how far the iterations stopped from the manifold."""
    rows, cols = jnp.sum(h_res, axis=1), jnp.sum(h_res, axis=0)
    return jnp.maximum(jnp.max(jnp.abs(rows - 1.0)),
                       jnp.max(jnp.abs(cols - 1.0)))


def coefficients(x, p, *, iters: int, eps: float, clamp):
    """x [n, P, C] -> (H_pre [n, P], H_post [n, P], H_res [n, n, P]),
    float32.  `p`: phi [n C, n^2 + 2n] (rows stream by stream), bias
    [n^2 + 2n] and alpha [3], both ordered pre, post, res."""
    n, positions, width = x.shape
    x32 = x.astype(jnp.float32)
    phi = p["phi"].astype(jnp.float32).reshape(n, width, n * n + 2 * n)
    # the norm's factor is a position's scalar: taken after the product
    inv = lax.rsqrt(jnp.mean(jnp.square(x32), axis=(0, 2)) + eps)    # [P]
    m = sum(jnp.dot(x32[j], phi[j], precision=lax.Precision.HIGHEST)
            for j in range(n))
    m = (m * inv[:, None]).T                        # [n^2 + 2n, P]
    bias, alpha = p["bias"].astype(jnp.float32), p["alpha"]
    h_pre = jax.nn.sigmoid(alpha[0] * m[:n] + bias[:n, None])
    h_post = 2.0 * jax.nn.sigmoid(alpha[1] * m[n:2 * n] + bias[n:2 * n, None])
    with jax.named_scope("mhc_sinkhorn"):
        a = jnp.clip(alpha[2] * m[2 * n:] + bias[2 * n:, None], *clamp)
        h_res = sinkhorn(jnp.exp(a).reshape(n, n, positions), iters, eps)
    return h_pre, h_post, h_res


def read(x, h_pre):
    """The sublayer's input: sum_j H_pre[j] X[j], [P, C] in x's dtype."""
    x32 = x.astype(jnp.float32)
    return sum(h_pre[j][:, None] * x32[j]
               for j in range(x.shape[0])).astype(x.dtype)


def write(x, y, h_post, h_res):
    """X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y, like x."""
    n = x.shape[0]
    x32, y32 = x.astype(jnp.float32), y.astype(jnp.float32)
    return jnp.stack([
        sum(h_res[i, j][:, None] * x32[j] for j in range(n))
        + h_post[i][:, None] * y32 for i in range(n)]).astype(x.dtype)


def _plain(sublayer, x, p, *, iters: int, eps: float, clamp):
    """`connect` in `jax.numpy`: XLA's fusions, autodiff's backward pass."""
    with jax.named_scope("mhc_mix"):
        h_pre, h_post, h_res = coefficients(x, p, iters=iters, eps=eps,
                                            clamp=clamp)
        h = read(x, h_pre)
    y, aux = sublayer(h)
    with jax.named_scope("mhc_mix"):
        return write(x, y, h_post, h_res), aux, \
            lax.stop_gradient(res_gap(h_res))


# -- the kernels ---------------------------------------------------------------
#
# Two layouts of a tile's small arrays.  ROWS [_SLOTS, T]: a lane a position,
# where the coefficients are computed (a Sinkhorn iteration is a few
# operations on n arrays of [n, T]).  COLUMNS [T, _SLOTS]: a sublane a
# position, where they are applied (a position's coefficient is a scalar of
# its row [C] of every stream).  Slot k of either, as Phi's columns lie:
# H_pre 0..n-1, H_post n..2n-1, H_res (i, j) at 2n + i n + j; then slot
# n^2 + 2n: the norm's factor.

_SLOTS = 32             # of which n^2 + 2n + 1 are used
_STRIP = 8              # positions a turn of a kernel's loop over its tile
_HI = lax.Precision.HIGHEST
KERNEL_TILE = 128       # positions a grid step
VMEM_LIMIT = 100 * 2 ** 20


class _Static(NamedTuple):
    """What the kernels are built for, besides their operands' shapes."""
    tile: int
    iters: int
    eps: float
    clamp: Tuple[float, float]
    interpret: bool


def plan(shape, dtype, kernels: bool) -> int:
    """The positions a grid step of the kernels for streams of `shape`
    [n, P, C], or 0 where the `jax.numpy` form runs: `kernels` as
    models/xing4.py `make` resolves it from the backend; float32, the
    channels in whole lanes, the positions in whole tiles, the
    coefficients and the norm's factor within the small arrays' slots."""
    n, positions, width = shape
    fits = (kernels and dtype == jnp.float32 and width % 128 == 0
            and positions % KERNEL_TILE == 0 and n * n + 2 * n < _SLOTS)
    return KERNEL_TILE if fits else 0


def _dot(a, b, contract):
    """a . b over a's axis contract[0] and b's contract[1], float32 at the
    highest precision (Mosaic: `#tpu.contract_precision<fp32>`)."""
    return lax.dot_general(
        a, b, (((contract[0],), (contract[1],)), ((), ())), precision=_HI,
        preferred_element_type=jnp.float32)


def _eye(size: int):
    return (lax.broadcasted_iota(jnp.int32, (size, size), 0)
            == lax.broadcasted_iota(jnp.int32, (size, size), 1)
            ).astype(jnp.float32)


def _to_rows(cols):
    """[T, _SLOTS] -> [_SLOTS, T], exactly: a product with the identity at
    the highest precision returns the three bfloat16 parts of its operand,
    summed."""
    return _dot(_eye(cols.shape[1]), cols, (1, 1))


def _to_cols(rows):
    """[_SLOTS, T] -> [T, _SLOTS], exactly (`_to_rows`)."""
    return _dot(rows, _eye(rows.shape[0]), (0, 0))


def _strips(tile: int, body):
    """body(the slice of a strip's positions), over a tile's strips."""
    def turn(i, carry):
        body(pl.ds(pl.multiple_of(i * _STRIP, _STRIP), _STRIP))
        return carry
    lax.fori_loop(0, tile // _STRIP, turn, 0)


def _lane(shape, k: int):
    return lax.broadcasted_iota(jnp.int32, shape, 1) == k


def _rowsum(a):
    return jnp.sum(a, axis=1, keepdims=True)


def _halves(res, iters: int, eps: float):
    """`sinkhorn` on the matrix's rows, res[i] [n (j), T] -> (the result
    likewise, every half-iteration's (result, reciprocal of its sums) for
    the way back)."""
    kept = []
    for _ in range(iters):
        d = 1.0 / (sum(res) + eps)                          # columns: over i
        res = [r * d for r in res]
        kept.append((res, [d] * len(res)))
        ds = [1.0 / (jnp.sum(r, 0, keepdims=True) + eps) for r in res]
        res = [r * d for r, d in zip(res, ds)]              # rows: over j
        kept.append((res, ds))
    return res, kept


def _halves_back(dres, kept):
    """The cotangent of `_halves`' argument from its result's: a half
    N = M d, d = 1 / (sum M + eps), takes dN back to (dN - sum(dN N)) d."""
    for half, (res, ds) in enumerate(reversed(kept)):
        t = [a * b for a, b in zip(dres, res)]
        if half % 2:                                        # columns
            s = [sum(t)] * len(t)
        else:                                               # rows
            s = [jnp.sum(a, 0, keepdims=True) for a in t]
        dres = [(a - b) * d for a, b, d in zip(dres, s, ds)]
    return dres


def _activations(m, scale, bias, n: int, clamp):
    """ROWS m -> (H_pre [n, T], H_post [n, T], the rows of the matrix
    Sinkhorn starts from, n of [n, T], the rows of a: what is clamped)."""
    a = scale * m + bias
    h_pre = jax.nn.sigmoid(a[:n])
    h_post = 2.0 * jax.nn.sigmoid(a[n:2 * n])
    a_res = [a[2 * n + i * n:2 * n + (i + 1) * n] for i in range(n)]
    return h_pre, h_post, [jnp.exp(jnp.clip(r, *clamp)) for r in a_res], \
        a_res


def _pack(parts, tile: int):
    """ROWS pieces, in the slots' order -> [_SLOTS, T]."""
    used = sum(p.shape[0] for p in parts)
    return jnp.concatenate(
        list(parts) + [jnp.zeros((_SLOTS - used, tile), jnp.float32)], 0)


def _read_kernel(st: _Static, x, phi_t, scale, bias, h, rows_out, cols_out,
                 sq):
    """A tile of positions: x [n, T, C], phi_t [_SLOTS, n C] -> h [T, C],
    ROWS (m, then the norm's factor) and COLUMNS (the coefficients, then
    the factor); `sq` [T, 1]: scratch."""
    n, tile, width = x.shape

    def squares(at):
        sq[at] = _rowsum(sum(x[j, at] * x[j, at] for j in range(n)))
    _strips(tile, squares)
    inv = lax.rsqrt(sq[...] / (n * width) + st.eps)             # [T, 1]
    # the streams go by the matrix unit, Phi stays in it
    r = sum(_dot(x[j], phi_t[:, j * width:(j + 1) * width], (1, 1))
            for j in range(n))                                  # [T, _SLOTS]
    m = _to_rows(jnp.where(_lane(r.shape, n * n + 2 * n), inv, r * inv))
    k = n * n + 2 * n
    h_pre, h_post, start, _ = _activations(m[:k], scale[:k], bias[:k], n,
                                           st.clamp)
    res, _ = _halves(start, st.iters, st.eps)
    rows_out[...] = m
    cols_out[...] = _to_cols(_pack([h_pre, h_post] + res + [m[k:k + 1]],
                                   tile))

    def mix(at):
        c = cols_out[at]
        h[at] = sum(c[:, j:j + 1] * x[j, at] for j in range(n))
    _strips(tile, mix)


def _write_kernel(x, y, cols, out):
    """x [n, T, C], y [T, C], COLUMNS -> X' [n, T, C]."""
    n, tile, _ = x.shape

    def mix(at):
        c, xs, y_at = cols[at], [x[j, at] for j in range(n)], y[at]
        for i in range(n):
            at_ij = lambda j: c[:, 2 * n + i * n + j:2 * n + i * n + j + 1]
            out[i, at] = sum(at_ij(j) * xs[j] for j in range(n)) \
                + c[:, n + i:n + i + 1] * y_at
    _strips(tile, mix)


def _dy_kernel(g, cols, dy):
    """The sublayer's cotangent: g = dX' [n, T, C] -> sum_i H_post[i] g[i]."""
    n, tile, _ = g.shape

    def mix(at):
        c = cols[at]
        dy[at] = sum(c[:, n + i:n + i + 1] * g[i, at] for i in range(n))
    _strips(tile, mix)


def _back_kernel(st: _Static, g, x, y, dh, cols, rows, phi_t, scale, bias,
                 dx, da_out, dphi_t, dcols):
    """Everything else of the way back, on a tile that stays in VMEM: g =
    dX', x [n, T, C], y, dh [T, C], the kept COLUMNS and ROWS -> dx [n, T,
    C], the cotangent of a = scale m + bias as ROWS, and this tile's term
    of Phi^T's, summed over the grid in `dphi_t`; `dcols` [T, _SLOTS]:
    scratch."""
    n, tile, width = x.shape
    k = n * n + 2 * n

    @pl.when(pl.program_id(0) == 0)
    def _():
        dphi_t[...] = jnp.zeros_like(dphi_t)

    def coefficient_cotangents(at):
        gs, xs = [g[i, at] for i in range(n)], [x[j, at] for j in range(n)]
        y_at, dh_at = y[at], dh[at]
        terms = [_rowsum(dh_at * xs[j]) for j in range(n)] \
            + [_rowsum(gs[i] * y_at) for i in range(n)] \
            + [_rowsum(gs[i] * xs[j]) for i in range(n) for j in range(n)]
        acc = jnp.zeros((_STRIP, _SLOTS), jnp.float32)
        for slot, term in enumerate(terms):
            acc = jnp.where(_lane(acc.shape, slot), term, acc)
        dcols[at] = acc
    _strips(tile, coefficient_cotangents)

    m_inv = rows[...]
    m, inv = m_inv[:k], m_inv[k:k + 1]                          # ROWS
    d = _to_rows(dcols[...])
    h_pre, h_post, start, a_res = _activations(m, scale[:k], bias[:k], n,
                                               st.clamp)
    _, kept = _halves(start, st.iters, st.eps)
    dstart = _halves_back(
        [d[2 * n + i * n:2 * n + (i + 1) * n] for i in range(n)], kept)
    lo, hi = st.clamp
    da = jnp.concatenate(
        [d[:n] * h_pre * (1.0 - h_pre),
         d[n:2 * n] * h_post * (1.0 - 0.5 * h_post)]
        + [jnp.where((a > lo) & (a < hi), ds * s, 0.0)
           for ds, s, a in zip(dstart, start, a_res)], 0)       # [k, T]
    da_out[...] = _pack([da], tile)
    dm = scale[:k] * da
    # m = r inv: dr = dm inv, and the norm's share of dx is
    # -(sum_k dm m) inv^2 / (n C) x
    pull = jnp.sum(dm * m, 0, keepdims=True) * inv * inv / (n * width)
    dr = _pack([dm * inv, pull], tile)                          # ROWS
    dr_cols = _to_cols(dr)                                      # COLUMNS
    for j in range(n):
        lanes = slice(j * width, (j + 1) * width)
        dphi_t[:, lanes] += _dot(dr, x[j], (1, 0))
        dx[j] = _dot(dr_cols, phi_t[:, lanes], (1, 0))
    dcols[...] = dr_cols

    def mix(at):
        c, dh_at = cols[at], dh[at]
        gs = [g[i, at] for i in range(n)]
        norm = dcols[at][:, k:k + 1]
        for j in range(n):
            at_ij = lambda i: c[:, 2 * n + i * n + j:2 * n + i * n + j + 1]
            dx[j, at] += sum(at_ij(i) * gs[i] for i in range(n)) \
                + c[:, j:j + 1] * dh_at - norm * x[j, at]
    _strips(tile, mix)


def _call(kernel, name: str, st: _Static, grid: int, in_specs, out_specs,
          out_shape, scratch=(), sequential: bool = False):
    return pl.pallas_call(
        kernel, grid=(grid,), in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, scratch_shapes=list(scratch),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary" if sequential else "parallel",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=st.interpret, name=name)


def _specs(n: int, tile: int, width: int):
    """Block specs of a tile of positions: the streams [n, P, C], a
    sublayer's array [P, C], ROWS [_SLOTS, P], COLUMNS [P, _SLOTS], and of
    what every tile sees whole: Phi^T [_SLOTS, n C], a column [_SLOTS, 1]."""
    return (pl.BlockSpec((n, tile, width), lambda i: (0, i, 0)),
            pl.BlockSpec((tile, width), lambda i: (i, 0)),
            pl.BlockSpec((_SLOTS, tile), lambda i: (0, i)),
            pl.BlockSpec((tile, _SLOTS), lambda i: (i, 0)),
            pl.BlockSpec((_SLOTS, n * width), lambda i: (0, 0)),
            pl.BlockSpec((_SLOTS, 1), lambda i: (0, 0)))


def _f32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32)


def _run_read(st: _Static, x, phi_t, scale, bias):
    """-> (h [P, C], ROWS [_SLOTS, P], COLUMNS [P, _SLOTS])."""
    n, positions, width = x.shape
    streams, slab, rows, cols, phi, column = _specs(n, st.tile, width)
    return _call(
        functools.partial(_read_kernel, st), "mhc_read_fwd", st,
        positions // st.tile, [streams, phi, column, column],
        [slab, rows, cols],
        [_f32(positions, width), _f32(_SLOTS, positions),
         _f32(positions, _SLOTS)],
        scratch=[pltpu.VMEM((st.tile, 1), jnp.float32)])(
            x, phi_t, scale, bias)


def _run_write(st: _Static, x, y, cols_kept):
    n, positions, width = x.shape
    streams, slab, _, cols, _, _ = _specs(n, st.tile, width)
    return _call(_write_kernel, "mhc_write_fwd", st, positions // st.tile,
                 [streams, slab, cols], streams, _f32(*x.shape))(
                     x, y, cols_kept)


def _run_dy(st: _Static, g, cols_kept):
    n, positions, width = g.shape
    streams, slab, _, cols, _, _ = _specs(n, st.tile, width)
    return _call(_dy_kernel, "mhc_write_bwd", st, positions // st.tile,
                 [streams, cols], slab, _f32(positions, width))(g, cols_kept)


def _run_back(st: _Static, g, x, y, dh, cols_kept, rows_kept, phi_t, scale,
              bias):
    """-> (dx [n, P, C], da ROWS [_SLOTS, P], dPhi^T [_SLOTS, n C])."""
    n, positions, width = x.shape
    streams, slab, rows, cols, phi, column = _specs(n, st.tile, width)
    return _call(
        functools.partial(_back_kernel, st), "mhc_read_bwd", st,
        positions // st.tile,
        [streams, streams, slab, slab, cols, rows, phi, column, column],
        [streams, rows, phi],
        [_f32(*x.shape), _f32(_SLOTS, positions), _f32(*phi_t.shape)],
        scratch=[pltpu.VMEM((st.tile, _SLOTS), jnp.float32)],
        sequential=True)(g, x, y, dh, cols_kept, rows_kept, phi_t, scale,
                         bias)


def _small(p, n: int, width: int):
    """A mixer's parameters as the kernels read them: Phi^T [_SLOTS, n C]
    (zero rows below its n^2 + 2n), and the scale and the bias of every
    slot as columns [_SLOTS, 1]."""
    k = n * n + 2 * n
    below = ((0, _SLOTS - k), (0, 0))
    alpha = p["alpha"].astype(jnp.float32)
    scale = jnp.repeat(alpha, jnp.asarray([n, n, n * n]),
                       total_repeat_length=k)
    return (jnp.pad(p["phi"].astype(jnp.float32).T, below),
            jnp.pad(scale[:, None], below),
            jnp.pad(p["bias"].astype(jnp.float32)[:, None], below))


def _h_res(cols_kept, n: int):
    """COLUMNS -> H_res [n, n, P], as `coefficients` returns it."""
    return cols_kept[:, 2 * n:2 * n + n * n].T.reshape(n, n, -1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _fused(st: _Static, sublayer, x, p, consts):
    """`connect` as the kernels; `sublayer(h, *consts)`: every array it
    reads besides h is an argument (`jax.closure_convert`)."""
    n, _, width = x.shape
    with jax.named_scope("mhc_mix"):
        h, _, cols = _run_read(st, x, *_small(p, n, width))
    y, aux = sublayer(h, *consts)
    with jax.named_scope("mhc_mix"):
        return _run_write(st, x, y, cols), aux, _h_res(cols, n)


def _fused_fwd(st: _Static, sublayer, x, p, consts):
    n, _, width = x.shape
    with jax.named_scope("mhc_mix"):
        small = _small(p, n, width)
        h, rows, cols = _run_read(st, x, *small)
    y, pull, aux = jax.vjp(lambda h, consts: sublayer(h, *consts), h, consts,
                           has_aux=True)
    with jax.named_scope("mhc_mix"):
        out = _run_write(st, x, y, cols)
    return (out, aux, _h_res(cols, n)), (x, y, rows, cols, small, pull, p)


def _fused_bwd(st: _Static, sublayer, kept, cotangents):
    x, y, rows, cols, small, pull, p = kept
    g = cotangents[0]           # aux and H_res carry no gradient
    n = x.shape[0]
    k = n * n + 2 * n
    with jax.named_scope("mhc_mix"):
        dy = _run_dy(st, g, cols)
    dh, dconsts = pull(dy)
    with jax.named_scope("mhc_mix"):
        dx, da, dphi_t = _run_back(st, g, x, y, dh, cols, rows, *small)
        da, m = da[:k], rows[:k]
        groups = lambda a: jnp.stack([
            jnp.sum(a[:n]), jnp.sum(a[n:2 * n]), jnp.sum(a[2 * n:])])
        dp = {"phi": dphi_t[:k].T.astype(p["phi"].dtype),
              "bias": jnp.sum(da, 1).astype(p["bias"].dtype),
              "alpha": groups(da * m).astype(p["alpha"].dtype)}
    return dx, dp, dconsts


_fused.defvjp(_fused_fwd, _fused_bwd)


def connect(sublayer, x, p, *, iters: int, eps: float, clamp,
            kernels: bool = False, interpret: bool = False):
    """One sublayer under its hyper-connection: x [n, P, C] ->
    (X' like x, what `sublayer` returns beside its output, `res_gap`).
    `sublayer`: [P, C] -> (y [P, C], aux); it norms its own input.
    `kernels` (the TPU, as models/xing4.py `make` resolves it) and a shape
    the kernels fit (`plan`) take the Pallas kernels; anything else the
    `jax.numpy` form.  `interpret` runs the kernels in Pallas' interpreter
    (the CPU tests)."""
    tile = plan(x.shape, x.dtype, kernels)
    if not tile:
        return _plain(sublayer, x, p, iters=iters, eps=eps, clamp=clamp)
    st = _Static(tile, iters, float(eps), tuple(map(float, clamp)),
                 interpret)
    converted, consts = jax.closure_convert(sublayer, x[0])
    out, aux, h_res = _fused(st, converted, x, p, consts)
    return out, aux, lax.stop_gradient(res_gap(h_res))
