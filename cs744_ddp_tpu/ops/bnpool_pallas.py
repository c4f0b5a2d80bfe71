"""Fused BN->ReLU->MaxPool2x2 with a Pallas TPU backward.

**Status: measured NEGATIVE result — correct, tested, NOT wired into the
model zoo.**  On the v5e chip (scan-amortized fwd+grad A/B vs the plain
XLA composition, 2026-07-31):

    bf16 [1536,32,32,64]: fused 7.86 ms/iter vs XLA 5.92 — 0.75x
    f32  [256,32,32,64]:  fused 3.13 ms/iter vs XLA 2.84 — 0.91x

(First formulation — whole-block intermediates — was 9.6 ms and hit
Mosaic's 16 MB scoped-VMEM limit at 2 MiB blocks; the committed version
streams chunks through a fori_loop, which recovered 1.8 ms but not the
gap.)  The lesson recorded so it is not retried: this chain is NOT
HBM-bound in any implementation — its single-pass traffic bound (~0.9 ms
at bf16/b1536) is unreachable because the routed-scatter formulation
costs ~30 VPU ops/element (routing compares, first-match masks, selects,
dtype round-trips), making it VPU-bound at ~6x the DMA time, while XLA's
four separate kernels each do a few ops/element and together finish in
~3.4 ms.  Combined with rounds 3-4's four jnp-level fusion attempts (all
~15% slower whole-step, models/layers.py::maxpool2x2), the conclusion is
now implementation-family-independent: XLA's native select-and-scatter +
split BN backward is the right lowering for this chain on this hardware.

Why it was built (round 5): the occupancy account (BASELINE.md,
tools/perf_occupancy.py) shows the TensorCore 99.9% busy — the remaining
MFU gap is in-kernel, and the dominant opportunity was the pool-preceded
BN block's BACKWARD: XLA executes it as four separate kernels
(select-and-scatter, relu-mask fusion, two BN-backward fusions) that
together re-read the stage-0 activation ~10x (2.63 ms/iter = 19.5% of the
bf16/b1536 step).  Pallas writes the memory schedule directly, which is
the one lever the jnp-level attempts lacked — the hypothesis was wrong
for an interesting reason (VPU cost, not memory schedule), which is why
the module stays: working evidence, reusable scaffolding (lane-merged
pooling layout, chunked-streaming grid pattern), numerics pinned by
tests/test_bnpool_pallas.py.

The backward is TWO Pallas passes over the residual (the minimum for
BatchNorm, whose dx needs the global sums):

  phase 1: recompute pool routing + relu gate from xhat, reduce
           sum(dy) and sum(dy*xhat) per channel         (reads xhat, dP)
  phase 2: dx = (gamma*inv/n)(n*dy - sum_dy - xhat*sum_dy_xhat),
           scattered back through the same routing      (reads again, writes dx)

Layout strategy (the whole trick): a [B,H,W,C] block is viewed as
[B, H/2, 2, W/2, 2C] — the H-split is a major-dim split (free) and the
W-pair MERGES INTO THE LANE DIMENSION (2C = 128 lanes exactly for the
C=64 stage this kernel targets; C>=128 stages use multiples).  Window
partners become lane-half slices, so the routing/scatter needs ZERO
sublane relayouts — the formulation error that made earlier attempts
slow (and made Mosaic spill registers when tried as stacks/reshapes).

Semantics match the unfused path exactly in f32; in bf16 the routing can
differ at ~1e-4 of elements where XLA's excess-precision pooling
(compare-before-rounding under --xla_allow_excess_precision) or the
residual's double rounding distinguishes values within 1-2 bf16 ulps —
the op is exactly consistent with ITS OWN forward (built from the same
rounded residual), pinned by the test:

  * pool gradient goes to the FIRST maximal element in row-major window
    order (torch's convention, XLA's select-and-scatter behavior —
    pinned in tests/test_layers.py);
  * relu gate is (pre-relu > 0), i.e. no gradient at exactly 0 (torch);
  * reductions accumulate in f32 regardless of the activation dtype;
  * the routing is recomputed from Z = gamma*xhat + beta, sharing the
    BN residual — relu destroyed negative Z, but wherever relu clipped,
    the gate zeroes the gradient, so recomputation is exact.

Forward stays plain XLA (it fuses into the producing conv); only the
backward is Pallas.  Reference chain being replaced:
``/root/reference/src/Part 1/model.py`` Conv->BN->ReLU->MaxPool blocks.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# The BN semantics this op must match are DEFINED in models/layers.py —
# share its constants/statistics so a future tuning there cannot silently
# diverge from this fused variant.
from ..models.layers import BN_EPS, _bn_train_fwd_impl

# VMEM budget per xhat block (the DMA granularity).  Compute streams the
# block in _CHUNK_ROWS-row chunks, so the block size is bounded by the
# VMEM the pipeline's double-buffered inputs + output occupy, not by the
# kernels' live intermediates.
_BLOCK_BYTES = 2 * 1024 * 1024


def _halves(x, c):
    """Lane halves of a [..., 2C] value: (even-column, odd-column)."""
    return x[..., :c], x[..., c:]


def _routed(xh5, dp, gamma2, beta2, c, act_dtype):
    """Per-quadrant routed+gated gradients and xhat quadrants.

    xh5: [B,H/2,2,W/2,2C] f32 (lane-merged view of xhat)
    dp:  [B,H/2,W/2,C]    f32 (pool output grad)
    Returns (dyq, xq): 4-tuples in row-major window order 00,01,10,11.

    The max/tie comparisons and the relu gate run on values ROUNDED to
    ``act_dtype`` — the dtype the forward's pool actually compared in —
    then upcast to f32 for the compare itself (the VPU has no bf16
    compare; upcasting is injective, so tie semantics are identical).
    bf16 routing thus matches the unfused path except where
    bf16(bf16(xhat)*gamma+beta) double-rounds differently from the
    forward's single rounding (a ~1-ulp tie flip that moves dP to an
    equal-valued window element).
    """
    x0, x1 = xh5[:, :, 0], xh5[:, :, 1]            # [B,H/2,W/2,2C]
    z0 = (x0 * gamma2 + beta2).astype(act_dtype).astype(jnp.float32)
    z1 = (x1 * gamma2 + beta2).astype(act_dtype).astype(jnp.float32)
    zero = jnp.zeros((), jnp.float32)
    y0 = jnp.maximum(z0, zero)
    y1 = jnp.maximum(z1, zero)
    a, b = _halves(y0, c)                          # window row 0
    cc, d = _halves(y1, c)                         # window row 1
    wmax = jnp.maximum(jnp.maximum(a, b), jnp.maximum(cc, d))
    hit_a = a == wmax
    hit_b = (b == wmax) & ~hit_a
    hit_c = (cc == wmax) & ~hit_a & ~hit_b
    hit_d = (d == wmax) & ~hit_a & ~hit_b & ~hit_c
    za, zb = _halves(z0, c)
    zc, zd = _halves(z1, c)
    dyq = (jnp.where(hit_a & (za > zero), dp, 0.0),
           jnp.where(hit_b & (zb > zero), dp, 0.0),
           jnp.where(hit_c & (zc > zero), dp, 0.0),
           jnp.where(hit_d & (zd > zero), dp, 0.0))
    xa, xb = _halves(x0, c)
    xc, xd = _halves(x1, c)
    return dyq, (xa, xb, xc, xd)


# Rows of the block processed per inner-loop iteration: the kernels hold
# ~12 chunk-sized f32 intermediates live, so the CHUNK bounds the vreg
# working set while the BLOCK (DMA granularity) stays large.
_CHUNK_ROWS = 4


def _sums_kernel(xhat_ref, dp_ref, gamma2_ref, beta2_ref, out_ref, *, c,
                 chunk_rows):
    """Phase 1: accumulate [2,C] = (sum_dy, sum_dy_xhat) over the grid,
    streaming the block through chunk_rows-row chunks.

    ASSUMES sequential grid execution: ``out_ref`` carries the running
    accumulator from step to step (init at program 0, += after), so the
    ``pallas_call`` must pin ``dimension_semantics=("arbitrary",)`` — on
    megacore TPUs (v4/v5p) a parallel grid dimension would be split across
    cores and the read-modify-write would race."""
    bn = xhat_ref.shape[0]
    gamma2, beta2 = gamma2_ref[:], beta2_ref[:]
    act = xhat_ref.dtype

    def chunk(i, acc):
        r = i * chunk_rows
        xh5 = xhat_ref[pl.ds(r, chunk_rows)].astype(jnp.float32)
        dp = dp_ref[pl.ds(r, chunk_rows)].astype(jnp.float32)
        dyq, xq = _routed(xh5, dp, gamma2, beta2, c, act)
        dy_tot = dyq[0] + dyq[1] + dyq[2] + dyq[3]
        dyx_tot = (dyq[0] * xq[0] + dyq[1] * xq[1]
                   + dyq[2] * xq[2] + dyq[3] * xq[3])
        return acc + jnp.stack([jnp.sum(dy_tot.reshape(-1, c), axis=0),
                                jnp.sum(dyx_tot.reshape(-1, c), axis=0)])

    acc = jax.lax.fori_loop(0, bn // chunk_rows, chunk,
                            jnp.zeros((2, c), jnp.float32))

    @pl.when(pl.program_id(0) == 0)
    def _():
        out_ref[:] = acc

    @pl.when(pl.program_id(0) != 0)
    def _():
        out_ref[:] += acc


def _dx_kernel(xhat_ref, dp_ref, gamma2_ref, beta2_ref, inv2_ref,
               sums2_ref, dx_ref, *, c, n, chunk_rows):
    """Phase 2: dx through the same routing, streamed in chunks.
    ``n`` = N*H*W, the BN reduction count (static)."""
    bn = xhat_ref.shape[0]
    gamma2, beta2 = gamma2_ref[:], beta2_ref[:]
    act = xhat_ref.dtype
    sum_dy2 = sums2_ref[0, :]                       # [2C], duplicated
    sum_dy_xhat2 = sums2_ref[1, :]
    scale2 = gamma2 * inv2_ref[:] * (1.0 / n)

    def chunk(i, carry):
        r = i * chunk_rows
        xh5 = xhat_ref[pl.ds(r, chunk_rows)].astype(jnp.float32)
        dp = dp_ref[pl.ds(r, chunk_rows)].astype(jnp.float32)
        dyq, xq = _routed(xh5, dp, gamma2, beta2, c, act)
        # dx per window row, built in the lane-merged [.., 2C] domain so
        # the store back through the free reshape needs no relayout.
        dz0 = jnp.concatenate([dyq[0], dyq[1]], axis=-1)
        dz1 = jnp.concatenate([dyq[2], dyq[3]], axis=-1)
        xh0 = jnp.concatenate([xq[0], xq[1]], axis=-1)
        xh1 = jnp.concatenate([xq[2], xq[3]], axis=-1)
        dx0 = scale2 * (n * dz0 - sum_dy2 - xh0 * sum_dy_xhat2)
        dx1 = scale2 * (n * dz1 - sum_dy2 - xh1 * sum_dy_xhat2)
        dx_ref[pl.ds(r, chunk_rows)] = jnp.stack(
            [dx0, dx1], axis=2).astype(dx_ref.dtype)
        return carry

    jax.lax.fori_loop(0, bn // chunk_rows, chunk, 0)


def _blk(shape, itemsize):
    """Batch-rows per block for a [N,H,W,C] residual: as many rows as
    keep the xhat block within _BLOCK_BYTES."""
    n, h, w, c = shape
    return max(1, min(n, _BLOCK_BYTES // (h * w * c * itemsize)))


def _dup(v):
    """[C] -> [2C] channel vector for the lane-merged domain."""
    return jnp.concatenate([v.astype(jnp.float32)] * 2)


def _pallas_backward(xhat, dp, gamma, beta, inv, out_dtype):
    """(dx, sum_dy, sum_dy_xhat) via the two-phase Pallas kernels."""
    n_, h, w, c = xhat.shape
    bn = _blk(xhat.shape, xhat.dtype.itemsize)
    while n_ % bn:
        bn -= 1
    chunk_rows = min(_CHUNK_ROWS, bn)
    while bn % chunk_rows:
        chunk_rows -= 1
    grid = (n_ // bn,)
    gamma2, beta2, inv2 = _dup(gamma), _dup(beta), _dup(inv)
    # The lane-merged view (free: row-major linearization is unchanged);
    # last two dims (W/2, 2C) tile the VPU exactly at C=64.
    xh5 = xhat.reshape(n_, h // 2, 2, w // 2, 2 * c)

    xh_spec = pl.BlockSpec((bn, h // 2, 2, w // 2, 2 * c),
                           lambda i: (i, 0, 0, 0, 0),
                           memory_space=pltpu.VMEM)
    dp_spec = pl.BlockSpec((bn, h // 2, w // 2, c), lambda i: (i, 0, 0, 0),
                           memory_space=pltpu.VMEM)
    ch_spec = pl.BlockSpec((2 * c,), lambda i: (0,),
                           memory_space=pltpu.VMEM)
    sums_spec = pl.BlockSpec((2, c), lambda i: (0, 0),
                             memory_space=pltpu.VMEM)

    # The sums kernel ACCUMULATES into out_ref across grid steps (phase-1
    # reduction), which is only sound if the grid executes sequentially on
    # one core: "arbitrary" semantics pin that, keeping megacore chips
    # (v4/v5p, which otherwise split a parallel grid across two cores with
    # separate out_ref instances) from racing the read-modify-write.
    sums = pl.pallas_call(
        partial(_sums_kernel, c=c, chunk_rows=chunk_rows),
        grid=grid,
        in_specs=[xh_spec, dp_spec, ch_spec, ch_spec],
        out_specs=sums_spec,
        out_shape=jax.ShapeDtypeStruct((2, c), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
    )(xh5, dp, gamma2, beta2)

    sums2 = jnp.concatenate([sums, sums], axis=1)   # [2, 2C]
    dx5 = pl.pallas_call(
        partial(_dx_kernel, c=c, n=float(n_ * h * w),
                chunk_rows=chunk_rows),
        grid=grid,
        in_specs=[xh_spec, dp_spec, ch_spec, ch_spec, ch_spec,
                  pl.BlockSpec((2, 2 * c), lambda i: (0, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=xh_spec,
        out_shape=jax.ShapeDtypeStruct((n_, h // 2, 2, w // 2, 2 * c),
                                       out_dtype),
    )(xh5, dp, gamma2, beta2, inv2, sums2)
    return dx5.reshape(n_, h, w, c), sums[0], sums[1]


def _fwd_impl(x, gamma, beta):
    """Plain-XLA forward: BN (centered or one-pass per dtype, matching
    models/layers.py semantics) -> relu -> 2x2 maxpool.

    Z is computed FROM THE ROUNDED RESIDUAL (xhat cast to the activation
    dtype and back) so the backward's routing reconstruction —
    act(f32(act(xhat)) * gamma + beta) — is BIT-IDENTICAL to what the
    forward's pool compared: the fused op is exactly consistent with its
    own gradient.  In f32 the casts are identity (the parity path is
    unchanged); in bf16 the output moves by <= 1 ulp vs the unfused
    composition (bf16 mode is already a documented deviation)."""
    if x.shape[1] % 2 or x.shape[2] % 2:
        raise ValueError(
            f"bn_relu_pool requires even H and W (2x2/2 pool windows; the "
            f"backward's lane-merged layout assumes no truncated rows), "
            f"got {x.shape}")
    # Statistics from the ONE shared BN implementation (centered two-pass
    # f32 / one-pass bf16 per models/layers.py); its y is discarded — the
    # fused op rebuilds z from the ROUNDED xhat below — and DCE'd by XLA.
    _, xhat, mean, var, inv = _bn_train_fwd_impl(x, gamma, beta)
    xhat_act = xhat.astype(x.dtype).astype(jnp.float32)
    z = (xhat_act * gamma + beta).astype(x.dtype)
    y = jnp.maximum(z, jnp.zeros((), x.dtype))
    pooled = lax.reduce_window(y, -jnp.inf, lax.max,
                               window_dimensions=(1, 2, 2, 1),
                               window_strides=(1, 2, 2, 1), padding="VALID")
    return pooled, xhat, mean, var, inv


@jax.custom_vjp
def bn_relu_pool(x, gamma, beta):
    """(pooled, mean, var) with the fused Pallas backward."""
    pooled, _, mean, var, _ = _fwd_impl(x, gamma, beta)
    return pooled, mean, var


def _bn_relu_pool_fwd(x, gamma, beta):
    pooled, xhat, mean, var, inv = _fwd_impl(x, gamma, beta)
    # Residual in the activation dtype (halves backward HBM traffic in
    # bf16 mode, same policy as models/layers.py::_bn_train_fwd).
    return (pooled, mean, var), (xhat.astype(x.dtype), inv, gamma, beta)


def _bn_relu_pool_bwd(res, cts):
    xhat_stored, inv, gamma, beta = res
    in_dtype = xhat_stored.dtype
    dp = cts[0]
    dx, sum_dy, sum_dy_xhat = _pallas_backward(
        xhat_stored, dp, gamma, beta, inv, in_dtype)
    # Exact cotangent terms for the mean/var outputs (normally zero: they
    # feed only the running-stats update — same policy as
    # models/layers.py::_bn_train_bwd, where XLA folds the zeros away).
    n = xhat_stored.shape[0] * xhat_stored.shape[1] * xhat_stored.shape[2]
    ct_mean = cts[1].astype(jnp.float32)
    ct_var = cts[2].astype(jnp.float32)
    dx = (dx.astype(jnp.float32) + ct_mean / n
          + (2.0 / n) * ct_var * (xhat_stored.astype(jnp.float32) / inv)
          ).astype(in_dtype)
    return dx, sum_dy_xhat, sum_dy


bn_relu_pool.defvjp(_bn_relu_pool_fwd, _bn_relu_pool_bwd)
