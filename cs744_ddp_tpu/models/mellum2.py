"""One chip's share of a mixture-of-experts decoder whose softmax layers
come in two kinds (``model_type: mellum``; Mellum2-12B-A2.5B-Instruct's
config.json).

Layers in the published pattern ``sliding, sliding, sliding, full``
(`layer_types`), every one sparse (`mlp_layer_types`).  Every layer, for a
residual stream x [P, H]:

    x = x + attn(rms(x; ln1))        x = x + moe(rms(x; ln2))
    rms(x; w) = x * rsqrt(mean(x^2) + eps) * w                 w starts at 1

then ``rms(x; final)`` and an untied head.  No bias anywhere.

  attention: q = h W_q [P, heads, D], k = h W_k, v = h W_v [P, kv_heads, D],
    rotate-half rotary over the whole head (ops/mla.py `rotary`), then
    o = softmax(q k^T / sqrt(D) + mask) v, grouped-query, and o W_o.  The
    two kinds differ in the mask and the rotary, not in their parameters:
      sliding: the window (ops/attention.py `window_attention`: a query sees
               the `window` keys that end at its own position); the default
               frequencies base^(-2i/D)
      full:    causal (`causal_attention`); YaRN's frequencies
               (`mla.yarn_inv_freq`), cos and sin times `attn_factor`
  expert layer (ops/moe.py): softmax over ALL experts, the top k
    renormalised, this chip's held experts' part of the sum; no shared
    expert.

What is held HERE is an argument of the factory (`Shape`: the layers, whole
periods in the published order; the expert ids; the rows of the vocabulary),
as for the other decoders.  The parameters of the two kinds sit in two
subtrees, stacked by period (and, the sliding ones, by their place in it):
the forward pass scans the periods, inside a period the sliding layers, then
runs the period's full layer (models/qwen3next.py's structure).  Inside a
layer the sequences go one at a time, each recomputed in the backward pass
but for the attention kernels' output and logsumexp, which are kept.
Training is next-token prediction (models/qwen3next.py `NextToken`).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..ops import attention, mla, moe
from . import qwen3next
from .sdar import rmsnorm


class Shape(NamedTuple):
    """Widths as published, and this chip's share."""
    hidden: int = 2304
    heads: int = 32
    kv_heads: int = 4
    head_dim: int = 128
    expert_width: int = 896
    num_experts: int = 64
    top_k: int = 8
    window: int = 1024                      # sliding_window
    rope_theta: float = 5e5                 # both kinds
    yarn_factor: float = 16.0
    yarn_original: int = 8192
    yarn_beta: Tuple[float, float] = (32.0, 1.0)    # fast, slow
    attn_factor: float = 1.2772588722239782
    eps: float = 1e-6
    interval: int = 4                       # three sliding, then one full
    layers: int = 8                         # of 28: two periods
    held: Tuple[int, ...] = tuple(range(8))     # of 64: 8 chips a layer
    vocab: int = 12288                      # of 98,304: an eighth
    seq_len: int = 8192


# The rotary base and YaRN's original length scaled down with the head and
# the sequence, so that its ramp falls inside the tiny head's 8 pairs (pair 0
# kept, 1-6 ramped, 7 over 16) and turns them visibly over 32 positions.
TINY = Shape(hidden=64, heads=4, kv_heads=2, head_dim=16, expert_width=32,
             num_experts=8, top_k=2, window=8, rope_theta=100.0,
             yarn_original=256, layers=4, held=(0, 1), vocab=64, seq_len=32)


def init_params(key, shape: Shape):
    """Normal, std 0.02, every matrix (the router too); RMSNorm gains 1;
    the embedding's rows std 1 (a position's route follows its token, as
    for models/sdar.py).  One key per drawn leaf, in this order: embed,
    the sliding layers' (wq, wk, wv, wo, router, w_gate, w_up, w_down), the
    full layers' (the same), head."""
    s = shape
    n, h, f, g = s.layers // s.interval, s.hidden, s.expert_width, len(s.held)
    q, kv = s.heads * s.head_dim, s.kv_heads * s.head_dim

    def layer(lead):
        return [("wq", lead + (h, q)), ("wk", lead + (h, kv)),
                ("wv", lead + (h, kv)), ("wo", lead + (q, h)),
                ("router", lead + (h, s.num_experts)),
                ("w_gate", lead + (g, h, f)), ("w_up", lead + (g, h, f)),
                ("w_down", lead + (g, f, h))]
    S, F = (n, s.interval - 1), (n,)
    drawn = ([("embed", None, (s.vocab, h))]
             + [("sliding", name, shp) for name, shp in layer(S)]
             + [("full", name, shp) for name, shp in layer(F)]
             + [("head", None, (h, s.vocab))])
    out = {"sliding": {}, "full": {}}
    for k, (group, name, shp) in zip(jax.random.split(key, len(drawn)),
                                     drawn):
        leaf = (1.0 if group == "embed" else 0.02) \
            * jax.random.normal(k, shp, jnp.float32)
        if name is None:
            out[group] = leaf
        else:
            out[group][name] = leaf
    ones = lambda *shp: jnp.ones(shp, jnp.float32)
    for group, lead in (("sliding", S), ("full", F)):
        out[group].update(ln1=ones(*lead, h), ln2=ones(*lead, h))
    return {"embed": out["embed"],
            "periods": {"sliding": out["sliding"], "full": out["full"]},
            "final_norm": ones(h), "head": out["head"]}


def default_inv_freq(dim: int, base: float) -> np.ndarray:
    """base^(-2i/dim) for the dim / 2 pairs (i, i + dim/2)."""
    return (base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
            ).astype(np.float32)


def _attention(shape: Shape, kernels: bool, full: bool, h, p, rotary):
    """h [P, H] (after ln1) -> o W_o [P, H], under the sliding window or,
    `full`, the causal rule."""
    s = shape
    P, D = h.shape[0], s.head_dim
    positions, inv_freq, attn_factor = rotary
    proj = lambda w, heads: jnp.dot(h, p[w].astype(h.dtype)).reshape(
        P, heads, D)
    turn = lambda x: mla.rotary(x, positions, inv_freq, attn_factor)
    q = turn(proj("wq", s.heads)) * jnp.asarray(D ** -0.5, h.dtype)
    k, v = turn(proj("wk", s.kv_heads)), proj("wv", s.kv_heads)
    # heads first; the kernels' operand type out here, so that what sits
    # under the rule's scope on a TPU is the three kernels alone
    core = jnp.bfloat16 if kernels else h.dtype
    lay = lambda a: a.transpose(1, 0, 2)[None].astype(core)
    if full:
        o = attention.causal_attention(lay(q), lay(k), lay(v),
                                       kernels=kernels)
    else:
        o = attention.window_attention(lay(q), lay(k), lay(v),
                                       window=s.window, kernels=kernels)
    o = o[0].transpose(1, 0, 2).astype(h.dtype)
    return jnp.dot(o.reshape(P, s.heads * D), p["wo"].astype(h.dtype))


def _layer(shape: Shape, kernels: bool, full: bool, rotary, x, p):
    """One sequence through one layer of either kind: x [P, H] -> (x, rows
    computed by this chip's experts, rows of its fullest expert, rows of
    the dropless buffer the expert layer touched)."""
    s = shape
    with jax.named_scope("attn_global" if full else "attn_window"):
        x = x + _attention(s, kernels, full, rmsnorm(x, p["ln1"], s.eps), p,
                           rotary)
    h = rmsnorm(x, p["ln2"], s.eps)
    y, rows, fullest = moe.expert_layer(
        h, p, held=s.held, num_experts=s.num_experts, top_k=s.top_k,
        kernels=kernels)
    return x + y, rows, fullest, moe.prefix_rows(
        rows, x.shape[0] * s.top_k, kernels)


def _remat_layer(shape: Shape, kernels: bool, full: bool, rotary, p):
    """`_layer` of one sequence under the layer's checkpoint: the backward
    pass recomputes all of it but the attention kernels' output and
    logsumexp, which it keeps (`attention.KEEP_ATTN_RESIDUALS`), so the
    forward kernel runs once."""
    return jax.checkpoint(
        lambda x: _layer(shape, kernels, full, rotary, x, p),
        policy=attention.KEEP_ATTN_RESIDUALS)


def make(shape: Shape = Shape(), kernels=None):
    """(init_fn, apply_fn) for one share.  `kernels`: the Pallas attention
    and grouped-matmul kernels; None = wherever the default backend is a
    TPU (a deviceless compile for a described TPU passes True)."""
    s = shape
    if s.layers % s.interval or s.heads % s.kv_heads or s.head_dim % 2:
        raise ValueError(
            f"mellum2: layers {s.layers} / interval {s.interval} (whole "
            f"periods), heads {s.heads} / kv_heads {s.kv_heads}, head_dim "
            f"{s.head_dim}")
    fast, slow = s.yarn_beta
    tables = {False: (default_inv_freq(s.head_dim, s.rope_theta), 1.0),
              True: (mla.yarn_inv_freq(s.head_dim, s.rope_theta,
                                       s.yarn_factor, s.yarn_original, fast,
                                       slow), s.attn_factor)}

    def init_fn(key):
        return init_params(key, s), {}

    def resolved() -> bool:
        return jax.default_backend() == "tpu" if kernels is None else kernels

    def apply_fn(params, bn_state, x, train=True, compute_dtype=None):
        """x: token ids [S, L].  Returns (hidden [S, L, H] after the final
        norm, {}, (rows computed by this chip's experts summed over layers,
        rows of the fullest held expert of any layer, rows of the dropless
        buffers touched for them))."""
        del train                       # no dropout, no batch statistics
        on_tpu = resolved()
        h = params["embed"][x]
        if compute_dtype is not None:
            h = h.astype(compute_dtype)
        positions = jnp.arange(x.shape[1])

        def layer(full):
            rotary = (positions,) + tables[full]

            def run(x, p):
                # one sequence at a time, each recomputed in the backward
                # pass but for its attention kernels' output; ONE
                # checkpoint a layer (models/qwen3next.py)
                x, rows, fullest, touched = lax.map(
                    _remat_layer(s, on_tpu, full, rotary, p), x)
                return x, jnp.stack([jnp.sum(rows), jnp.max(fullest),
                                     jnp.sum(touched)])
            return run

        def period(x, p):
            x, sliding = lax.scan(layer(False), x, p["sliding"])
            x, last = layer(True)(x, p["full"])
            return x, jnp.concatenate([sliding, last[None]])
        h, counts = lax.scan(period, h, params["periods"])
        counts = counts.reshape(-1, 3)          # a row a layer
        hidden = rmsnorm(h, params["final_norm"], s.eps)
        return hidden, bn_state, (jnp.sum(counts[:, 0]), jnp.max(counts[:, 1]),
                                  jnp.sum(counts[:, 2]))

    apply_fn.objective = NextToken(s, resolved)
    return init_fn, apply_fn


class NextToken(qwen3next.NextToken):
    """Next-token prediction (models/qwen3next.py `NextToken`: the loss, the
    evaluation, the expert counters, nothing drawn) with this model's
    gauges."""

    @functools.cached_property
    def gauges(self):
        """Whether the attention runs as the Pallas kernels (1) or as
        jax.numpy (0), the window, whether the layers' checkpoint keeps the
        kernels' output (1: the backward pass runs no forward kernel; 0 on
        the jax.numpy path, which names nothing to keep), and what the tiles
        of each rule's kernels cost at this length (the TPU's path): a
        recorder's, once."""
        s = self.shape
        on = int(self.kernels())
        return [("window_kernel", on, {}),
                ("attn_window", s.window, {}),
                ("attn_residuals_kept", on, {})] \
            + attention.window_tile_gauges(self.seq_len, s.window) \
            + attention.causal_tile_gauges(self.seq_len)
