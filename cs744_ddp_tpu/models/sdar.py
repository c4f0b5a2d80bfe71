"""One chip's share of an SDAR mixture-of-experts block-diffusion decoder
(``model_type: sdar_moe``; SDAR-30B-A3B-Chat's config.json).

The published block, for a residual stream x [P, H]:

    h = rmsnorm(x; ln1)
    q = rope(rmsnorm_head(h Wq; q_norm), pos)   k likewise   v = h Wv
    x = x + (softmax(q k^T / sqrt(D) + M) v) Wo      M: ops/attention.py
    h = rmsnorm(x; ln2)
    x = x + sum over the top-8 of softmax(h Wr), renormalised, of
            w_e * Wd_e(silu(Wg_e h) * (Wu_e h))      ops/moe.py

then a final RMSNorm and an untied head.  Grouped-query attention (each
key/value head serves HQ/HKV query heads), a per-head RMSNorm on q and k
(the Qwen3-MoE block, which sdar_moe continues), rotary positions over the
whole head, no bias anywhere, no shared expert, no dense layer.

What is held HERE is an argument of the factory, never a constant: the
number of layers, the expert ids (`held`, out of `num_experts`: the router
still scores all of them) and the rows of the vocabulary.  What the absent
experts would have added is left out and the partial sum goes on to the next
layer; nothing stands in for the other chips.

Training runs ONCE over the 2L positions ``[xt ; x0]`` of every sequence
(noisy copy, then clean), with position ids ``[0..L-1 ; 0..L-1]``; only the
noisy half goes on to the head (`BlockDiffusion`, below, is the objective
the compiled steps call).  The layers are stacked on a leading axis and
scanned; inside a layer the sequences go one at a time, each recomputed in
the backward pass but for the attention kernel's output and logsumexp, which
are kept.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..ops import attention, loss as losslib, moe

EVAL_KEY = 0x5DA2       # the evaluation's draws come from a fixed key


class Shape(NamedTuple):
    """Widths as published, and this chip's share."""
    hidden: int = 2048
    heads: int = 32
    kv_heads: int = 4
    head_dim: int = 128
    expert_width: int = 768
    num_experts: int = 128
    top_k: int = 8
    rope_theta: float = 1e6
    eps: float = 1e-6
    layers: int = 6                         # of 48
    held: Tuple[int, ...] = tuple(range(16))    # of 128: 8 chips a layer
    vocab: int = 18992                      # of 151,936: an eighth
    seq_len: int = 4096
    block: int = 4

    @property
    def mask_id(self) -> int:
        return self.vocab - 1               # data ids come from [0, vocab-1)


TINY = Shape(hidden=64, heads=4, kv_heads=2, head_dim=16, expert_width=32,
             num_experts=8, top_k=2, layers=2, held=(0, 1), vocab=64,
             seq_len=32, block=4)


def rmsnorm(x, gain, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * lax.rsqrt(jnp.mean(jnp.square(x32), -1, keepdims=True) + eps)
    return (y * gain).astype(x.dtype)


def rope(x, positions, theta):
    """x [S, P, heads, D], rotate-half convention over the whole head."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None, :]
    x32 = x.astype(jnp.float32)
    rot = jnp.concatenate([-x32[..., d // 2:], x32[..., :d // 2]], -1)
    return (x32 * cos + rot * sin).astype(x.dtype)


def init_params(key, shape: Shape):
    """Normal, std 0.02, every matrix (the router too); norm gains 1; the
    embedding's rows std 1, so that a position's route follows its token
    and not its sequence (ISSUE 29).  One key per leaf, in this order."""
    s = shape
    n, h, f, g = s.layers, s.hidden, s.expert_width, len(s.held)
    q, kv = s.heads * s.head_dim, s.kv_heads * s.head_dim
    shapes = [("embed", (s.vocab, h), 1.0),
              ("wq", (n, h, q), 0.02), ("wk", (n, h, kv), 0.02),
              ("wv", (n, h, kv), 0.02), ("wo", (n, q, h), 0.02),
              ("router", (n, h, s.num_experts), 0.02),
              ("w_gate", (n, g, h, f), 0.02), ("w_up", (n, g, h, f), 0.02),
              ("w_down", (n, g, f, h), 0.02), ("head", (h, s.vocab), 0.02)]
    keys = jax.random.split(key, len(shapes))
    drawn = {name: std * jax.random.normal(k, shp, jnp.float32)
             for k, (name, shp, std) in zip(keys, shapes)}
    ones = lambda *shp: jnp.ones(shp, jnp.float32)
    layers = {name: drawn[name] for name in
              ("wq", "wk", "wv", "wo", "router", "w_gate", "w_up", "w_down")}
    layers.update(ln1=ones(n, h), ln2=ones(n, h),
                  q_norm=ones(n, s.head_dim), k_norm=ones(n, s.head_dim))
    return {"embed": drawn["embed"], "layers": layers,
            "final_norm": ones(h), "head": drawn["head"]}


def _layer(shape: Shape, kernels: bool, x, p, positions):
    """One sequence through one layer: x [P, H] -> (x, rows computed by
    this chip's experts, rows of its fullest expert, rows of the dropless
    buffer the expert layer touched), P = 2L."""
    s = shape
    P = x.shape[0]
    h = rmsnorm(x, p["ln1"], s.eps)
    proj = lambda w: jnp.dot(h, w.astype(h.dtype))
    q = proj(p["wq"]).reshape(1, P, s.heads, s.head_dim)
    k = proj(p["wk"]).reshape(1, P, s.kv_heads, s.head_dim)
    v = proj(p["wv"]).reshape(1, P, s.kv_heads, s.head_dim)
    q = rope(rmsnorm(q, p["q_norm"], s.eps), positions, s.rope_theta)
    k = rope(rmsnorm(k, p["k_norm"], s.eps), positions, s.rope_theta)
    q = q * jnp.asarray(s.head_dim ** -0.5, q.dtype)
    heads_first = lambda a: a.transpose(0, 2, 1, 3)
    a = attention.blockdiff_attention(
        heads_first(q), heads_first(k), heads_first(v),
        seq_len=s.seq_len, block=s.block, kernels=kernels)
    a = heads_first(a).reshape(P, s.heads * s.head_dim)
    x = x + jnp.dot(a, p["wo"].astype(a.dtype))
    h = rmsnorm(x, p["ln2"], s.eps)
    y, rows, fullest = moe.expert_layer(
        h, p, held=s.held, num_experts=s.num_experts, top_k=s.top_k,
        kernels=kernels)
    return x + y, rows, fullest, moe.prefix_rows(rows, P * s.top_k, kernels)


def _remat_layer(shape: Shape, kernels: bool, p, positions):
    """`_layer` of one sequence under the layer's checkpoint: the backward
    pass recomputes all of it but the attention kernel's output and
    logsumexp, which it keeps (`attention.KEEP_ATTN_RESIDUALS`), so the
    forward kernel runs once."""
    return jax.checkpoint(
        lambda x: _layer(shape, kernels, x, p, positions),
        policy=attention.KEEP_ATTN_RESIDUALS)


def make(shape: Shape = Shape(), kernels=None):
    """(init_fn, apply_fn) for one share.  `kernels`: the Pallas attention
    and grouped-matmul kernels; None = wherever the default backend is a
    TPU (a deviceless compile for a described TPU passes True)."""
    s = shape
    if s.seq_len % s.block or s.heads % s.kv_heads:
        raise ValueError(f"sdar: seq_len {s.seq_len} / block {s.block}, "
                         f"heads {s.heads} / kv_heads {s.kv_heads}")

    def init_fn(key):
        return init_params(key, s), {}

    def resolved() -> bool:
        return jax.default_backend() == "tpu" if kernels is None else kernels

    def apply_fn(params, bn_state, x, train=True, compute_dtype=None):
        """x: (xt [S, L], x0 [S, L]) token ids.  Returns (hidden [S, L, H]
        of the noisy half after the final norm, {}, (rows computed by this
        chip's experts summed over layers, rows of the fullest held expert
        of any layer, rows of the dropless buffers touched for them))."""
        del train                       # no dropout, no batch statistics
        on_tpu = resolved()
        xt, x0 = x
        ids = jnp.concatenate([xt, x0], axis=1)
        h = params["embed"][ids]
        if compute_dtype is not None:
            h = h.astype(compute_dtype)
        positions = jnp.concatenate([jnp.arange(s.seq_len)] * 2)

        def layer(x, p):
            # One sequence at a time, each recomputed in the backward pass
            # but for its attention kernel's output and logsumexp: what a
            # layer keeps is its input and those two, and what it holds
            # while it works (q at 32 heads, the dropless buffer at top_k
            # times its input) is one sequence's, not the step's.
            x, rows, fullest, touched = lax.map(
                _remat_layer(s, on_tpu, p, positions), x)
            return x, (jnp.sum(rows), jnp.max(fullest), jnp.sum(touched))
        h, (rows, fullest, touched) = lax.scan(layer, h, params["layers"])
        hidden = rmsnorm(h[:, :s.seq_len], params["final_norm"], s.eps)
        return hidden, bn_state, (jnp.sum(rows), jnp.max(fullest),
                                  jnp.sum(touched))

    apply_fn.objective = BlockDiffusion(s, resolved)
    return init_fn, apply_fn


class BlockDiffusion:
    """The training objective of a block-diffusion decoder, as the compiled
    steps of train/step.py call it in the place of crop/flip +
    cross-entropy: the noising is drawn on the device from the step's key,
    the loss is the masked-diffusion cross-entropy on the noisy half.
    The methods and attributes are `train.step.ImageObjective`'s.

    `extras` names the per-step scalars a train step reports besides its
    loss (they ride in the metric ring's row) and how shards, and then the
    steps of an epoch, combine."""

    extras = (("moe_rows_local", "sum"), ("moe_rows_max_expert", "max"),
              ("tokens_masked", "sum"), ("moe_rows_touched", "sum"))
    eval_key = EVAL_KEY
    eval_dtypes = (jnp.float32, jnp.int32, jnp.int32)   # + masked tokens
    example_dtype = jnp.int32
    stream = True           # every epoch its own sequences (data/tokens.py)
    checks_vma = False      # train/step.py `_vary`

    def __init__(self, shape: Shape, kernels=lambda: False):
        self.shape, self.kernels = shape, kernels
        self.seq_len, self.vocab = shape.seq_len, shape.vocab
        self.example_shape = (shape.seq_len,)
        # constants a sequence, tallied beside the extras: the rows this
        # chip's experts get when routing is even
        self.per_example = {"moe_rows_expected": (
            2 * shape.seq_len * shape.top_k * len(shape.held)
            * shape.layers / shape.num_experts)}

    @functools.cached_property
    def gauges(self):
        """Whether the layers' checkpoint keeps the attention kernel's
        output (1: the backward pass runs no forward kernel; 0 on the
        jax.numpy path, which names nothing to keep), and what the kernels'
        tiles cost at this shape (the TPU's path; elsewhere the blocked
        formulation runs in their place): a recorder's, worked out when one
        asks."""
        return [("attn_residuals_kept", int(self.kernels()), {})] \
            + attention.kernel_tile_gauges(self.seq_len, self.shape.block)

    def prepare(self, key, tokens, augment=None, compute_dtype=None):
        s = self.shape
        xt, masked, weight = losslib.blockdiff_noise(
            key, tokens, s.block, s.mask_id)
        return {"xt": xt, "x0": tokens, "masked": masked, "weight": weight}

    def _counts(self, apply_fn, params, bn_state, x, compute_dtype):
        hidden, new_bn, routed = apply_fn(
            params, bn_state, (x["xt"], x["x0"]), train=True,
            compute_dtype=compute_dtype)
        per_seq = losslib.blockdiff_head_counts(
            hidden, params["head"], x["x0"], x["masked"], x["weight"])
        return per_seq, new_bn, routed

    def loss(self, apply_fn, params, bn_state, x, labels=None,
             compute_dtype=None):
        """-> (mean over the sequences of the per-sequence loss,
        (new_bn, extras)).  `labels`: the image path's, unused (a
        sequence's targets are its own tokens)."""
        (loss, _, count), new_bn, (rows, fullest, touched) = self._counts(
            apply_fn, params, bn_state, x, compute_dtype)
        extras = (rows.astype(jnp.float32), fullest.astype(jnp.float32),
                  jnp.sum(count).astype(jnp.float32),
                  touched.astype(jnp.float32))
        return jnp.mean(loss), (new_bn, extras)

    def eval_counts(self, apply_fn, params, bn_state, key, tokens, labels,
                    compute_dtype=None):
        """Forward only, the same loss: (sum of the valid sequences'
        losses, masked tokens predicted right, masked tokens); a padded
        row's label is -1."""
        valid = labels >= 0
        (loss, hit, count), _, _ = self._counts(
            apply_fn, params, bn_state, self.prepare(key, tokens),
            compute_dtype)
        return (jnp.sum(jnp.where(valid, loss, 0.0)),
                jnp.sum(jnp.where(valid, hit, 0)),
                jnp.sum(jnp.where(valid, count, 0)))
