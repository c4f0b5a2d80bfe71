"""One chip's share of a hybrid linear-attention mixture-of-experts decoder
(``model_type: qwen3_next``; Qwen3-Next-80B-A3B-Instruct's config.json).

Layers of TWO kinds, in the published pattern: layer i (0-based) is full
attention iff ``(i + 1) % full_attention_interval == 0``, else linear
attention.  Every layer, for a residual stream x [P, H]:

    x = x + mixer(norm(x; ln1))        x = x + moe(norm(x; ln2))
    norm(x; w) = x * rsqrt(mean(x^2) + eps) * (1 + w)        w starts at 0

then ``norm(x; final)`` and an untied head.  No bias anywhere.

  linear-attention mixer (Gated DeltaNet, ops/gdn.py)
    [q | k | v | z] = h W_qkvz      [b | a] = h W_ba  (one each a value head)
    [q | k | v] = silu(causal depthwise conv, 4 taps, of [q | k | v])
    beta = sigmoid(b)     g = -exp(A_log) * softplus(a + dt_bias)
    q, k L2-normalised over the head, each key head serving
    value_heads / key_heads consecutive value heads, q scaled by dk^-1/2
    o = the gated delta rule over the sequence, per head, float32
    y = (o * rsqrt(mean(o^2) + eps) * w_norm) * silu(z), per head; y W_out

  full-attention mixer (ops/attention.py `causal_attention`)
    [q | gate] = h W_q, per head D + D      k = h W_k      v = h W_v
    q, k normed over the head (zero-centred gains), rotary (rotate-half)
    on the first `rotary_dim` of the head and the identity on the rest
    o = softmax(q k^T / sqrt(D) + causal) v, grouped-query
    (o * sigmoid(gate)) W_o

  expert layer (ops/moe.py): softmax over ALL experts, the top k
    renormalised, this chip's held experts' part of the sum; plus the
    shared expert times its sigmoid gate, whole (`moe.shared_expert`).

What is held HERE is an argument of the factory (`Shape`: the layers, a
multiple of the interval, so whole periods in the published order; the
expert ids; the rows of the vocabulary), as for models/sdar.py, whose
`rmsnorm` and `rope` this model uses.  The parameters of the two kinds of
layer sit in two subtrees, stacked by period (and, the linear ones, by
their place in the period): the forward pass scans the periods, inside a
period the linear layers, then runs the period's full layer.  Inside a
layer the sequences go one at a time, each recomputed in the backward pass.
Training is next-token prediction (`NextToken`, the objective the compiled
steps call): nothing is drawn, every position but the last is predicted.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..ops import attention, gdn, loss as losslib, moe
from .sdar import rmsnorm, rope

EVAL_KEY = 0x93E7       # the objective's protocol; this one draws nothing


class Shape(NamedTuple):
    """Widths as published, and this chip's share."""
    hidden: int = 2048
    heads: int = 16
    kv_heads: int = 2
    head_dim: int = 256
    rotary_dim: int = 64                    # partial_rotary_factor 0.25
    lin_key_heads: int = 16
    lin_value_heads: int = 32
    lin_key_dim: int = 128
    lin_value_dim: int = 128
    conv_taps: int = 4
    expert_width: int = 512
    shared_width: int = 512
    num_experts: int = 512
    top_k: int = 10
    rope_theta: float = 1e7
    eps: float = 1e-6
    interval: int = 4                       # full_attention_interval
    layers: int = 4                         # of 48: one period
    held: Tuple[int, ...] = tuple(range(32))    # of 512: 16 chips a layer
    vocab: int = 18992                      # of 151,936: an eighth
    seq_len: int = 8192


TINY = Shape(hidden=64, heads=4, kv_heads=2, head_dim=16, rotary_dim=4,
             lin_key_heads=2, lin_value_heads=4, lin_key_dim=8,
             lin_value_dim=8, expert_width=32, shared_width=32,
             num_experts=8, top_k=2, layers=4, held=(0, 1), vocab=64,
             seq_len=32)

def init_params(key, shape: Shape):
    """Normal, std 0.02, every matrix (the router, the shared expert's gate
    and the convolution's taps too); zero-centred norm gains 0, the gated
    norm's plain gain 1; A_log = log(U(0.001, 16)), dt_bias 1; the
    embedding's rows std 1 (a position's route follows its token, as for
    models/sdar.py).  One key per drawn leaf, in this order: embed, the
    linear layers' (w_qkvz, w_ba, conv, w_out, A_log, then the expert
    layer's eight), the full layers' (wq, wk, wv, wo, the eight), head."""
    s = shape
    n, lin = s.layers // s.interval, s.interval - 1
    h, f, fs, g = s.hidden, s.expert_width, s.shared_width, len(s.held)
    kd, vd = s.lin_key_heads * s.lin_key_dim, s.lin_value_heads * s.lin_value_dim
    q, kv = s.heads * s.head_dim, s.kv_heads * s.head_dim

    def moe_shapes(lead):
        return [("router", lead + (h, s.num_experts)),
                ("w_gate", lead + (g, h, f)), ("w_up", lead + (g, h, f)),
                ("w_down", lead + (g, f, h)), ("shared_gate", lead + (h, fs)),
                ("shared_up", lead + (h, fs)), ("shared_down", lead + (fs, h)),
                ("shared_sig", lead + (h, 1))]
    L, F = (n, lin), (n,)
    linear = [("w_qkvz", L + (h, 2 * kd + 2 * vd)),
              ("w_ba", L + (h, 2 * s.lin_value_heads)),
              ("conv", L + (s.conv_taps, 2 * kd + vd)), ("w_out", L + (vd, h)),
              ("A_log", L + (s.lin_value_heads,))] + moe_shapes(L)
    full = [("wq", F + (h, 2 * q)), ("wk", F + (h, kv)), ("wv", F + (h, kv)),
            ("wo", F + (q, h))] + moe_shapes(F)
    drawn = ([("embed", None, (s.vocab, h))]
             + [("linear", name, shp) for name, shp in linear]
             + [("full", name, shp) for name, shp in full]
             + [("head", None, (h, s.vocab))])
    keys = jax.random.split(key, len(drawn))
    out = {"linear": {}, "full": {}}
    for k, (group, name, shp) in zip(keys, drawn):
        if name == "A_log":
            leaf = jnp.log(jax.random.uniform(k, shp, jnp.float32, 1e-3, 16.0))
        else:
            std = 1.0 if group == "embed" else 0.02
            leaf = std * jax.random.normal(k, shp, jnp.float32)
        if name is None:
            out[group] = leaf
        else:
            out[group][name] = leaf
    zeros = lambda *shp: jnp.zeros(shp, jnp.float32)
    ones = lambda *shp: jnp.ones(shp, jnp.float32)
    out["linear"].update(ln1=zeros(*L, h), ln2=zeros(*L, h),
                         dt_bias=ones(*L, s.lin_value_heads),
                         gdn_norm=ones(*L, s.lin_value_dim))
    out["full"].update(ln1=zeros(*F, h), ln2=zeros(*F, h),
                       q_norm=zeros(*F, s.head_dim),
                       k_norm=zeros(*F, s.head_dim))
    return {"embed": out["embed"],
            "periods": {"linear": out["linear"], "full": out["full"]},
            "final_norm": zeros(h), "head": out["head"]}


def _norm(x, gain, eps):
    return rmsnorm(x, 1.0 + gain, eps)


def _gdn_mixer(shape: Shape, kernels: bool, h, p):
    """h [P, H] (after ln1) -> the linear-attention mixer's output [P, H]
    (ops/gdn.py `mixer`, beta in (0, 1))."""
    s = shape
    return gdn.mixer(h, p["w_qkvz"], p["w_ba"], p,
                     key_heads=s.lin_key_heads, value_heads=s.lin_value_heads,
                     dk=s.lin_key_dim, dv=s.lin_value_dim, eps=s.eps,
                     kernels=kernels)[0]


def _full_mixer(shape: Shape, kernels: bool, h, p, positions):
    """h [P, H] (after ln1) -> the gated softmax-attention output [P, H]."""
    s = shape
    P, D = h.shape[0], s.head_dim
    proj = lambda w: jnp.dot(h, w.astype(h.dtype))
    qg = proj(p["wq"]).reshape(1, P, s.heads, 2 * D)
    q, gate = qg[..., :D], qg[..., D:]
    k = proj(p["wk"]).reshape(1, P, s.kv_heads, D)
    v = proj(p["wv"]).reshape(1, P, s.kv_heads, D)

    def rotary(x):
        r = s.rotary_dim
        return jnp.concatenate(
            [rope(x[..., :r], positions, s.rope_theta), x[..., r:]], -1)
    q = rotary(_norm(q, p["q_norm"], s.eps)) * jnp.asarray(D ** -0.5, q.dtype)
    k = rotary(_norm(k, p["k_norm"], s.eps))
    heads_first = lambda a: a.transpose(0, 2, 1, 3)
    a = attention.causal_attention(heads_first(q), heads_first(k),
                                   heads_first(v), kernels=kernels)
    a = heads_first(a) * jax.nn.sigmoid(gate)
    return jnp.dot(a.reshape(P, s.heads * D), p["wo"].astype(a.dtype))


def _layer(shape: Shape, kernels: bool, mixer, x, p):
    """One sequence through one layer of either kind: x [P, H] -> (x, rows
    computed by this chip's experts, rows of its fullest expert, rows of
    the dropless buffer the expert layer touched)."""
    s = shape
    x = x + mixer(_norm(x, p["ln1"], s.eps), p)
    h = _norm(x, p["ln2"], s.eps)
    y, rows, fullest = moe.expert_layer(
        h, p, held=s.held, num_experts=s.num_experts, top_k=s.top_k,
        kernels=kernels)
    x = x + y + moe.shared_expert(h, p)
    return x, rows, fullest, moe.prefix_rows(
        rows, x.shape[0] * s.top_k, kernels)


def make(shape: Shape = Shape(), kernels=None):
    """(init_fn, apply_fn) for one share.  `kernels`: the Pallas attention,
    grouped-matmul and delta-rule kernels; None = wherever the default
    backend is a TPU (a deviceless compile for a described TPU passes
    True)."""
    s = shape
    if s.layers % s.interval or s.heads % s.kv_heads \
            or s.lin_value_heads % s.lin_key_heads:
        raise ValueError(
            f"qwen3next: layers {s.layers} / interval {s.interval} (whole "
            f"periods), heads {s.heads} / kv_heads {s.kv_heads}, linear "
            f"value heads {s.lin_value_heads} / key heads {s.lin_key_heads}")

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
        full = lambda h, p: _full_mixer(s, on_tpu, h, p, positions)

        def layer(mixer):
            def run(x, p):
                # one sequence at a time, each recomputed in the backward
                # pass (models/sdar.py `layer`).  ONE checkpoint a layer:
                # one each for the mixer and the expert layer keeps a
                # second [S, P, H] a layer and compiled to 1.0-1.6 GiB more
                x, rows, fullest, touched = lax.map(jax.checkpoint(
                    lambda x_seq: _layer(s, on_tpu, mixer, x_seq, p)), x)
                return x, jnp.stack([jnp.sum(rows), jnp.max(fullest),
                                     jnp.sum(touched)])
            return run

        def period(x, p):
            x, lin = lax.scan(layer(functools.partial(_gdn_mixer, s, on_tpu)),
                              x, p["linear"])
            x, last = layer(full)(x, p["full"])
            return x, jnp.concatenate([lin, last[None]])
        h, counts = lax.scan(period, h, params["periods"])
        counts = counts.reshape(-1, 3)          # a row a layer
        hidden = _norm(h, params["final_norm"], s.eps)
        return hidden, bn_state, (jnp.sum(counts[:, 0]), jnp.max(counts[:, 1]),
                                  jnp.sum(counts[:, 2]))

    apply_fn.objective = NextToken(s, resolved)
    return init_fn, apply_fn


class NextToken:
    """Next-token prediction, as the compiled steps of train/step.py call
    it (the methods and attributes are `train.step.ImageObjective`'s; the
    extras are models/sdar.py `BlockDiffusion`'s with the tokens predicted
    in the masked tokens' place)."""

    extras = (("moe_rows_local", "sum"), ("moe_rows_max_expert", "max"),
              ("tokens_predicted", "sum"), ("moe_rows_touched", "sum"))
    eval_key = EVAL_KEY
    eval_dtypes = (jnp.float32, jnp.int32, jnp.int32)   # + tokens predicted
    example_dtype = jnp.int32
    stream = True           # every epoch its own sequences (data/tokens.py)
    checks_vma = False      # train/step.py `_vary`

    def __init__(self, shape: Shape, kernels=lambda: False):
        self.shape, self.kernels = shape, kernels
        self.seq_len, self.vocab = shape.seq_len, shape.vocab
        self.example_shape = (shape.seq_len,)
        self.per_example = {"moe_rows_expected": (
            shape.seq_len * shape.top_k * len(shape.held) * shape.layers
            / shape.num_experts)}

    @functools.cached_property
    def gauges(self):
        """Which form of the recurrence runs (1 the Pallas kernels, 0 the
        jax.numpy one) and its chunking; which form of the mixer's front
        (the same, for a float32 projection: what `apply_fn` computes
        unless told a compute dtype); the diagonal block the chunks'
        inversion starts from (both forms'); what the causal kernels' tiles
        cost at this length (the TPU's path): a recorder's, once."""
        s = self.shape
        fused, c = gdn.plan(self.seq_len, s.lin_key_dim, s.lin_value_dim,
                            self.kernels())
        front = gdn.front_plan(self.seq_len, s.lin_key_dim,
                               s.lin_value_heads * s.lin_value_dim,
                               jnp.float32, self.kernels())
        return [("gdn_kernel", int(fused), {}), ("gdn_chunk", c, {}),
                ("gdn_chunks_per_sequence", -(-self.seq_len // c), {}),
                ("gdn_front_kernel", int(front > 0), {}),
                ("gdn_inverse_block", gdn.INVERSE_BLOCK, {})] \
            + attention.causal_tile_gauges(self.seq_len)

    def prepare(self, key, tokens, augment=None, compute_dtype=None):
        return tokens                   # nothing is drawn

    def _counts(self, apply_fn, params, bn_state, tokens, compute_dtype):
        hidden, new_bn, routed = apply_fn(params, bn_state, tokens,
                                          train=True,
                                          compute_dtype=compute_dtype)
        return losslib.next_token_head_counts(
            hidden, params["head"], tokens), new_bn, routed

    def loss(self, apply_fn, params, bn_state, x, labels=None,
             compute_dtype=None):
        """-> (mean over the sequences of the per-sequence loss,
        (new_bn, extras))."""
        (loss, _, count), new_bn, (rows, fullest, touched) = self._counts(
            apply_fn, params, bn_state, x, compute_dtype)
        extras = (rows.astype(jnp.float32), fullest.astype(jnp.float32),
                  jnp.sum(count).astype(jnp.float32),
                  touched.astype(jnp.float32))
        return jnp.mean(loss), (new_bn, extras)

    def eval_counts(self, apply_fn, params, bn_state, key, tokens, labels,
                    compute_dtype=None):
        """Forward only, the same loss: (sum of the valid sequences'
        losses, tokens predicted right, tokens predicted); a padded row's
        label is -1."""
        valid = labels >= 0
        (loss, hit, count), _, _ = self._counts(
            apply_fn, params, bn_state, tokens, compute_dtype)
        return (jnp.sum(jnp.where(valid, loss, 0.0)),
                jnp.sum(jnp.where(valid, hit, 0)),
                jnp.sum(jnp.where(valid, count, 0)))
