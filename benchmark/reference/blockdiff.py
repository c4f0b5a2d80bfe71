"""The plain reference of a block-diffusion mixture-of-experts decoder
(`sdar_moe`) and of its training objective: the equations of ISSUE 29 in
straightforward jax.numpy, float32 at the backend's default matmul
precision (what the configuration states, the benchmark's practice: PERF.md
section 2), a dense [2L, 2L] mask built from the block ids, the experts as
a loop over the held ones with a 0/1 selection, loss and gradients by
autodiff.  It imports nothing of the program and takes nothing the program
made: weights, noising and evaluation draws come from the seeds by the
configuration's own recipe, written out here.

Departures from the published description, each for memory and none for
the mathematics: a sequence at a time and, inside attention, a block of
query rows at a time (a sequence's [32, 8192, 8192] scores would be 8.6
GB), each layer and each block of rows recomputed in the backward pass
(`jax.checkpoint`); what the experts held elsewhere would add is left out,
as the configuration's share says.

Planted faults, for the tests and the chip study only (`follow`'s last
arguments): `causal_mask` (a plain causal mask over the 2L positions in
place of the block-diffusion mask), `drop_rows` (an expert takes at most
capacity 1.0 = positions * top_k / num_experts rows of a sequence, in
position order, and the rest are dropped), `drop_half` (half of every
step's sequences left out), `freeze` (a step that leaves its state as it
was).  `dtype` is the lower-precision control: the WHOLE computation
in that type (`bfloat16`: weights, activations, gradients and the
optimizer's state), which the configuration's float32 must be told from.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

ROW_BLOCK = 256         # query rows worked on at a time


def sizes(config: dict) -> dict:
    """The configuration's widths and share under short names."""
    return dict(
        H=config["hidden_size"], HQ=config["num_attention_heads"],
        HKV=config["num_key_value_heads"], D=config["head_dim"],
        F=config["moe_intermediate_size"], K=config["num_experts_per_tok"],
        E=config["published"]["num_experts"], held=config["experts_held"],
        NL=config["num_hidden_layers"], V=config["vocab_size"],
        L=config["seq_len"], B=config["block_length"],
        theta=float(config["rope_theta"]), eps=config["rms_norm_eps"])


def init(config: dict, key):
    """The configuration's init (`assumed.init`): normal, std 0.02, every
    matrix, the router too; norm gains 1; the embedding's rows std 1.  One
    key per drawn leaf, split in this order."""
    z = sizes(config)
    n, h, f, g = z["NL"], z["H"], z["F"], len(z["held"])
    q, kv = z["HQ"] * z["D"], z["HKV"] * z["D"]
    drawn = [("embed", (z["V"], h), 1.0),
             ("wq", (n, h, q), 0.02), ("wk", (n, h, kv), 0.02),
             ("wv", (n, h, kv), 0.02), ("wo", (n, q, h), 0.02),
             ("router", (n, h, z["E"]), 0.02),
             ("w_gate", (n, g, h, f), 0.02), ("w_up", (n, g, h, f), 0.02),
             ("w_down", (n, g, f, h), 0.02), ("head", (h, z["V"]), 0.02)]
    keys = jax.random.split(key, len(drawn))
    w = {name: std * jax.random.normal(k, shape, jnp.float32)
         for k, (name, shape, std) in zip(keys, drawn)}
    layers = {name: w[name] for name in ("wq", "wk", "wv", "wo", "router",
                                         "w_gate", "w_up", "w_down")}
    layers["ln1"] = jnp.ones((n, h), jnp.float32)
    layers["ln2"] = jnp.ones((n, h), jnp.float32)
    layers["q_norm"] = jnp.ones((n, z["D"]), jnp.float32)
    layers["k_norm"] = jnp.ones((n, z["D"]), jnp.float32)
    return {"embed": w["embed"], "layers": layers,
            "final_norm": jnp.ones((h,), jnp.float32), "head": w["head"]}


def dense_mask(L: int, B: int, causal: bool = False):
    """[2L, 2L] bool: may query i see key j.  Positions [xt ; x0]."""
    i = np.arange(2 * L)
    if causal:                                      # the planted fault
        return jnp.asarray(i[None, :] <= i[:, None])
    noisy = i < L
    blk = (i % L) // B
    qn, kn = noisy[:, None], noisy[None, :]
    bq, bk = blk[:, None], blk[None, :]
    return jnp.asarray((qn & kn & (bq == bk)) | (qn & ~kn & (bk < bq))
                       | (~qn & ~kn & (bk <= bq)))


def rmsnorm(x, gain, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * gain


def rope(x, pos, theta):
    """x [P, heads, D]; rotate-half over the whole head."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos.astype(x.dtype) + rot * sin.astype(x.dtype)


def attention(q, k, v, mask, z):
    """q [P, HQ, D], k, v [P, HKV, D], mask [P, P] -> [P, HQ*D]."""
    P = q.shape[0]
    group = z["HQ"] // z["HKV"]
    rows = min(ROW_BLOCK, P)

    @jax.checkpoint
    def block(start):
        qb = lax.dynamic_slice_in_dim(q, start, rows, 0)
        mb = lax.dynamic_slice_in_dim(mask, start, rows, 0)
        kk = jnp.repeat(k, group, axis=1)               # [P, HQ, D]
        vv = jnp.repeat(v, group, axis=1)
        s = jnp.einsum("qhd,khd->hqk", qb, kk) / math.sqrt(z["D"])
        s = jnp.where(mb[None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), vv)
    out = lax.map(block, jnp.arange(0, P, rows))
    return out.reshape(P, z["HQ"] * z["D"])


def experts(h, p, z, drop_rows: bool):
    """h [P, H] -> this share's part of the expert layer's output."""
    probs = jax.nn.softmax(h @ p["router"], axis=-1)        # all E experts
    top_p, top_e = lax.top_k(probs, z["K"])
    w = top_p / jnp.sum(top_p, -1, keepdims=True)
    out = jnp.zeros_like(h)
    capacity = math.ceil(h.shape[0] * z["K"] / z["E"])
    for g, e in enumerate(z["held"]):
        chosen = top_e == e                                 # [P, K]
        weight = jnp.sum(jnp.where(chosen, w, 0.0), -1)     # 0 if not chosen
        if drop_rows:                                       # planted fault
            taken = jnp.any(chosen, -1)
            weight = jnp.where(jnp.cumsum(taken) <= capacity, weight, 0.0)
        y = (jax.nn.silu(h @ p["w_gate"][g]) * (h @ p["w_up"][g])) \
            @ p["w_down"][g]
        out = out + weight[:, None] * y
    return out


def hidden_states(params, xt, x0, z, mask, drop_rows=False):
    """One sequence: ids [L] + [L] -> the noisy half's final states."""
    L = z["L"]
    x = params["embed"][jnp.concatenate([xt, x0])]          # [2L, H]
    pos = jnp.concatenate([jnp.arange(L), jnp.arange(L)])

    @jax.checkpoint
    def layer(x, p):
        h = rmsnorm(x, p["ln1"], z["eps"])
        P = h.shape[0]
        q = (h @ p["wq"]).reshape(P, z["HQ"], z["D"])
        k = (h @ p["wk"]).reshape(P, z["HKV"], z["D"])
        v = (h @ p["wv"]).reshape(P, z["HKV"], z["D"])
        q = rope(rmsnorm(q, p["q_norm"], z["eps"]), pos, z["theta"])
        k = rope(rmsnorm(k, p["k_norm"], z["eps"]), pos, z["theta"])
        x = x + attention(q, k, v, mask, z) @ p["wo"]
        h = rmsnorm(x, p["ln2"], z["eps"])
        return x + experts(h, p, z, drop_rows)
    for i in range(z["NL"]):
        x = layer(x, jax.tree.map(lambda a: a[i], params["layers"]))
    return rmsnorm(x[:L], params["final_norm"], z["eps"])


def noise(key, tokens, B, mask_id):
    """The noising of a step's sequences [S, L] (`assumed.objective`): per
    block t ~ U[1/B, 1], each token masked with probability t, at least one
    a block (the first).  -> (xt, masked, 1/t per position)."""
    S, L = tokens.shape
    kt, km = jax.random.split(key)
    t = 1.0 / B + (1.0 - 1.0 / B) * jax.random.uniform(
        kt, (S, L // B), jnp.float32)
    u = jax.random.uniform(km, (S, L // B, B), jnp.float32)
    masked = u < t[..., None]
    masked = masked.at[..., 0].set(masked[..., 0] | ~jnp.any(masked, -1))
    masked = masked.reshape(S, L)
    return (jnp.where(masked, mask_id, tokens), masked,
            jnp.repeat(1.0 / t, B, axis=-1))


def sequence_loss(params, xt, x0, masked, weight, z, mask, drop_rows=False):
    """(loss of one sequence, masked tokens predicted right)."""
    hidden = hidden_states(params, xt, x0, z, mask, drop_rows)
    logits = hidden @ params["head"]                        # [L, V]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, x0[:, None], axis=-1)[:, 0]
    loss = jnp.sum(jnp.where(masked, weight.astype(nll.dtype) * nll, 0.0)) \
        / z["L"]
    hit = jnp.sum(masked & (jnp.argmax(logits, -1) == x0))
    return loss, hit


def tree_norms(tree):
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(k): float(jnp.sqrt(jnp.sum(jnp.square(
        jnp.asarray(v, jnp.float32))))) for k, v in flat}


def follow(config, *, seed, weights_seed, world, per_chip_batch, train,
           heldout, steps=3, drop_half=False, freeze=False,
           causal_mask=False, drop_rows=False, dtype="float32"):
    """Train `steps` steps from the seeds and evaluate; return every number
    benchmark/correct.py reads.  `train` [N, L]: the stream's first
    sequences, step k taking rows [k*world*b, (k+1)*world*b), shard r its
    b = per_chip_batch rows of those; `heldout` [M, L]."""
    z = sizes(config)
    opt = config["optimizer"]
    lr, mu, wd = config["lr"], opt["momentum"], opt["weight_decay"]
    mask_id = z["V"] - 1
    mask = dense_mask(z["L"], z["B"], causal=causal_mask)
    params = jax.tree.map(lambda a: a.astype(dtype),
                          init(config, jax.random.PRNGKey(weights_seed)))
    vel = jax.tree.map(jnp.zeros_like, params)
    # Weights, velocity and two gradients are 10 GB at the real sizes: the
    # initial weights wait on the host, and the updates reuse their buffers.
    p0 = jax.device_get(params)
    key_epoch = jax.random.fold_in(jax.random.PRNGKey(seed), 0)

    # the mask is an argument, not a constant of the compiled program: at
    # the real sizes it is 67 MB, and the planted causal mask reuses the
    # executable
    @jax.jit
    def seq_grad(params, mask, xt, x0, masked, weight):
        return jax.value_and_grad(
            lambda p: sequence_loss(p, xt, x0, masked, weight, z, mask,
                                    drop_rows)[0])(params)

    def sgd(params, vel, g):
        d = jax.tree.map(lambda p, gg: gg + wd * p, params, g)
        vel = jax.tree.map(lambda v, dd: mu * v + dd, vel, d)
        return jax.tree.map(lambda p, v: p - lr * v, params, vel), vel
    sgd = jax.jit(sgd, donate_argnums=(0, 1))
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b),
                  donate_argnums=(0,))
    scale = jax.jit(lambda a, c: jax.tree.map(lambda x: x * c, a),
                    donate_argnums=(0,))
    out = {"loss": [], "grad_norms": []}
    b = per_chip_batch
    for k in range(steps):
        key_step = jax.random.fold_in(key_epoch, k)
        losses, g = [], None
        for r in range(world):
            rows = jnp.asarray(train[(k * world + r) * b:
                                     (k * world + r + 1) * b])
            xt, masked, weight = noise(jax.random.fold_in(key_step, r),
                                       rows, z["B"], mask_id)
            use = range(b // 2) if drop_half else range(b)
            total, g_sum = 0.0, None
            for s in use:
                loss, gs = seq_grad(params, mask, xt[s], rows[s], masked[s],
                                    weight[s])
                total += float(loss)
                g_sum = gs if g_sum is None else add(g_sum, gs)
                del gs
            losses.append(total / len(use))
            g_sum = scale(g_sum, 1.0 / (len(use) * world))
            g = g_sum if g is None else add(g, g_sum)   # mean of the shards
            del g_sum
        out["loss"].append(sum(losses) / len(losses))
        out["grad_norms"].append(tree_norms(g))
        if not freeze:
            params, vel = sgd(params, vel, g)
        if k == 0:
            out["momentum1_norms"] = tree_norms(vel)
        del g
    out["dparam_norms"] = tree_norms(jax.tree.map(
        lambda a, c: a - jnp.asarray(c), params, p0))

    @jax.jit
    def seq_eval(params, mask, xt, x0, masked, weight):
        return sequence_loss(params, xt, x0, masked, weight, z, mask,
                             drop_rows)

    gb = b * world
    loss_sum, correct = 0.0, 0
    for t in range(0, len(heldout), gb):
        key_t = jax.random.fold_in(jax.random.PRNGKey(config["eval_key"]),
                                   t // gb)
        for r in range(world):
            rows = jnp.asarray(heldout[t + r * b:t + (r + 1) * b])
            if not len(rows):
                continue
            # the program pads its last batch to the compiled shape and
            # draws for the padded rows too: draw for b rows, use the real
            pad = jnp.zeros((b - len(rows), z["L"]), rows.dtype)
            xt, masked, weight = noise(jax.random.fold_in(key_t, r),
                                       jnp.concatenate([rows, pad]),
                                       z["B"], mask_id)
            for s in range(len(rows)):
                l, c = seq_eval(params, mask, xt[s], rows[s], masked[s],
                                weight[s])
                loss_sum += float(l)
                correct += int(c)
    out["eval_loss"] = loss_sum / len(heldout)
    out["eval_correct"] = correct
    out["eval_n"] = len(heldout)
    return out
