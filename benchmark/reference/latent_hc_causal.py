"""The plain reference of a latent-attention mixture-of-experts decoder with
a hyper-connected residual (`xing4_0`) trained on next-token prediction: the
equations of ISSUE 35 in straightforward jax.numpy, float32 at the
backend's default matmul precision (what the configuration states, and the
practice of the benchmark's other references; PERF.md section 2 has what
`"highest"`, which ISSUE 35 asked for, read on the chip and why it was not
kept), the hyper-connections' narrow product at the highest (they are
float32 throughout), loss and gradients by autodiff.  It imports nothing of the program and takes nothing the program
made: the weights come from the weights' seed by the configuration's own
recipe, written out here.

Per position, C = hidden_size, n = hc_mult, X in R^{n x C}:

    X_0[j] = E[id]                          every stream starts as the embedding
    around a sublayer F (MLA, then the feed-forward; each its own Phi, b, a):
      u = vec(X);  m = (u / sqrt(mean(u^2) + hc_eps)) Phi
      H_pre = sigmoid(a_pre m[:n] + b_pre);  H_post = 2 sigmoid(a_post m[n:2n] + b_post)
      H_res = sinkhorn(exp(clip(a_res m[2n:] + b_res, lo, hi)))     20 iterations of
              every column over its sum, then every row over its sum (a plain loop)
      y = F(rmsnorm(sum_j H_pre[j] X[j]; g));  X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y
    logits = rmsnorm(sum_j X[j]; g_f) W_head

MLA: c_q = rmsnorm(h W_qa); [q_nope | q_pe] = c_q W_qb a head; [c_kv | k_pe] =
h W_kva; [k_nope | v] = rmsnorm(c_kv) W_kvb a head; rotary (YaRN's
frequencies) on q_pe and on the ONE k_pe that every head uses; softmax over
the causal scores times 192^-0.5 mscale^2; W_o.  Attention is computed a
HEAD at a time on explicit blocks of `ROW_BLOCK` query rows against all the
keys, where the program hands all heads to a kernel.  The first
`first_k_dense_replace` layers' feed-forward is a SwiGLU; the others' is
sigmoid scores over ALL experts, the top k of s + the selection bias, w =
routed_scaling_factor s / (sum s + 1e-20) over the chosen, a LOOP over the
expert ids held here, plus the shared expert whole and ungated.

Departures, each for memory and none for the mathematics: a sequence at a
time; attention in blocks; each layer recomputed in the backward pass
(`jax.checkpoint`); what the experts held elsewhere would add is left out,
as the configuration's share says.

Planted faults, for the tests and the chip study only (`follow`'s last
arguments; each a run-time select between the sound value and the faulty
one, so that ONE compiled program serves the sound run and every fault: a
compile of the per-sequence gradient at the real sizes takes minutes): `sinkhorn_1` (one iteration), `no_res_mix` (H_res = I),
`rope_on_all` (rotary over all 192 of q and k), `no_yarn_scale` (scale
192^-0.5), `softmax_route` (softmax scores), `no_route_scale` (x 1), and
`drop_half`, `freeze` as the other references' (`drop_half`: half of every
step's sequences left out; where a step has ONE sequence, the second half
of its predicted positions).  `dtype` is the lower-precision control: the
WHOLE computation in that type.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

ROW_BLOCK = 256         # query rows worked on at a time
FAULTS = ("sinkhorn_1", "no_res_mix", "rope_on_all", "no_yarn_scale",
          "softmax_route", "no_route_scale")


def sizes(config: dict) -> dict:
    """The configuration's widths and share under short names."""
    ys = config["rope_scaling"]
    return dict(
        C=config["hidden_size"], H=config["num_attention_heads"],
        NOPE=config["qk_nope_head_dim"], ROPE=config["qk_rope_head_dim"],
        DV=config["v_head_dim"], RQ=config["q_lora_rank"],
        RKV=config["kv_lora_rank"], I=config["intermediate_size"],
        F=config["moe_intermediate_size"],
        FS=config["moe_intermediate_size"] * config["n_shared_experts"],
        K=config["num_experts_per_tok"],
        E=config["published"]["n_routed_experts"],
        held=config["experts_held"], NL=config["num_hidden_layers"],
        ND=config["first_k_dense_replace"], V=config["vocab_size"],
        L=config["seq_len"], N=config["hc_mult"],
        iters=config["hc_sinkhorn_iters"], hc_eps=config["hc_eps"],
        clamp=(config["mhc_h_res_clamp_min"], config["mhc_h_res_clamp_max"]),
        route_scale=float(config["routed_scaling_factor"]),
        theta=float(config["rope_theta"]), eps=config["rms_norm_eps"],
        factor=float(ys["factor"]),
        original=ys["original_max_position_embeddings"],
        beta_fast=ys["beta_fast"], beta_slow=ys["beta_slow"],
        mscale=ys["mscale"], mscale_all_dim=ys["mscale_all_dim"],
        hc_init=config["hc_init"])


# -- the configuration's init ------------------------------------------------

def init(config: dict, key):
    """The configuration's init (`assumed.init`, `hc_init`).  One key per
    drawn leaf, split in the order the file states."""
    z = sizes(config)
    c, n, g = z["C"], z["N"], len(z["held"])
    nd, ns = z["ND"], z["NL"] - z["ND"]
    mix = n * n + 2 * n

    def both(lead):
        return [("w_qa", lead + (c, z["RQ"])),
                ("w_qb", lead + (z["RQ"], z["H"] * (z["NOPE"] + z["ROPE"]))),
                ("w_kva", lead + (c, z["RKV"] + z["ROPE"])),
                ("w_kvb", lead + (z["RKV"], z["H"] * (z["NOPE"] + z["DV"]))),
                ("w_o", lead + (z["H"] * z["DV"], c)),
                ("hc_attn", lead + (n * c, mix)),
                ("hc_mlp", lead + (n * c, mix))]
    drawn = [("embed", None, (z["V"], c))]
    drawn += [("dense", name, shape) for name, shape in both((nd,)) + [
        ("mlp_gate", (nd, c, z["I"])), ("mlp_up", (nd, c, z["I"])),
        ("mlp_down", (nd, z["I"], c))]]
    drawn += [("sparse", name, shape) for name, shape in both((ns,)) + [
        ("router", (ns, c, z["E"])), ("router_bias", (ns, z["E"])),
        ("w_gate", (ns, g, c, z["F"])), ("w_up", (ns, g, c, z["F"])),
        ("w_down", (ns, g, z["F"], c)), ("shared_gate", (ns, c, z["FS"])),
        ("shared_up", (ns, c, z["FS"])), ("shared_down", (ns, z["FS"], c))]]
    drawn += [("head", None, (c, z["V"]))]
    hi = z["hc_init"]
    i = np.arange(n)
    b_res = 2.0 * np.eye(n) + (i[None, :] - i[:, None])     # hc_init.b_res
    bias = jnp.asarray(np.concatenate(
        [hi["b_pre"], hi["b_post"], b_res.reshape(-1)]), jnp.float32)
    w = {"dense": {}, "sparse": {}}
    for k, (group, name, shape) in zip(jax.random.split(key, len(drawn)),
                                       drawn):
        std = 1.0 if group == "embed" else \
            hi["router_bias_std"] if name == "router_bias" else \
            hi["phi_std"] if (name or "").startswith("hc_") else 0.02
        leaf = std * jax.random.normal(k, shape, jnp.float32)
        if name is None:
            w[group] = leaf
        elif name.startswith("hc_"):
            w[group][name] = {
                "phi": leaf,
                "alpha": jnp.full((shape[0], 3), hi["alpha"], jnp.float32),
                "bias": jnp.tile(bias, (shape[0], 1))}
        else:
            w[group][name] = leaf
    ones = lambda *s: jnp.ones(s, jnp.float32)
    for group, lead in (("dense", nd), ("sparse", ns)):
        w[group].update(ln1=ones(lead, c), ln2=ones(lead, c),
                        q_norm=ones(lead, z["RQ"]),
                        kv_norm=ones(lead, z["RKV"]))
    return {"embed": w["embed"], "dense": w["dense"], "sparse": w["sparse"],
            "final_norm": ones(c), "head": w["head"]}


# -- the layers --------------------------------------------------------------

def rmsnorm(x, g, eps):
    return g * x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)


def yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_frequencies(d, z):
    """The d / 2 rotary frequencies by hand: f_i = base^(-2i/d); dim(r) =
    d ln(original / (2 pi r)) / (2 ln base); low = floor(dim(beta_fast)),
    high = ceil(dim(beta_slow)), clamped to [0, d - 1]; ramp_i = clip((i -
    low) / (high - low), 0, 1); (f_i / factor) ramp_i + f_i (1 - ramp_i)."""
    dim = lambda r: d * math.log(z["original"] / (2 * math.pi * r)) \
        / (2 * math.log(z["theta"]))
    low = max(math.floor(dim(z["beta_fast"])), 0)
    high = min(math.ceil(dim(z["beta_slow"])), d - 1)
    out = []
    for i in range(d // 2):
        f = z["theta"] ** (-2.0 * i / d)
        ramp = min(max((i - low) / ((high - low) or 0.001), 0.0), 1.0)
        out.append(f / z["factor"] * ramp + f * (1.0 - ramp))
    return np.asarray(out, np.float32)


def rotate(x, pos, freq, factor):
    """x [P, heads, d]: the pair (i, i + d/2) turned by pos x freq_i."""
    d = x.shape[-1]
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(freq)[None, :]
    cos = (jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)
           * factor)[:, None, :].astype(x.dtype)
    sin = (jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)
           * factor)[:, None, :].astype(x.dtype)
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + rot * sin


def latent_attention(h, p, z, pos, rope_on_all=False, no_yarn_scale=False):
    """MLA: h [P, C] -> [P, C], a head at a time."""
    P, H, nope, rope, dv = h.shape[0], z["H"], z["NOPE"], z["ROPE"], z["DV"]
    c_q = rmsnorm(h @ p["w_qa"], p["q_norm"], z["eps"])
    q = (c_q @ p["w_qb"]).reshape(P, H, nope + rope)
    kva = h @ p["w_kva"]
    c_kv, k_pe = kva[:, :z["RKV"]], kva[:, z["RKV"]:]
    kv = (rmsnorm(c_kv, p["kv_norm"], z["eps"]) @ p["w_kvb"]).reshape(
        P, H, nope + dv)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.repeat(k_pe[:, None, :], H, axis=1)], -1)
    v = kv[..., nope:]
    factor = yarn_mscale(z["factor"], z["mscale"]) \
        / yarn_mscale(z["factor"], z["mscale_all_dim"])
    part = yarn_frequencies(rope, z)
    turn = lambda a: jnp.concatenate(
        [a[..., :nope], rotate(a[..., nope:], pos, part, factor)], -1)
    every = yarn_frequencies(nope + rope, z)            # planted fault
    q = jnp.where(rope_on_all, rotate(q, pos, every, factor), turn(q))
    k = jnp.where(rope_on_all, rotate(k, pos, every, factor), turn(k))
    scale = (nope + rope) ** -0.5 * jnp.where(
        no_yarn_scale, 1.0,                             # planted fault
        yarn_mscale(z["factor"], z["mscale_all_dim"]) ** 2).astype(q.dtype)
    rows = min(ROW_BLOCK, P)
    keys = jnp.arange(P)

    def head(qkv):
        qh, kh, vh = qkv                            # [P, 192], [P, 192], [P, 128]

        @jax.checkpoint
        def block(start):
            qb = lax.dynamic_slice_in_dim(qh, start, rows, 0)
            s = (qb @ kh.T) * scale
            seen = keys[None, :] <= (start + jnp.arange(rows))[:, None]
            s = jnp.where(seen, s, -jnp.inf)
            return jax.nn.softmax(s, -1) @ vh
        return lax.map(block, jnp.arange(0, P, rows)).reshape(P, dv)
    heads_first = lambda a: a.transpose(1, 0, 2)
    o = lax.map(head, (heads_first(q), heads_first(k), heads_first(v)))
    return heads_first(o).reshape(P, H * dv) @ p["w_o"]


def swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def experts(h, p, z, softmax_route=False, no_route_scale=False):
    """h [P, C] -> this share's routed part + the shared expert."""
    logits = h @ p["router"]                            # all E experts
    s = jnp.where(softmax_route, jax.nn.softmax(logits, -1),    # fault
                  jax.nn.sigmoid(logits))
    _, top_e = lax.top_k(s + p["router_bias"], z["K"])
    top_s = jnp.take_along_axis(s, top_e, axis=-1)
    w = top_s / (jnp.sum(top_s, -1, keepdims=True) + 1e-20)
    w = w * jnp.where(no_route_scale, 1.0,                      # fault
                      z["route_scale"]).astype(w.dtype)
    out = jnp.zeros_like(h)
    for g, e in enumerate(z["held"]):
        weight = jnp.sum(jnp.where(top_e == e, w, 0.0), -1)  # 0 if not chosen
        out = out + weight[:, None].astype(h.dtype) * swiglu(
            h, p["w_gate"][g], p["w_up"][g], p["w_down"][g])
    return out + swiglu(h, p["shared_gate"], p["shared_up"],
                        p["shared_down"])


def sinkhorn(m, iters, eps):
    """m [P, n, n] positive: `iters` times, every column over its sum,
    then every row over its sum.  -> (the result, what ONE iteration
    gives)."""
    once = None
    for i in range(iters):
        m = m / (jnp.sum(m, axis=1, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=2, keepdims=True) + eps)
        if i == 0:
            once = m
    return m, once


def hyper_connected(sublayer, X, hc, z, sinkhorn_1=False, no_res_mix=False):
    """X [P, n, C] -> X' around `sublayer` ([P, C] -> [P, C])."""
    P, n, C = X.shape
    u = X.reshape(P, n * C)
    m = jnp.matmul(u * lax.rsqrt(jnp.mean(jnp.square(u), -1, keepdims=True)
                                 + z["hc_eps"]), hc["phi"],
                   precision=lax.Precision.HIGHEST)
    a, b = hc["alpha"], hc["bias"]
    h_pre = jax.nn.sigmoid(a[0] * m[:, :n] + b[:n])
    h_post = 2.0 * jax.nn.sigmoid(a[1] * m[:, n:2 * n] + b[n:2 * n])
    A = jnp.clip(a[2] * m[:, 2 * n:] + b[2 * n:], *z["clamp"])
    h_res, once = sinkhorn(jnp.exp(A).reshape(P, n, n), z["iters"],
                           z["hc_eps"])
    h_res = jnp.where(sinkhorn_1, once, h_res)      # planted fault
    h_res = jnp.where(no_res_mix, jnp.eye(n, dtype=X.dtype), h_res)  # fault
    y = sublayer(jnp.sum(h_pre[:, :, None] * X, axis=1))
    mixed = jnp.sum(h_res[:, :, :, None] * X[:, None, :, :], axis=2)
    return mixed + h_post[:, :, None] * y[:, None, :]


def hidden_states(params, tokens, z, sinkhorn_1=False, no_res_mix=False,
                  rope_on_all=False, no_yarn_scale=False,
                  softmax_route=False, no_route_scale=False):
    """One sequence: ids [L] -> the final states [L, C]."""
    x = params["embed"][tokens]
    X = jnp.repeat(x[:, None, :], z["N"], axis=1)       # [L, n, C]
    pos = jnp.arange(tokens.shape[0])
    hc = dict(sinkhorn_1=sinkhorn_1, no_res_mix=no_res_mix)

    def layer(feed_forward):
        @jax.checkpoint
        def run(X, p):
            X = hyper_connected(
                lambda h: latent_attention(
                    rmsnorm(h, p["ln1"], z["eps"]), p, z, pos,
                    rope_on_all, no_yarn_scale), X, p["hc_attn"], z, **hc)
            return hyper_connected(
                lambda h: feed_forward(rmsnorm(h, p["ln2"], z["eps"]), p),
                X, p["hc_mlp"], z, **hc), None
        return run
    # the leading dense layers, then the expert layers, each kind stacked
    X, _ = lax.scan(layer(lambda h, p: swiglu(
        h, p["mlp_gate"], p["mlp_up"], p["mlp_down"])), X, params["dense"])
    X, _ = lax.scan(layer(lambda h, p: experts(
        h, p, z, softmax_route, no_route_scale)), X, params["sparse"])
    return rmsnorm(jnp.sum(X, axis=1), params["final_norm"], z["eps"])


def sequence_loss(params, tokens, z, first_half=False, **faults):
    """(mean next-token loss of one sequence over its L - 1 predicted
    positions, tokens predicted right).  `first_half` (the fault
    `drop_half` at one sequence a step): the mean over the first half of
    them."""
    logits = hidden_states(params, tokens, z, **faults)[:-1] @ params["head"]
    target = tokens[1:]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, target[:, None], axis=-1)[:, 0]
    n = len(target)
    kept = jnp.where(first_half, jnp.arange(n) < n // 2, True)
    return jnp.sum(jnp.where(kept, nll, 0.0)) / jnp.sum(kept), \
        jnp.sum(jnp.argmax(logits, -1) == target)


def tree_norms(tree):
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(k): float(jnp.sqrt(jnp.sum(jnp.square(
        jnp.asarray(v, jnp.float32))))) for k, v in flat}


def follow(config, *, seed, weights_seed, world, per_chip_batch, train,
           heldout, steps=3, drop_half=False, freeze=False, dtype="float32",
           **faults):
    """Train `steps` steps from the seeds and evaluate; return every number
    benchmark/correct.py reads.  `train` [N, L]: the stream's first
    sequences, step k taking rows [k*world*b, (k+1)*world*b), shard r its
    b = per_chip_batch rows of those; `heldout` [M, L].  `seed` drew the
    tokens; the objective draws nothing.  `faults`: of `FAULTS`."""
    del seed
    unknown = set(faults) - set(FAULTS)
    if unknown:
        raise TypeError(f"follow: no fault {sorted(unknown)}")
    return _follow(config, weights_seed, world, per_chip_batch, train,
                   heldout, steps, drop_half, freeze, dtype, faults)


def _follow(config, weights_seed, world, b, train, heldout, steps, drop_half,
            freeze, dtype, faults):
    z = sizes(config)
    opt = config["optimizer"]
    lr, mu, wd = config["lr"], opt["momentum"], opt["weight_decay"]
    params = jax.tree.map(lambda a: a.astype(dtype),
                          init(config, jax.random.PRNGKey(weights_seed)))
    # weights, velocity and two gradients are 12 GB at the real sizes: the
    # initial weights and, between updates, the velocity wait on the host
    p0 = jax.device_get(params)
    vel = jax.tree.map(np.zeros_like, p0)

    # every fault a traced flag: one program for the sound run and all
    flags = {name: jnp.asarray(bool(faults.get(name))) for name in FAULTS}
    flags["first_half"] = jnp.asarray(bool(drop_half and b == 1))

    @jax.jit
    def seq_grad(params, tokens, flags):
        return jax.value_and_grad(lambda p: sequence_loss(
            p, tokens, z, **flags)[0])(params)

    def sgd(params, vel, g):
        d = jax.tree.map(lambda p, gg: gg + wd * p, params, g)
        vel = jax.tree.map(lambda v, dd: mu * v + dd, vel, d)
        return jax.tree.map(lambda p, v: p - lr * v, params, vel), vel
    sgd = jax.jit(sgd, donate_argnums=(0, 1))
    add = jax.jit(lambda a, c: jax.tree.map(jnp.add, a, c),
                  donate_argnums=(0,))
    scale = jax.jit(lambda a, c: jax.tree.map(lambda x: x * c, a),
                    donate_argnums=(0,))
    out = {"loss": [], "grad_norms": []}
    for k in range(steps):
        losses, g = [], None
        for r in range(world):
            rows = jnp.asarray(train[(k * world + r) * b:
                                     (k * world + r + 1) * b])
            use = range(b // 2) if drop_half and b > 1 else range(b)
            total, g_sum = 0.0, None
            for s in use:
                loss, gs = seq_grad(params, rows[s], flags)
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
            params, new_vel = sgd(params, jax.device_put(vel), g)
            vel = jax.device_get(new_vel)
            del new_vel
        if k == 0:
            out["momentum1_norms"] = tree_norms(vel)
        del g
    out["dparam_norms"] = tree_norms(jax.tree.map(
        lambda a, c: np.asarray(a, np.float32) - np.asarray(c, np.float32),
        jax.device_get(params), p0))

    seq_eval = jax.jit(lambda params, tokens, flags: sequence_loss(
        params, tokens, z, **flags))
    flags["first_half"] = jnp.asarray(False)    # the evaluation is whole
    loss_sum, correct = 0.0, 0
    for row in heldout:
        l, c = seq_eval(params, jnp.asarray(row), flags)
        loss_sum += float(l)
        correct += int(c)
    out["eval_loss"] = loss_sum / len(heldout)
    out["eval_correct"] = correct
    out["eval_n"] = len(heldout)
    return out
