"""The block-diffusion mixture-of-experts decoder (models/sdar.py,
ops/attention.py, ops/moe.py, ops/loss.py, data/tokens.py) at a tiny size
on the CPU: hidden 64, 4 heads / 2 kv of 16, 8 experts of 32, top-2, L = 32,
B = 4, 2 layers, vocabulary 64.  The program against the benchmark's plain
reference (benchmark/reference/blockdiff.py, which imports nothing of the
program) on seeded weights: loss, every gradient leaf, three SGD steps
through `Trainer`, `test_model`; the mask against its four rules; the share
test; the dropless layer; the attention paths against the dense mask; the
layers' checkpoint, which keeps the attention kernel's output.
"""

import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import blockdiff as ref          # noqa: E402
from cs744_ddp_tpu.data import tokens                      # noqa: E402
from cs744_ddp_tpu.models import sdar                      # noqa: E402
from cs744_ddp_tpu.obs import Telemetry                    # noqa: E402
from cs744_ddp_tpu.ops import attention, moe, sgd          # noqa: E402
from cs744_ddp_tpu.ops import loss as losslib              # noqa: E402
from cs744_ddp_tpu.train.loop import Trainer               # noqa: E402
from test_mellum2 import near, pallas_calls                # noqa: E402

CONFIG = json.load(open(os.path.join(
    ROOT, "benchmark", "tests", "tiny-sdar-f32.json")))
TINY = sdar.TINY


def close(a, b, tol=2e-5):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.max(np.abs(a - b)) <= tol * max(1.0, np.max(np.abs(b)))


# -- the mask -----------------------------------------------------------------

def four_rules(i, j, L, B):
    blk = lambda x: (x % L) // B
    if i < L and j < L:
        return blk(i) == blk(j)
    if i < L:
        return blk(j) < blk(i)
    if j >= L:
        return blk(j) <= blk(i)
    return False


@pytest.mark.parametrize("traced", [False, True], ids=["numpy", "traced"])
@pytest.mark.parametrize("L,B,stride", [(16, 4, 1), (256, 4, 1),
                                        (4096, 4, 257), (24, 3, 1)])
def test_mask_follows_the_four_rules_entry_by_entry(L, B, stride, traced):
    """The shipped rule (a query's code against a key's masked and shifted
    id, ops/attention.py) against the four cases spelled out: powers of two
    (the cell's 4096 x 4 on every 257th row) and one pair that is not."""
    rows, ids = np.arange(0, 2 * L, stride), np.arange(2 * L)
    rule = lambda q, k: attention.blockdiff_allowed(q, k, L, B)
    if traced:
        got = np.asarray(jax.jit(rule)(jnp.asarray(rows)[:, None],
                                       jnp.asarray(ids)[None, :]))
    else:
        got = rule(rows[:, None], ids[None, :])
    assert got.dtype == np.bool_
    want = np.array([[four_rules(i, j, L, B) for j in ids] for i in rows])
    assert np.array_equal(got, want)
    if stride == 1:
        assert got.sum() == L * B + L * L        # a quarter of (2L)^2, + L*B
        assert np.array_equal(got, np.asarray(ref.dense_mask(L, B)))


def tile_shaped(jaxpr, shape, inside=False, found=None):
    """Primitives inside a `pallas_call` whose result has `shape`, by
    integer / boolean or floating result."""
    found = {"int": [], "float": []} if found is None else found
    for e in jaxpr.eqns:
        kernel = inside or e.primitive.name == "pallas_call"
        for v in e.params.values():
            for sub in v if isinstance(v, (list, tuple)) else [v]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    tile_shaped(sub, shape, kernel, found)
        if inside and e.primitive.name not in ("jit", "pjit"):
            for o in e.outvars:
                if getattr(o.aval, "shape", None) == shape:
                    kind = "float" if jnp.issubdtype(
                        o.aval.dtype, jnp.floating) else "int"
                    found[kind].append(e.primitive.name)
    return found


def test_forward_kernel_spends_no_division_on_the_mask():
    """What PR 32 found, pinned: the library evaluates a computable mask's
    rule on every score of every visited tile, and the rule as it was
    written (`%`, `//` with their sign repairs) cost a tile 49 integer /
    boolean operations, 4 `rem` and 2 `div` among them, beside 6 float
    ones.  Traced at the cell's sizes on the CPU; nothing runs."""
    L, B, G = 4096, 4, 8
    tiles = attention._fit(L, B, attention.KERNEL_TILES, None)
    kernel = attention._splash_kernel(L, B, G, tiles, False)
    q = jax.ShapeDtypeStruct((G, 2 * L, 128), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((2 * L, 128), jnp.bfloat16)
    ops = tile_shaped(jax.make_jaxpr(kernel)(q, k, k).jaxpr,
                      (tiles.fwd[0], tiles.fwd[2]))
    assert "dot_general" in ops["float"] and "exp" in ops["float"]
    assert not {"rem", "div"} & set(ops["int"]), ops["int"]
    assert 0 < len(ops["int"]) <= 20, ops["int"]


def dense_attention(q, k, v, L, B):
    ids = np.arange(2 * L)
    m = attention.blockdiff_allowed(ids[:, None], ids[None, :], L, B)
    g = q.shape[1] // k.shape[1]
    kk, vv = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    s = jnp.where(m, jnp.einsum("shqd,shkd->shqk", q, kk), -jnp.inf)
    return jnp.einsum("shqk,shkd->shqd", jax.nn.softmax(s, -1), vv)


def qkv(L, D, hq=4, hkv=2, s=2):
    k0 = jax.random.PRNGKey(1)
    mk = lambda i, h: jax.random.normal(jax.random.fold_in(k0, i),
                                        (s, h, 2 * L, D), jnp.float32)
    return mk(0, hq), mk(1, hkv), mk(2, hkv)


def test_blocked_attention_matches_the_dense_mask_forward_and_backward():
    """L = 32, B = 4, tiles of 8: query tile 0 meets wholly masked clean
    tiles 1..3, every diagonal tile is partly masked, tiles below it are
    wholly allowed."""
    L, B = 32, 4
    q, k, v = qkv(L, 16, hq=2, hkv=1, s=1)
    f = lambda *a: attention.blockdiff_attention(
        *a, seq_len=L, block=B, kernels=False, tile=8)
    g = lambda *a: dense_attention(*a, L, B)
    assert close(f(q, k, v), g(q, k, v))
    cot = jnp.sin(jnp.arange(q.size, dtype=jnp.float32)).reshape(q.shape)
    df = jax.grad(lambda *a: jnp.sum(f(*a) * cot), (0, 1, 2))(q, k, v)
    dg = jax.grad(lambda *a: jnp.sum(g(*a) * cot), (0, 1, 2))(q, k, v)
    assert all(close(a, b, 1e-4) for a, b in zip(df, dg))


@pytest.mark.parametrize("tile", [
    128, attention.Tiles(fwd=(256, 128, 128), dkv=(128, 256, 128),
                         dq=(256, 128))], ids=["square", "rectangular"])
def test_pallas_attention_kernels_match_the_dense_mask_interpreted(tile):
    """The TPU path's kernels (forward, dq, dkv) under the same rule, in
    Pallas' interpreter: head size 128, tiles of 128 (then of 256 x 128 and
    128 x 256) over 2L = 512, so tiles are skipped, partly masked and
    wholly allowed."""
    L, B = 256, 4
    q, k, v = qkv(L, 128, hq=2, hkv=1, s=1)
    f = lambda *a: attention.blockdiff_attention(
        *a, seq_len=L, block=B, kernels=True, interpret=True, tile=tile)
    g = lambda *a: dense_attention(*a, L, B)
    assert close(f(q, k, v), g(q, k, v), 5e-2)      # bfloat16 in and out
    df = jax.grad(lambda *a: jnp.sum(jnp.sin(f(*a))), (0, 1, 2))(q, k, v)
    dg = jax.grad(lambda *a: jnp.sum(jnp.sin(g(*a))), (0, 1, 2))(q, k, v)
    assert all(close(a, b, 1e-1) for a, b in zip(df, dg))


@pytest.mark.parametrize("bq,bkv", [(128, 128), (256, 128), (64, 512),
                                    (512, 512)])
def test_tile_tally_is_the_brute_force_count(bq, bkv):
    L, B = 256, 4
    ids = np.arange(2 * L)
    dense = np.array([[four_rules(i, j, L, B) for j in ids] for i in ids])
    t = dense.reshape(2 * L // bq, bq, 2 * L // bkv, bkv)
    visited = int(t.any((1, 3)).sum())
    partial = visited - int(t.all((1, 3)).sum())
    got = attention.tile_tally(L, B, bq, bkv)
    assert got[:2] == (visited, partial)
    assert got[2] == pytest.approx(visited * bq * bkv / dense.sum())


def test_tile_gauges_name_each_kernel_at_the_cells_sizes():
    rows = attention.kernel_tile_gauges(4096, 4)
    assert {(name, attrs["kernel"]) for name, _, attrs in rows} == {
        (n, k) for n in ("attn_tiles_visited", "attn_tiles_partial",
                         "attn_visited_over_allowed")
        for k in ("fwd", "dkv", "dq")}
    assert {attrs["rule"] for _, _, attrs in rows} == {"blockdiff"}
    over = {a["kernel"]: v for n, v, a in rows
            if n == "attn_visited_over_allowed"}
    assert all(1.0 < v < 2.01 for v in over.values()), over


# -- the expert layer ---------------------------------------------------------

def layer_params(key, hidden=64, width=32, experts=8):
    ks = jax.random.split(key, 4)
    n = lambda k, *s: 0.1 * jax.random.normal(k, s, jnp.float32)
    return {"router": n(ks[0], hidden, experts) * 10,
            "w_gate": n(ks[1], experts, hidden, width),
            "w_up": n(ks[2], experts, hidden, width),
            "w_down": n(ks[3], experts, width, hidden)}


def share_of(p, held):
    idx = np.asarray(held)
    return dict(p, w_gate=p["w_gate"][idx], w_up=p["w_up"][idx],
                w_down=p["w_down"][idx])


def test_four_shares_add_up_to_the_uncut_layer():
    """8 experts in 4 shares of 2: the shares' outputs add up to what the
    plain reference gives for the whole layer, and their rows to P * top_k."""
    p = layer_params(jax.random.PRNGKey(0))
    h = jax.random.normal(jax.random.PRNGKey(1), (64, 64), jnp.float32)
    z = dict(K=2, E=8, held=list(range(8)))
    whole = ref.experts(h, p, z, drop_rows=False)
    total, rows = 0.0, 0
    for held in ((0, 1), (2, 3), (4, 5), (6, 7)):
        out, n, fullest = moe.expert_layer(
            h, share_of(p, held), held=held, num_experts=8, top_k=2,
            kernels=False)
        total, rows = total + out, rows + int(n)
        assert 0 < int(fullest) <= int(n)
    assert rows == 64 * 2
    assert close(total, whole)


def test_no_row_is_dropped_when_every_row_goes_to_one_held_expert():
    """A planted router: every position's first choice is expert 5, held
    here.  Its group takes all P rows (8x the even share) and the output is
    that expert's on every row."""
    p = layer_params(jax.random.PRNGKey(2))
    p["router"] = jnp.zeros_like(p["router"]).at[:, 5].set(1.0)
    h = jnp.abs(jax.random.normal(jax.random.PRNGKey(3), (96, 64))) + 0.1
    out, n, fullest = moe.expert_layer(
        h, share_of(p, (5,)), held=(5,), num_experts=8, top_k=2,
        kernels=False)
    assert int(n) == 96 and int(fullest) == 96
    _, w = moe.route(h, p["router"], 2)
    dense = (jax.nn.silu(h @ p["w_gate"][5]) * (h @ p["w_up"][5])) \
        @ p["w_down"][5]
    assert close(out, w[:, :1] * dense)


def test_grouped_matmul_kernel_path_matches_the_plain_one_interpreted():
    """The Pallas grouped matmul on the dropless buffer (rows past the live
    ones are never written: the interpreter leaves NaN there), forward and
    every gradient, against XLA's ragged dot."""
    p = layer_params(jax.random.PRNGKey(4), hidden=128, width=128)
    h = jax.random.normal(jax.random.PRNGKey(5), (256, 128), jnp.float32)
    sh = share_of(p, (2, 5))

    def run(kernels):
        def f(sh, h):
            out, n, _ = moe.expert_layer(
                h, sh, held=(2, 5), num_experts=8, top_k=2, kernels=kernels,
                interpret=kernels)
            return jnp.sum(jnp.sin(out)), n
        (val, n), g = jax.value_and_grad(f, (0, 1), has_aux=True)(sh, h)
        return val, n, g
    old = moe.GMM_TILING
    moe.GMM_TILING = (128, 128, 128)
    try:
        v1, n1, g1 = run(True)
    finally:
        moe.GMM_TILING = old
    v0, n0, g0 = run(False)
    assert int(n1) == int(n0) and close(v1, v0, 2e-2)
    flat1, flat0 = jax.tree.leaves(g1), jax.tree.leaves(g0)
    assert all(np.all(np.isfinite(a)) for a in flat1)
    assert all(close(a, b, 5e-2) for a, b in zip(flat1, flat0))


@pytest.mark.parametrize("kernels", [False, True], ids=["plain", "pallas"])
@pytest.mark.parametrize("where", ["none", "rung", "rung+1", "all"])
def test_every_rung_of_the_ladder_gives_what_the_whole_buffer_gives(
        monkeypatch, where, kernels):
    """The layer on the prefix its routed count picks against the layer on
    the whole buffer (a ladder of the top rung alone), for counts of 0 rows,
    exactly a rung's, a rung's + 1 and all positions x top_k: output,
    counts, every gradient, and the rung.  The kernel path runs
    interpreted, so rows past the live ones hold NaN."""
    positions, k = (256, 2) if kernels else (64, 2)
    n = positions * k
    if kernels:
        monkeypatch.setattr(moe, "GMM_TILING", (128, 128, 128))
    rungs = moe.ladder(n, kernels)
    assert rungs[-1] == n and len(rungs) >= 3
    live = {"none": 0, "rung": rungs[1], "rung+1": rungs[1] + 1, "all": n}[
        where]
    want = {"none": rungs[0], "rung": rungs[1], "rung+1": rungs[2],
            "all": n}[where]
    p = layer_params(jax.random.PRNGKey(6), hidden=128 if kernels else 64,
                     width=128 if kernels else 32)
    hidden = p["router"].shape[0]
    h = jax.random.normal(jax.random.PRNGKey(7), (positions, hidden))
    a, b = live - live // 2, live // 2
    ids = np.stack([np.where(np.arange(positions) < a, 2, 0),
                    np.where(np.arange(positions) < b, 5, 1)], 1)
    real_route = moe.route

    def planted_route(h, w_router, top_k):
        """The router's weights on planted ids: the first `a` positions'
        first choice is held expert 2, the first `b` positions' second is
        held expert 5, every other choice an absent expert's."""
        _, w = real_route(h, w_router, top_k)
        return jnp.asarray(ids, jnp.int32), w
    monkeypatch.setattr(moe, "route", planted_route)
    sh = share_of(p, (2, 5))

    def run():
        def f(sh, h):
            out, rows, fullest = moe.expert_layer(
                h, sh, held=(2, 5), num_experts=8, top_k=k, kernels=kernels,
                interpret=kernels)
            return jnp.sum(jnp.sin(out)), (out, rows, fullest)
        (_, aux), g = jax.value_and_grad(f, (0, 1), has_aux=True)(sh, h)
        return aux, g
    (out, rows, fullest), grads = run()
    assert int(moe.prefix_rows(rows, n, kernels)) == want
    monkeypatch.setattr(moe, "LADDER", (1,))
    assert moe.ladder(n, kernels) == (n,)
    (out_top, rows_top, fullest_top), grads_top = run()
    assert int(rows) == int(rows_top) == live
    assert int(fullest) == int(fullest_top) == a
    # float32 rounding; on the kernel path a last bit of float32 becomes
    # a last bit of the bfloat16 a cotangent is rounded to on its way
    tol = 2.0 ** -8 if kernels else 1e-6
    assert np.all(np.isfinite(out)) and close(out, out_top, 1e-6)
    flat, flat_top = jax.tree.leaves(grads), jax.tree.leaves(grads_top)
    assert len(flat) == 5       # h, router, w_gate, w_up, w_down
    assert all(np.all(np.isfinite(g)) for g in flat)
    assert all(close(g, t, tol) for g, t in zip(flat, flat_top))


def test_expert_layer_refuses_a_held_expert_the_router_does_not_score():
    p = layer_params(jax.random.PRNGKey(0))
    h = jnp.ones((8, 64), jnp.float32)
    for held, experts in (((8,), 8), ((0, 1), 16)):
        with pytest.raises(ValueError, match="num_experts"):
            moe.expert_layer(h, share_of(p, (0,)), held=held,
                             num_experts=experts, top_k=2, kernels=False)


# -- the model and its objective against the plain reference -------------------

def program_and_reference(seed=0):
    init_fn, apply_fn = sdar.make(TINY)
    params, _ = init_fn(jax.random.PRNGKey(seed))
    rparams = ref.init(CONFIG, jax.random.PRNGKey(seed))
    return apply_fn, params, rparams


def test_init_is_the_configurations_recipe():
    _, params, rparams = program_and_reference(7)
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    rflat = dict(jax.tree_util.tree_flatten_with_path(rparams)[0])
    assert set(k for k, _ in flat) == set(rflat)
    assert all(np.array_equal(v, rflat[k]) for k, v in flat)
    assert abs(float(jnp.std(params["embed"])) - 1.0) < 0.05
    assert abs(float(jnp.std(params["layers"]["router"])) - 0.02) < 0.002


def test_noising_masks_at_least_one_token_a_block_and_weights_by_1_over_t():
    toks = jnp.arange(8 * 32, dtype=jnp.int32).reshape(8, 32) % 63
    key = jax.random.PRNGKey(3)
    xt, masked, w = losslib.blockdiff_noise(key, toks, 4, 63)
    rxt, rmasked, rw = ref.noise(key, toks, 4, 63)
    assert np.array_equal(xt, rxt) and np.array_equal(masked, rmasked)
    assert np.array_equal(w, rw)
    assert np.all(np.asarray(masked).reshape(8, 8, 4).any(-1))
    assert np.all(np.asarray(xt)[np.asarray(masked)] == 63)
    assert np.all((np.asarray(w) >= 1.0) & (np.asarray(w) <= 4.0))


def test_loss_and_every_gradient_leaf_match_the_reference():
    apply_fn, params, rparams = program_and_reference()
    z = ref.sizes(CONFIG)
    mask = ref.dense_mask(z["L"], z["B"])
    toks = jax.random.randint(jax.random.PRNGKey(9), (2, 32), 0, 63)
    key = jax.random.PRNGKey(4)
    obj = apply_fn.objective
    x = obj.prepare(key, toks)

    def prog(p):
        loss, (_, extras) = obj.loss(apply_fn, p, {}, x)
        return loss, extras
    (loss, extras), grads = jax.value_and_grad(prog, has_aux=True)(params)

    xt, masked, w = ref.noise(key, toks, z["B"], z["V"] - 1)

    def plain(p):
        return sum(ref.sequence_loss(p, xt[s], toks[s], masked[s], w[s], z,
                                     mask)[0] for s in range(2)) / 2
    rloss, rgrads = jax.value_and_grad(plain)(rparams)
    assert abs(float(loss) - float(rloss)) < 1e-5 * float(rloss)
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    rflat = dict(jax.tree_util.tree_flatten_with_path(rgrads)[0])
    for k, g in flat:
        assert close(g, rflat[k], 2e-4), jax.tree_util.keystr(k)
    rows, fullest, count, touched = (float(e) for e in extras)
    assert count == float(jnp.sum(masked)) and 0 < fullest <= rows <= touched


# -- the attention kernel's output kept across the layer's checkpoint ---------

# The kernels' smallest shape: heads of 128, a key/value head for two query
# heads, tiles of 128; L = 256, so 512 positions a sequence.
KERNEL_SHAPE = TINY._replace(hidden=128, heads=2, kv_heads=1, head_dim=128,
                             expert_width=128, seq_len=256)


def redone_layer(shape, kernels, p, positions):
    """`sdar._remat_layer` under the default policy: the backward pass
    recomputes the whole layer, the attention kernel's output too."""
    return jax.checkpoint(
        lambda x: sdar._layer(shape, kernels, x, p, positions))


def model_grad(shape, kernels):
    """(the gradient of the model's training loss, its parameters, a batch
    of two sequences)."""
    init_fn, apply_fn = sdar.make(shape, kernels=kernels)
    params, _ = init_fn(jax.random.PRNGKey(0))
    obj = apply_fn.objective
    toks = jax.random.randint(jax.random.PRNGKey(9), (2, shape.seq_len), 0,
                              shape.vocab - 1)
    x = obj.prepare(jax.random.PRNGKey(4), toks)
    grad = jax.value_and_grad(lambda p: obj.loss(apply_fn, p, {}, x)[0])
    return grad, params


@pytest.mark.parametrize("policy", ["kept", "default"])
def test_the_models_gradient_runs_the_forward_kernel_once(policy,
                                                          monkeypatch):
    """The decoder's train gradient with the kernels on, traced (not run)
    at head 128 over 512 positions, two sequences: the layer scan's one
    body calls the forward kernel once, as it calls dq and dkv; under the
    default policy the recomputation calls it a second time."""
    if policy == "default":
        monkeypatch.setattr(sdar, "_remat_layer", redone_layer)
    grad, params = model_grad(KERNEL_SHAPE, True)
    names = [n for n in pallas_calls(jax.make_jaxpr(grad)(params).jaxpr)
             if n and n.startswith("splash_")]
    forward = 1 if policy == "kept" else 2
    assert sorted(names) == sorted(
        ["splash_mqa_fwd_residuals"] * forward
        + ["splash_mqa_dq_no_residuals", "splash_mqa_dkv_no_residuals"])


def test_keeping_the_kernels_output_leaves_loss_and_gradients_as_they_were(
        monkeypatch):
    """The model at L = 128 with both kinds of kernel in Pallas' interpreter
    (tiles of 128): the loss and every gradient leaf under the layer's
    checkpoint as shipped, which keeps the attention kernel's output and
    logsumexp, and under the default policy, which recomputes them from the
    same q, k, v, agree to 1e-6 of a leaf's largest entry."""
    monkeypatch.setattr(attention, "blockdiff_attention", functools.partial(
        attention.blockdiff_attention, interpret=True, tile=128))
    monkeypatch.setattr(moe, "expert_layer", functools.partial(
        moe.expert_layer, interpret=True))
    monkeypatch.setattr(moe, "GMM_TILING", (128, 128, 128))
    grad, params = model_grad(KERNEL_SHAPE._replace(seq_len=128), True)
    loss, kept = jax.jit(grad)(params)
    monkeypatch.setattr(sdar, "_remat_layer", redone_layer)
    rloss, redone = jax.jit(grad)(params)
    assert abs(float(loss) - float(rloss)) <= 1e-6 * abs(float(rloss))
    flat = jax.tree_util.tree_flatten_with_path(kept)[0]
    rflat = dict(jax.tree_util.tree_flatten_with_path(redone)[0])
    for k, g in flat:
        assert np.all(np.isfinite(g)), jax.tree_util.keystr(k)
        assert near(g, rflat[k], 1e-6), jax.tree_util.keystr(k)


def test_the_gauge_says_whether_the_kernels_output_is_kept():
    """`attn_residuals_kept`: 1 where the kernels run (their output and
    logsumexp carry the name the layer's checkpoint keeps), 0 on the
    jax.numpy path, which names nothing; the CPU resolves to the latter.
    The report prints it under the attention tiles."""
    def gauges(shape, kernels):
        _, apply_fn = sdar.make(shape, kernels=kernels)
        return apply_fn.objective.gauges
    kept = lambda *a: {name: value for name, value, _ in gauges(*a)
                       }["attn_residuals_kept"]
    assert kept(sdar.Shape(), True) == 1
    assert kept(sdar.Shape(), False) == 0
    assert kept(TINY, None) == 0
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    import telemetry_report
    events = [dict(attrs, kind="gauge", name=name, value=value)
              for name, value, attrs in gauges(sdar.Shape(), True)]
    text = "\n".join(telemetry_report._attn_lines(events))
    assert "logsumexp kept across the layers' checkpoint: yes" in text
    assert telemetry_report._window_lines(events) == []


def write_tokens(root, train, heldout):
    os.makedirs(os.path.join(root, "tokens"))
    np.save(os.path.join(root, "tokens", "train.npy"), train)
    np.save(os.path.join(root, "tokens", "heldout.npy"), heldout)


@pytest.mark.parametrize("devices", [1, 2])
def test_three_sgd_steps_and_test_model_through_trainer_match_the_reference(
        tmp_path, devices):
    """`Trainer.train_model` (staged epoch, scanned window, ring drain) and
    `test_model` against the reference followed step by step; on two
    devices the ddp strategy's reduction is the only one (train/step.py
    `checks_vma`)."""
    rng = np.random.default_rng(5)
    b = 4 * devices
    train = rng.integers(0, 63, (4 * b, 32), dtype=np.int32)
    heldout = rng.integers(0, 63, (6, 32), dtype=np.int32)     # ragged eval
    write_tokens(str(tmp_path), train, heldout)
    tel = Telemetry()
    tr = Trainer(model="sdar-tiny", strategy="ddp", num_devices=devices,
                 global_batch=b, data_dir=str(tmp_path), seed=11, init_seed=3,
                 sgd_cfg=sgd.SGDConfig(lr=0.01), limit_train_batches=3,
                 telemetry=tel, log=lambda s: None)
    assert tr.real_data
    timers = tr.train_model(0)
    eval_loss, correct, acc = tr.test_model()
    want = ref.follow(CONFIG, seed=11, weights_seed=3, world=devices,
                      per_chip_batch=4, train=train, heldout=heldout, steps=3)
    assert np.allclose(timers.losses, want["loss"], rtol=2e-5)
    assert abs(eval_loss - want["eval_loss"]) < 2e-5 * want["eval_loss"]
    assert correct == want["eval_correct"]
    # counters of the epoch, beside dispatches and host_round_trips
    totals = tel.counter_totals()
    assert totals["dispatches"] == totals["host_round_trips"] == 2
    assert totals["moe_rows_local"] == tr.last_epoch_extras["moe_rows_local"]
    assert totals["moe_rows_expected"] == 3 * b * 2 * 32 * 2 * 2 * 2 / 8
    assert totals["tokens_masked"] > 0
    # the rungs the expert layers ran on: at least the live rows, at most
    # positions x top_k rows a layer of a sequence
    touched = tr.last_epoch_extras["moe_rows_touched"]
    assert totals["moe_rows_touched"] == touched
    assert totals["moe_rows_local"] <= touched <= 3 * b * 2 * 32 * 2 * 2
    assert touched % (2 * 32 * 2 // 4) == 0          # whole rungs of N/4
    rows = [r for r in tel.records if r["kind"] == "counter"
            and r["name"] == "moe_rows_local"]
    assert rows and all(r["epoch"] == 0 for r in rows)
    steps = [r for r in tel.records if r["kind"] == "step"]
    assert len(steps) == 3 and all("moe_rows_max_expert" in s for s in steps)
    # the attention kernels' tile tally, once a kernel: at L = 32 one tile
    # an edge, so 4 tiles, the clean -> noisy one never visited
    gauges = {(r["name"], r.get("kernel")): r["value"] for r in tel.records
              if r["kind"] == "gauge" and r["name"].startswith("attn_")}
    assert len(gauges) == 9 + 1
    assert all(gauges["attn_tiles_visited", k] == 3
               and gauges["attn_tiles_partial", k] == 3
               for k in ("fwd", "dkv", "dq"))
    assert {r["rule"] for r in tel.records if r["kind"] == "gauge"
            and r["name"].startswith("attn_")
            and r["name"] != "attn_residuals_kept"} == {"blockdiff"}
    # the CPU runs the blocked jax.numpy form, which names nothing to keep
    assert gauges["attn_residuals_kept", None] == 0
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    import telemetry_report
    text = "\n".join(telemetry_report._attn_lines(tel.records))
    assert "fwd  visits 3 tiles, 3 partly allowed" in text
    assert "logsumexp kept across the layers' checkpoint: no" in text
    # the host spans of the default path are this path's too: one window,
    # no ragged tail, the evaluation (tests/test_loop_spans.py's tree), and
    # the first epoch's collective statistics under a second obs_emit
    names = [r["name"] for r in tel.records if r["kind"] == "span"
             and "epoch" in r and r["name"] != "compile_warmup"]
    assert sorted(names) == sorted([
        "epoch_train", "stage_lookup", "ring_alloc", "train_window",
        "window_dispatch", "window_drain", "window_host", "obs_emit",
        "obs_emit", "eval", "eval_stage_lookup", "eval_dispatch",
        "eval_fetch"])


def test_epochs_of_the_stream_never_recur_and_restage(tmp_path):
    tr = Trainer(model="sdar-tiny", strategy="ddp", num_devices=1,
                 global_batch=4, data_dir=str(tmp_path), seed=2,
                 limit_train_batches=2, log=lambda s: None)
    assert not tr.real_data
    a = np.asarray(tr._stage_train_epoch(0)[0])
    b = np.asarray(tr._stage_train_epoch(1)[0])
    assert a.shape == (2, 4, 32) and not np.array_equal(a, b)
    assert np.array_equal(a, np.asarray(tr._stage_train_epoch(0)[0]))
    assert a.max() < TINY.mask_id
    split = tokens.TokenSplit(np.arange(12, dtype=np.int32).reshape(6, 2),
                              2, 64)
    assert split.epoch(1, 4)[:, 0].tolist() == [8, 10, 0, 2]   # wraps


def test_decoder_refuses_the_paths_it_does_not_run(tmp_path):
    with pytest.raises(ValueError, match="default windowed path"):
        Trainer(model="sdar-tiny", strategy="ddp", num_devices=1,
                global_batch=4, data_dir=str(tmp_path), host_augment=True,
                log=lambda s: None)
