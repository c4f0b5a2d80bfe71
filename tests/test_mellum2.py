"""The window-and-full decoder (models/mellum2.py, ops/attention.py
`window_attention` beside `causal_attention`, ops/mla.py's YaRN outside
latent attention, ops/moe.py's softmax routing without a shared expert) at a
tiny size on the CPU: hidden 64, one period of three sliding layers (a
window of 8 keys) and one full layer, 4 query heads of 16 over 2 key/value
heads, 8 softmax-routed experts of 32 of which 2 are held, top-2, L = 32,
vocabulary 64.  The program against the benchmark's plain reference
(benchmark/reference/window_causal.py, which imports nothing of the
program) on seeded weights.
"""

import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import window_causal as ref      # noqa: E402
from cs744_ddp_tpu import cli, models                      # noqa: E402
from cs744_ddp_tpu.models import mellum2                   # noqa: E402
from cs744_ddp_tpu.obs import Telemetry                    # noqa: E402
from cs744_ddp_tpu.ops import attention, mla, moe, sgd     # noqa: E402
from cs744_ddp_tpu.train.loop import Trainer               # noqa: E402

CONFIG = json.load(open(os.path.join(
    ROOT, "benchmark", "tests", "tiny-mellum2-f32.json")))
REAL = json.load(open(os.path.join(
    ROOT, "benchmark", "configs", "mellum2-12b-a2.5b-ep8-f32.json")))
TINY = mellum2.TINY


def close(a, b, tol=2e-5):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.max(np.abs(a - b)) <= tol * max(1.0, np.max(np.abs(b)))


def near(a, b, tol):
    """Within `tol` of b's largest entry, however small: a gradient leaf."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.max(np.abs(a - b)) <= tol * np.max(np.abs(b))


# -- the window ---------------------------------------------------------------

def dense_window(q, k, v, window):
    """The rule on the whole [P, P] grid: query i sees key j iff
    i - window < j <= i; grouped-query by reshaping, as the program."""
    s, hq, n, d = q.shape
    hkv = k.shape[1]
    gap = np.arange(n)[:, None] - np.arange(n)[None, :]
    seen = (gap >= 0) & (gap < window)
    qg = q.reshape(s, hkv, hq // hkv, n, d)
    sc = jnp.where(seen, jnp.einsum("shgqd,shkd->shgqk", qg, k), -jnp.inf)
    o = jnp.einsum("shgqk,shkd->shgqd", jax.nn.softmax(sc, -1), v)
    return o.reshape(s, hq, n, d)


def qkv(n, d, hq=4, hkv=2):
    mk = lambda i, h: jax.random.normal(
        jax.random.fold_in(jax.random.PRNGKey(1), i), (2, h, n, d))
    return mk(0, hq) * d ** -0.5, mk(1, hkv), mk(2, hkv)


@pytest.mark.parametrize("tile,window", [(8, 5), (8, 8), (16, 12), (4, 9),
                                         (8, 32), (16, 40)])
def test_blocked_window_attention_is_the_dense_masked_softmax(tile, window):
    """Windows shorter, as long as and longer than a tile; a window of P
    or more is the causal rule.  Values and the three gradients."""
    q, k, v = qkv(32, 16)
    f = lambda *a: attention.window_attention(*a, window=window,
                                              kernels=False, tile=tile)
    want = dense_window(q, k, v, window)
    assert f(q, k, v).shape == (2, 4, 32, 16)
    assert close(f(q, k, v), want)
    if window >= 32:
        assert close(want, attention.causal_attention(q, k, v,
                                                      kernels=False))
    grad = lambda fn: jax.grad(lambda *a: jnp.sum(jnp.sin(fn(*a))),
                               argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(grad(f), grad(lambda *a: dense_window(*a, window))):
        assert close(a, b, 1e-4)
    # a key `window` positions back, or ahead, moves nothing
    moved = f(q, k.at[:, :, 10].add(1.0), v) - f(q, k, v)
    rows = np.flatnonzero(np.any(np.asarray(moved) != 0, axis=(0, 1, 3)))
    assert rows.min() == 10 and rows.max() == min(31, 10 + window - 1)


def test_pallas_window_kernels_interpreted_are_the_dense_masked_softmax():
    """The splash kernels under the library's `LocalMask` at head 128, two
    query heads a key/value head, tiles of 128 over 384 positions and a
    window of 160 (a band across tile edges), in Pallas' interpreter:
    forward and the gradients; bfloat16 operands, so to 2^-6 and 2^-5."""
    q, k, v = qkv(384, 128, hq=2, hkv=1)
    q, k, v = q[:1], k[:1], v[:1]
    f = lambda *a: attention.window_attention(
        *a, window=160, kernels=True, interpret=True, tile=128)
    want = dense_window(q, k, v, 160)
    assert f(q, k, v).shape == (1, 2, 384, 128)
    assert close(f(q, k, v), want, 2.0 ** -6)
    grad = lambda fn: jax.grad(lambda *a: jnp.sum(jnp.sin(fn(*a))),
                               argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(grad(f), grad(lambda *a: dense_window(*a, 160))):
        assert close(a, b, 2.0 ** -5)


# -- the attention kernels' output kept across a checkpoint -------------------

# Each rule's splash kernels in Pallas' interpreter at tiles of 128, head 128,
# two query heads a key/value head; the positions they take.
RULES = {
    "blockdiff": (lambda *a: attention.blockdiff_attention(
        *a, seq_len=256, block=4, kernels=True, interpret=True, tile=128),
        512),
    "causal": (lambda *a: attention.causal_attention(
        *a, kernels=True, interpret=True, tile=128), 384),
    "window": (lambda *a: attention.window_attention(
        *a, window=160, kernels=True, interpret=True, tile=128), 384)}


def checkpointed_grad(rule, policy):
    """(the gradient of sum(sin(attention)) with the rule's kernels under
    `jax.checkpoint(policy=policy)`, its q, k, v)."""
    f, n = RULES[rule]
    q, k, v = (a[:1] for a in qkv(n, 128, hq=2, hkv=1))
    grad = jax.grad(lambda *a: jnp.sum(jnp.sin(
        jax.checkpoint(f, policy=policy)(*a))), argnums=(0, 1, 2))
    return grad, (q, k, v)


def pallas_calls(jaxpr):
    """The kernel names of the `pallas_call`s in a jaxpr and in the jaxprs
    its equations hold, in order."""
    from jax.extend.core import ClosedJaxpr, Jaxpr
    names = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            names.append(eqn.params["name"])
            continue
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                if isinstance(sub, ClosedJaxpr):
                    names += pallas_calls(sub.jaxpr)
                elif isinstance(sub, Jaxpr):
                    names += pallas_calls(sub)
    return names


@pytest.mark.parametrize("rule", RULES)
def test_keeping_the_kernels_residuals_leaves_the_gradient_as_it_was(rule):
    """Under `KEEP_ATTN_RESIDUALS` the backward kernels take the output and
    logsumexp the forward kernel gave, where the default policy recomputes
    them from the same q, k, v: the same three gradients (bit for bit as
    run; asserted to 1e-6 of a gradient's largest entry)."""
    grad, args = checkpointed_grad(rule, attention.KEEP_ATTN_RESIDUALS)
    redone, _ = checkpointed_grad(rule, None)
    for a, b in zip(jax.jit(grad)(*args), jax.jit(redone)(*args)):
        assert near(a, b, 1e-6)


@pytest.mark.parametrize("rule", RULES)
def test_keeping_the_kernels_residuals_drops_the_recomputed_forward(rule):
    """The gradient under the default policy calls the forward kernel twice
    (the forward pass and its recomputation); under `KEEP_ATTN_RESIDUALS`
    once; dq and dkv once either way."""
    def calls(policy):
        grad, args = checkpointed_grad(rule, policy)
        names = pallas_calls(jax.make_jaxpr(grad)(*args).jaxpr)
        return {k: names.count(k) for k in set(names)}
    both = {"splash_mqa_dq_no_residuals": 1, "splash_mqa_dkv_no_residuals": 1}
    assert calls(None) == dict(both, splash_mqa_fwd_residuals=2)
    assert calls(attention.KEEP_ATTN_RESIDUALS) == dict(
        both, splash_mqa_fwd_residuals=1)


def test_the_models_gradient_runs_each_layers_forward_kernel_once():
    """The decoder's train gradient with the kernels on, traced (not run)
    at head 128 over 512 positions: each of the four layers of a period
    calls its rule's forward kernel once, as it calls dq and dkv (under
    the default policy the recomputation would call it a second time)."""
    shape = TINY._replace(hidden=256, heads=2, kv_heads=1, head_dim=128,
                          window=160, seq_len=512, vocab=256)
    init_fn, apply_fn = mellum2.make(shape, kernels=True)
    params = jax.eval_shape(lambda k: init_fn(k)[0], jax.random.PRNGKey(0))
    obj = apply_fn.objective
    toks = jax.ShapeDtypeStruct((2, shape.seq_len), jnp.int32)
    grad = jax.grad(lambda p, t: obj.loss(apply_fn, p, {}, t)[0])
    names = [n for n in pallas_calls(jax.make_jaxpr(grad)(params, toks).jaxpr)
             if n and n.startswith("splash_")]
    # one scan body for the sliding layers, one full layer: a call site a
    # kind of layer
    assert sorted(names) == sorted(
        ["splash_mqa_fwd_residuals", "splash_mqa_dq_no_residuals",
         "splash_mqa_dkv_no_residuals"] * 2)


@pytest.mark.parametrize("n,window,bq,bkv", [
    (64, 10, 16, 16), (64, 10, 8, 16), (64, 64, 16, 8), (96, 20, 32, 16),
    (96, 1, 16, 16), (128, 33, 32, 32)])
def test_window_tile_tally_is_the_brute_force_count(n, window, bq, bkv):
    gap = np.arange(n)[:, None] - np.arange(n)[None, :]
    dense = (gap >= 0) & (gap < window)
    t = dense.reshape(n // bq, bq, n // bkv, bkv)
    visited = int(t.any((1, 3)).sum())
    partial = visited - int(t.all((1, 3)).sum())
    got = attention.window_tile_tally(n, window, bq, bkv)
    assert got[:2] == (visited, partial)
    assert got[2] == pytest.approx(visited * bq * bkv / dense.sum())
    assert attention.window_pairs(n, window) == dense.sum()


def test_window_tile_tally_at_the_cells_sizes():
    """P = 8192, a window of 1024, tiles of 1024: the diagonal tile and the
    one before it of every row of tiles, all partly allowed, twice the
    allowed scores."""
    visited, partial, over = attention.window_tile_tally(8192, 1024, 1024,
                                                         1024)
    assert (visited, partial) == (15, 15)
    assert over == pytest.approx(1.99987, abs=1e-5)
    assert attention.window_pairs(8192, 1024) == 7_864_832


# -- the two rotary tables ----------------------------------------------------

def test_yarn_table_for_mellums_numbers_is_the_formula_by_hand():
    """d = 128, base 500,000, factor 16 over 8192 original positions,
    beta 32 / 1: dim(32) = 18.08, dim(1) = 34.98, so low 18 and high 35;
    pairs 0-17 keep base^(-2i/128), pairs 35-63 are that over 16, a linear
    ramp between."""
    got = mla.yarn_inv_freq(128, 5e5, 16.0, 8192, 32.0, 1.0)
    dim = lambda r: 128 * math.log(8192 / (2 * math.pi * r)) \
        / (2 * math.log(5e5))
    assert (math.floor(dim(32)), math.ceil(dim(1))) == (18, 35)
    f = 5e5 ** (-np.arange(0, 128, 2) / 128)
    assert got.shape == (64,)
    assert np.allclose(got[:18], f[:18], rtol=1e-6)
    assert np.allclose(got[35:], f[35:] / 16, rtol=1e-6)
    ramp = (np.arange(18, 35) - 18) / 17
    assert np.allclose(got[18:35], f[18:35] * (1 - ramp + ramp / 16),
                       rtol=1e-6)
    z = ref.sizes(REAL)
    assert np.allclose(ref.yarn_frequencies(128, z), got, rtol=1e-6)
    # the default table of the sliding layers
    assert np.allclose(mellum2.default_inv_freq(128, 5e5), f, rtol=1e-6)
    assert z["attn_factor"] == mellum2.Shape().attn_factor
    assert z["attn_factor"] == pytest.approx(0.1 * math.log(16) + 1)


def test_the_attention_factor_multiplies_cos_and_sin():
    """Every pair (i, i + 64) of a rotated head is the unrotated pair
    turned and scaled by the factor: its length times 1.27726."""
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 2, 128))
    pos = jnp.arange(8) * 37            # float32 angles to a few 1e-5
    inv = mla.yarn_inv_freq(128, 5e5, 16.0, 8192, 32.0, 1.0)
    a = mellum2.Shape().attn_factor
    got = np.asarray(mla.rotary(x, pos, inv, a), np.float64)
    xs = np.asarray(x, np.float64)
    length = lambda y: np.hypot(y[..., :64], y[..., 64:])
    assert np.allclose(length(got), a * length(xs), rtol=1e-5)
    z = (xs[..., :64] + 1j * xs[..., 64:]) * np.exp(
        1j * np.asarray(pos)[:, None, None] * np.asarray(inv, np.float64))
    assert np.allclose(got, a * np.concatenate([z.real, z.imag], -1),
                       rtol=1e-4, atol=1e-4)
    assert np.allclose(np.asarray(ref.rotate(x, pos, jnp.asarray(inv), a)),
                       got, atol=1e-5)


# -- the expert layer's shares ------------------------------------------------

def test_eight_shares_of_eight_experts_add_up_to_the_uncut_layer():
    """64 experts, top 8, as 8 chips' shares of 8: the parts the shares
    give (no shared expert) are what the plain reference gives for the
    whole layer, and their rows add up to P * top_k."""
    k = jax.random.split(jax.random.PRNGKey(2), 5)
    c, f, e = 32, 16, 64
    p = {"router": 0.3 * jax.random.normal(k[0], (c, e)),
         "w_gate": 0.3 * jax.random.normal(k[1], (e, c, f)),
         "w_up": 0.3 * jax.random.normal(k[2], (e, c, f)),
         "w_down": 0.3 * jax.random.normal(k[3], (e, f, c))}
    h = jax.random.normal(k[4], (48, c))
    whole = ref.experts(h, p, dict(K=8, held=list(range(e))))
    total, rows = 0.0, 0
    for chip in range(8):
        ids = tuple(range(8 * chip, 8 * chip + 8))
        part = dict(p, **{n: p[n][8 * chip:8 * chip + 8]
                          for n in ("w_gate", "w_up", "w_down")})
        out, n, _ = moe.expert_layer(h, part, held=ids, num_experts=e,
                                     top_k=8, kernels=False)
        total, rows = total + out, rows + int(n)
    assert rows == 48 * 8
    assert close(total, whole)


# -- the model and its objective against the plain reference ------------------

def config_for(layers):
    return dict(CONFIG, num_hidden_layers=layers)


def test_init_is_the_configurations_recipe():
    params, _ = mellum2.make(TINY)[0](jax.random.PRNGKey(7))
    rparams = ref.init(CONFIG, jax.random.PRNGKey(7))
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    rflat = dict(jax.tree_util.tree_flatten_with_path(rparams)[0])
    assert set(k for k, _ in flat) == set(rflat)
    assert all(np.array_equal(v, rflat[k]) for k, v in flat)
    sliding, full = params["periods"]["sliding"], params["periods"]["full"]
    assert sliding["wq"].shape == (1, 3, 64, 64)
    assert sliding["wk"].shape == (1, 3, 64, 32)
    assert full["w_gate"].shape == (1, 2, 64, 32)
    assert full["router"].shape == (1, 64, 8)
    assert set(sliding) == set(full) and "shared_gate" not in full
    assert np.all(np.asarray(full["ln1"]) == 1.0)
    assert abs(float(jnp.std(params["embed"])) - 1.0) < 0.05
    assert abs(float(jnp.std(full["wo"])) - 0.02) < 0.002


@pytest.mark.parametrize("layers", [4, 8], ids=["one-period", "two-periods"])
def test_loss_and_every_gradient_leaf_match_the_reference(layers):
    """The published order (three sliding layers, then a full one), once
    and twice; gains moved off 1 so that they count.  The program's blocked
    window and causal tiles and its scans against the reference's dense
    masks and loop over layers: float32 on both sides, so to 1e-5 on the
    loss and 1e-4 of a leaf's largest entry on its gradient (sums in other
    orders: 3e-7 read); each planted fault moves some leaf by a third or
    more of it."""
    config = config_for(layers)
    init_fn, apply_fn = mellum2.make(TINY._replace(layers=layers))
    params, _ = init_fn(jax.random.PRNGKey(0))
    params = jax.tree_util.tree_map_with_path(
        lambda path, a: a + 0.1 if path[-1].key in ("ln1", "ln2",
                                                    "final_norm") else a,
        params)
    toks = jax.random.randint(jax.random.PRNGKey(9), (2, 32), 0, 63)
    obj = apply_fn.objective

    def prog(p):
        loss, (_, extras) = obj.loss(apply_fn, p, {}, obj.prepare(None, toks))
        return loss, extras
    (loss, extras), grads = jax.jit(
        jax.value_and_grad(prog, has_aux=True))(params)
    z = ref.sizes(config)

    def plain(p):
        return sum(ref.sequence_loss(p, toks[s], z)[0] for s in range(2)) / 2
    rloss, rgrads = jax.jit(jax.value_and_grad(plain))(params)
    assert abs(float(loss) - float(rloss)) < 1e-5 * float(rloss)
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    rflat = dict(jax.tree_util.tree_flatten_with_path(rgrads)[0])
    assert len(flat) == 3 + 2 * 10
    for k, g in flat:
        assert near(g, rflat[k], 1e-4), jax.tree_util.keystr(k)
        assert np.any(np.asarray(g)), jax.tree_util.keystr(k)
    rows, fullest, count, touched = (float(e) for e in extras)
    assert count == 2 * 31
    assert 0 < fullest <= rows <= touched <= layers * 2 * 32 * 2
    # each planted fault is another model: the reference tells them apart
    for fault in ref.FAULTS:
        bad = jax.jit(jax.grad(lambda p: sum(ref.sequence_loss(
            p, toks[s], z, **{fault: True})[0] for s in range(2)) / 2))(
            params)
        assert not all(near(a, b, 0.1) for a, b in zip(
            jax.tree.leaves(bad), jax.tree.leaves(rgrads))), fault


def test_evaluation_counts_match_the_reference():
    init_fn, apply_fn = mellum2.make(TINY)
    params, _ = init_fn(jax.random.PRNGKey(1))
    toks = jax.random.randint(jax.random.PRNGKey(2), (3, 32), 0, 63)
    labels = jnp.asarray([0, 0, -1])            # the last row is padding
    loss, hit, count = apply_fn.objective.eval_counts(
        apply_fn, params, {}, None, toks, labels)
    z = ref.sizes(CONFIG)
    want = [ref.sequence_loss(params, toks[s], z) for s in range(2)]
    assert float(loss) == pytest.approx(sum(float(l) for l, _ in want),
                                        rel=1e-5)
    assert int(hit) == sum(int(c) for _, c in want) and int(count) == 62


SCOPES = ("attn_window", "window_core", "attn_global", "attn_causal",
          "moe_route", "moe_experts", "lm_head")


def test_every_scope_is_in_the_compiled_train_program():
    """Forward and backward: the scopes the benchmark's readers class the
    device time by, in the `op_name` of the compiled train step's
    instructions (what `benchmark/readers/lm.py` reads); the window's core
    inside the sliding mixer, the causal core inside the full one."""
    import re
    init_fn, apply_fn = mellum2.make(TINY)
    params, _ = init_fn(jax.random.PRNGKey(0))
    toks = jnp.zeros((2, 32), jnp.int32)
    obj = apply_fn.objective
    step = jax.jit(jax.grad(lambda p: obj.loss(apply_fn, p, {}, toks)[0]))
    names = set(re.findall(r'op_name="([^"]*)"',
                           step.lower(params).compile().as_text()))
    for scope in SCOPES:
        held = [n for n in names if f"/{scope}/" in n]
        assert any("transpose(jvp" in n for n in held), scope
        assert any("transpose(jvp" not in n for n in held), scope
    assert any("/attn_window/window_core/" in n for n in names)
    assert any("/attn_global/attn_causal/" in n for n in names)
    assert not any("/attn_window/" in n and "attn_causal" in n
                   for n in names)
    assert not any("/attn_global/" in n and "window_core" in n
                   for n in names)


def write_tokens(root, train, heldout):
    os.makedirs(os.path.join(root, "tokens"))
    np.save(os.path.join(root, "tokens", "train.npy"), train)
    np.save(os.path.join(root, "tokens", "heldout.npy"), heldout)


@pytest.mark.parametrize("devices", [1, 2])
def test_sgd_steps_and_test_model_through_trainer_match_the_reference(
        tmp_path, devices):
    """`Trainer.train_model` (staged epoch, scanned window, ring drain) and
    `test_model` against the reference followed step by step: the losses,
    the parameters' change, the evaluation; on two devices under `ddp`.
    Float32 both sides: 2e-5 of a loss, 1e-3 of a leaf's change (three
    momentum steps carry the gradients' round-off)."""
    rng = np.random.default_rng(5)
    b = 2 * devices
    train = rng.integers(0, 63, (4 * b, 32), dtype=np.int32)
    heldout = rng.integers(0, 63, (5, 32), dtype=np.int32)     # ragged eval
    write_tokens(str(tmp_path), train, heldout)
    tel = Telemetry()
    tr = Trainer(model="mellum2-tiny", strategy="ddp", num_devices=devices,
                 global_batch=b, data_dir=str(tmp_path), seed=11, init_seed=3,
                 sgd_cfg=sgd.SGDConfig(lr=0.01), limit_train_batches=3,
                 telemetry=tel, log=lambda s: None)
    assert tr.real_data
    p0 = jax.device_get(tr.state.params)
    timers = tr.train_model(0)
    eval_loss, correct, acc = tr.test_model()
    want = ref.follow(CONFIG, seed=11, weights_seed=3, world=devices,
                      per_chip_batch=2, train=train, heldout=heldout, steps=3)
    assert np.allclose(timers.losses, want["loss"], rtol=2e-5)
    assert abs(eval_loss - want["eval_loss"]) < 2e-5 * want["eval_loss"]
    assert correct == want["eval_correct"]
    moved = ref.tree_norms(jax.tree.map(
        lambda a, c: np.asarray(a) - c, jax.device_get(tr.state.params), p0))
    assert set(moved) == set(want["dparam_norms"])
    for leaf, norm in want["dparam_norms"].items():
        assert abs(moved[leaf] - norm) <= 1e-3 * norm + 1e-9, leaf
    # counters of the epoch, beside dispatches and host_round_trips
    totals = tel.counter_totals()
    assert totals["dispatches"] == totals["host_round_trips"] == 2
    assert totals["moe_rows_local"] == tr.last_epoch_extras["moe_rows_local"]
    # four expert layers: 32 positions x top-2 x 2 held of 8
    assert totals["moe_rows_expected"] == 3 * b * 32 * 2 * 2 * 4 / 8
    assert totals["tokens_predicted"] == 3 * b * 31
    assert totals["moe_rows_touched"] \
        == tr.last_epoch_extras["moe_rows_touched"]
    steps = [r for r in tel.records if r["kind"] == "step"]
    assert len(steps) == 3 and all("moe_rows_max_expert" in s for s in steps)
    # the gauges, once: the window's form and length, both rules' tiles
    gauges = {(r["name"], r.get("rule"), r.get("kernel")): r["value"]
              for r in tel.records if r["kind"] == "gauge"}
    assert gauges["window_kernel", None, None] == 0     # the CPU: jax.numpy
    assert gauges["attn_window", None, None] == 8
    assert gauges["attn_residuals_kept", None, None] == 0
    for rule in ("window", "causal"):
        assert all(gauges["attn_tiles_visited", rule, k] == 1
                   for k in ("fwd", "dkv", "dq"))
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    import telemetry_report
    text = "\n".join(telemetry_report._window_lines(tel.records)
                     + telemetry_report._attn_lines(tel.records)
                     + telemetry_report._moe_lines(tel.records))
    assert "== window attention ==" in text
    assert "(jax.numpy): a query sees the 8 keys that end at its own " \
        "position" in text
    assert "output and logsumexp kept across the layers' checkpoint: no" \
        in text
    assert "window    fwd  visits 1 tiles" in text
    assert "causal    dq   visits 1 tiles" in text
    assert f"predicted tokens {3 * b * 31:,}" in text


def test_the_gauges_say_which_attention_runs_and_what_its_tiles_cost():
    def gauges(shape, kernels):
        _, apply_fn = mellum2.make(shape, kernels=kernels)
        return {(name, a.get("rule"), a.get("kernel")): value
                for name, value, a in apply_fn.objective.gauges}
    real = gauges(mellum2.Shape(), True)
    assert real["window_kernel", None, None] == 1
    assert real["attn_window", None, None] == 1024
    # 1024 x 1024 forward and dkv tiles, 512 x 512 dq tiles (KERNEL_TILES)
    for k in ("fwd", "dkv"):
        assert real["attn_tiles_visited", "window", k] == 15
        assert real["attn_tiles_partial", "window", k] == 15
        assert real["attn_visited_over_allowed", "window", k] == \
            pytest.approx(1.99987, abs=1e-5)
        assert real["attn_tiles_visited", "causal", k] == 36
    assert real["attn_tiles_visited", "window", "dq"] == 45
    assert len(real) == 3 + 2 * 9
    assert gauges(mellum2.Shape(), False)["window_kernel", None, None] == 0
    assert gauges(TINY, None)["window_kernel", None, None] == 0  # the CPU


def test_the_gauge_says_whether_the_kernels_output_is_kept():
    """`attn_residuals_kept`: 1 where the kernels run (their output and
    logsumexp carry the name the layers' checkpoint keeps), 0 on the
    jax.numpy path, which names nothing; the CPU resolves to the latter."""
    def kept(shape, kernels):
        _, apply_fn = mellum2.make(shape, kernels=kernels)
        return {name: value for name, value, _ in apply_fn.objective.gauges
                }["attn_residuals_kept"]
    assert kept(mellum2.Shape(), True) == 1
    assert kept(mellum2.Shape(), False) == 0
    assert kept(TINY, None) == 0
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    import telemetry_report
    _, apply_fn = mellum2.make(mellum2.Shape(), kernels=True)
    events = [dict(attrs, kind="gauge", name=name, value=value)
              for name, value, attrs in apply_fn.objective.gauges]
    text = "\n".join(telemetry_report._window_lines(events)
                     + telemetry_report._attn_lines(events))
    assert "(the Pallas kernels): a query sees the 1,024 keys" in text
    assert "logsumexp kept across the layers' checkpoint: yes" in text


def test_a_share_of_whole_periods_only_and_of_its_own_fields():
    with pytest.raises(ValueError, match="whole periods"):
        models.get_model("mellum2-tiny", layers=6)
    with pytest.raises(ValueError, match="dense_layers"):
        models.get_model("mellum2-tiny", dense_layers=1)
    init_fn, apply_fn = models.get_model("mellum2-tiny", layers=8,
                                         held=(1, 5), seq_len=16)
    shape = apply_fn.objective.shape
    assert (shape.layers, shape.held, shape.seq_len) == (8, (1, 5), 16)
    params = jax.eval_shape(lambda k: init_fn(k)[0], jax.random.PRNGKey(0))
    assert params["periods"]["sliding"]["w_gate"].shape[:3] == (2, 3, 2)
    assert params["periods"]["full"]["wq"].shape == (2, 64, 64)


def test_the_published_share_is_the_configurations_count():
    """`--model mellum2-12b-a2.5b` resolves at the share the benchmark's
    configuration states, whose `counted` is the program's leaf shapes."""
    init_fn, apply_fn = models.get_model("mellum2-12b-a2.5b")
    shape = apply_fn.objective.shape
    params = jax.eval_shape(lambda k: init_fn(k)[0], jax.random.PRNGKey(0))
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params))
    assert n == REAL["counted"]["all"] == 624_072_960
    assert REAL["counted"]["bytes_weight_gradient_momentum_float32"] \
        == 12 * n == 7_488_875_520
    assert (shape.layers, len(shape.held), shape.vocab, shape.seq_len) == (
        REAL["num_hidden_layers"], REAL["num_experts"], REAL["vocab_size"],
        REAL["seq_len"])
    # every published width, under the catalog's keys
    assert (shape.hidden, shape.heads, shape.kv_heads, shape.head_dim,
            shape.expert_width, shape.num_experts, shape.top_k,
            shape.window, shape.eps) == tuple(
        REAL[k] if k != "num_experts" else REAL["published"][k]
        for k in ("hidden_size", "num_attention_heads",
                  "num_key_value_heads", "head_dim", "moe_intermediate_size",
                  "num_experts", "num_experts_per_tok", "sliding_window",
                  "rms_norm_eps"))
    full = REAL["rope_parameters"]["full_attention"]
    sliding = REAL["rope_parameters"]["sliding_attention"]
    assert (shape.rope_theta, shape.yarn_factor, shape.yarn_original,
            shape.yarn_beta, shape.attn_factor) == (
        full["rope_theta"], full["factor"],
        full["original_max_position_embeddings"],
        (full["beta_fast"], full["beta_slow"]), full["attention_factor"])
    assert sliding == {"rope_type": "default", "rope_theta": shape.rope_theta}
    kinds = REAL["layer_types"][:shape.layers]
    assert kinds == ["sliding_attention"] * 3 + ["full_attention"] \
        + ["sliding_attention"] * 3 + ["full_attention"]


def test_cli_trains_and_evaluates_the_decoder_with_its_shares_flags(tmp_path,
                                                                    capsys):
    """`python -m cs744_ddp_tpu.cli --model mellum2-tiny` on the default
    path with the share's flags the decoders have in common; the flags of
    other decoders' shares are refused by name."""
    argv = ["--model", "mellum2-tiny", "--strategy", "ddp",
            "--num-devices", "1", "--batch-size", "4", "--lr", "0.01",
            "--limit-train-batches", "2", "--data-dir", str(tmp_path),
            "--lm-layers", "4", "--lm-experts-held", "0-1",
            "--lm-vocab", "48", "--lm-seq-len", "16"]
    tr = cli.main(argv)
    shape = tr.objective.shape
    assert (shape.seq_len, shape.vocab, shape.held) == (16, 48, (0, 1))
    assert len(tr.train_split) == 64
    out = capsys.readouterr().out
    assert "Test set: Average loss:" in out and "/240 (" in out   # 16 x 15
    with pytest.raises(ValueError, match="block"):
        cli.main(argv + ["--lm-block", "4"])
    with pytest.raises(ValueError, match="dense_layers"):
        cli.main(argv + ["--lm-dense-layers", "1"])
