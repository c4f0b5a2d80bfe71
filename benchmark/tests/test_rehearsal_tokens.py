"""The `train-tokens` driver end to end without a chip: the tiny decoder
(`sdar-tiny`: hidden 64, 4 heads / 2 kv of 16, 8 experts of 32 of which 2
are held, top-2, L = 32, B = 4, 2 layers, vocabulary 64) on the CPU mesh.
A sound run must come out correct; the timed path broken underneath, the
lower-precision control and each fault planted in the reference must not.
"""

import json
import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))

LIMITS = {"loss1": 1e-4, "loss2": 1e-4, "loss3": 1e-4, "grad1_leaf": 1e-3,
          "dparam3_leaf": 1e-3, "eval_loss3": 1e-4}


def ctx(chips=1, seed=3, **over):
    import tempfile
    import time
    from benchmark.window import Phases
    manifest = {"configs": [{"name": "tiny-sdar-f32",
                             "file": "benchmark/tests/tiny-sdar-f32.json"}],
                "workloads": [], "end_to_end": [], "per_layer": []}
    config = json.load(open(os.path.join(HERE, "tiny-sdar-f32.json")))
    out = {"manifest": manifest,
           "cell": {"name": "tiny-sdar-cpu", "config": "tiny-sdar-f32",
                    "traffic": "rehearsal", "chips": chips},
           "config": config,
           "traffic": {"kind": "train-tokens", "strategy": "ddp",
                       "chips": chips, "warmup_units": 1,
                       "stream_units": 512, "weights_seed": 0,
                       "trace_seconds": 0.2, "trace_min_units": 2,
                       "modules": {"train": ["jit_window"],
                                   "eval": ["jit_evaluate"]}},
           "seed": seed, "seconds": 0.2, "trace": False,
           "t_start": time.perf_counter(),
           "phases": Phases(time.perf_counter(), time.perf_counter),
           "out_dir": tempfile.mkdtemp(prefix="bench-rehearsal-"),
           "limits": LIMITS}
    out.update(over)
    return out


@pytest.mark.parametrize("chips", [1, 2])
def test_sound_run_is_correct(chips):
    from benchmark.drivers import train_tokens
    r = train_tokens.run(ctx(chips, seed=2 ** 31 + 11))
    assert r["correct"], r["compared"]
    units = r["window"].units
    assert r["attempted"] == 4 * len(units) and r["failed"] == 0
    assert all(u["images"] == 16 * chips for u in units)
    # the stream: every unit its own epoch, its own routed rows
    assert [u["epoch"] for u in units] == list(range(1, 1 + len(units)))
    assert all(u["moe_rows_local"] > 0 and u["tokens_masked"] > 0
               for u in units)
    assert r["counters"]["compiles_in_window"] == 0


def test_other_weights_seed_other_weights_same_verdict():
    """`weights_seed` pins the weights, `--seed` the data: the reference is
    given both and a run on other weights is as correct."""
    from benchmark.drivers import train_tokens
    c = ctx()
    c["traffic"]["weights_seed"] = 5
    r = train_tokens.run(c)
    assert r["correct"], r["compared"]


class Broken:
    """The trainer's window callable with a fault planted in what it
    returns (`lower`, which the trainer's warm-up calls, passes through)."""

    def __init__(self, window, fault):
        self.window, self.fault = window, fault
        self.lower = window.lower

    def __call__(self, state, ring, *rest):
        import jax
        kept = jax.tree.map(lambda x: x + 0, state)     # state is donated
        new_state, (buf, cnt) = self.window(state, ring, *rest)
        if self.fault == "freeze":
            return kept, (buf, cnt)
        return new_state, (buf.at[:, 0].multiply(1.01), cnt)  # loss_altered


def broken_trainer(fault):
    from benchmark.drivers import train_tokens

    def build(config, traffic, seed, telemetry, data_dir):
        t = train_tokens.build_trainer(config, traffic, seed, telemetry,
                                       data_dir)
        t.train_window_ring = Broken(t.train_window_ring, fault)
        return t
    return build


@pytest.mark.parametrize("fault", ["freeze", "loss_altered"])
def test_broken_timed_path_is_not_correct(fault):
    from benchmark.drivers import train_tokens
    r = train_tokens.run(ctx(build_trainer=broken_trainer(fault)))
    assert not r["correct"], r["compared"]


@pytest.mark.parametrize("fault", ["causal_mask", "drop_rows", "drop_half",
                                   "freeze"])
def test_fault_in_the_reference_reads_past_the_limits(fault):
    """A plain causal mask in place of the block-diffusion mask, rows over
    a capacity of 1.0 dropped in the expert layer, half of every step's
    sequences left out, a state left unchanged: planted in the reference
    put in the program's place."""
    from benchmark import correct
    from benchmark.drivers import train_tokens
    c = ctx()
    train, heldout = train_tokens.make_data(c["seed"], c["config"],
                                            c["traffic"], 1)
    args = (c["manifest"], c["cell"], c["config"], c["traffic"], c["seed"],
            train, heldout)
    sound = train_tokens.reference_record(*args)
    faulty = train_tokens.reference_record(*args, **{fault: True})
    ok, table = correct.decide(correct.numbers(faulty, sound)["numbers"],
                               LIMITS)
    assert not ok, table


def test_reference_in_the_precision_below_is_not_correct():
    """The control as the contract states it: the plain reference computed
    in bfloat16 throughout (weights and optimizer state too) in the
    program's place.  Its updates are below a bfloat16 weight's resolution,
    so the parameters' change is lost at any size."""
    from benchmark import correct
    from benchmark.drivers import train_tokens
    c = ctx()
    train, heldout = train_tokens.make_data(c["seed"], c["config"],
                                            c["traffic"], 1)
    args = (c["manifest"], c["cell"], c["config"], c["traffic"], c["seed"],
            train, heldout)
    sound = train_tokens.reference_record(*args)
    low = train_tokens.reference_record(*args, dtype="bfloat16")
    nums = correct.numbers(low, sound)["numbers"]
    ok, table = correct.decide(nums, LIMITS)
    assert not ok and nums["dparam3_leaf"] > 0.5, table


def test_program_bf16_path_is_not_correct():
    """The program's own bf16 path in the program's place (float32 master
    weights, bfloat16 activations).  Here, where a float32 matmul is exact;
    on the chip, whose default float32 matmul rounds its operands to
    bfloat16 anyway, that path reads like a sound run (PERF.md section 2)
    and the reference in bfloat16, above, is the control."""
    from benchmark.drivers import train_tokens

    def bf16(config, traffic, seed, telemetry, data_dir):
        return train_tokens.build_trainer(config, traffic, seed, telemetry,
                                          data_dir, precision="bf16")
    r = train_tokens.run(ctx(build_trainer=bf16))
    assert not r["correct"], r["compared"]
