"""The rest of a run without the look for a chip: the `train-epochs` driver
on tests/tinynet.py on the CPU mesh, sound and with the timed path broken
underneath.  Sound runs must come out correct; every fault a training cell
can have must come out NOT correct, and so must the lower-precision control.
"""

import json
import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))

LIMITS = {"loss1": 1e-4, "loss2": 1e-4, "loss3": 1e-4, "grad1_leaf": 1e-3,
          "dparam3_leaf": 1e-3, "eval_loss3": 1e-4}


def ctx(chips, seed=3, tmp=None, **over):
    import tempfile
    import time
    import tinynet
    from benchmark.window import Phases
    from cs744_ddp_tpu import models
    models.register_model("tiny", tinynet.tiny_cnn)
    manifest = {"configs": [{"name": "tiny-cifar-f32",
                             "file": "benchmark/tests/tiny-cifar-f32.json"}],
                "workloads": [], "end_to_end": [], "per_layer": []}
    config = json.load(open(os.path.join(HERE, "tiny-cifar-f32.json")))
    out = {"manifest": manifest,
           "cell": {"name": "tiny-train-cpu", "config": "tiny-cifar-f32",
                    "traffic": "rehearsal", "chips": chips},
           "config": config,
           "traffic": {"kind": "train-epochs", "strategy": "ddp",
                       "chips": chips, "warmup_units": 1,
                       "trace_seconds": 0.2, "trace_min_units": 2},
           "seed": seed, "seconds": 0.2, "trace": False,
           "t_start": time.perf_counter(),
           "phases": Phases(time.perf_counter(), time.perf_counter),
           "out_dir": tempfile.mkdtemp(prefix="bench-rehearsal-"),
           "limits": LIMITS}
    out.update(over)
    return out


@pytest.mark.parametrize("chips", [1, 4])
def test_sound_run_is_correct(chips):
    from benchmark.drivers import train_epochs
    r = train_epochs.run(ctx(chips, seed=2 ** 31 + 11))
    assert r["correct"], r["compared"]
    # 200 images per chip at batch 64: 3 full batches + a ragged tail of 8
    units = r["window"].units
    assert r["attempted"] == 4 * len(units) and r["failed"] == 0
    assert all(u["images"] == 200 * chips for u in units)
    assert r["counters"]["compiles_in_window"] == 0
    assert r["setup_s"] > 0 and r["window"].seconds >= 0.2


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
        if self.fault == "state_unchanged":
            return kept, (buf, cnt)
        return new_state, (buf.at[:, 0].multiply(1.01), cnt)  # loss_altered


def broken_trainer(fault):
    from benchmark.drivers import train_epochs

    def build(config, traffic, seed, telemetry, data_dir):
        t = train_epochs.build_trainer(config, traffic, seed, telemetry,
                                       data_dir)
        t.train_window_ring = Broken(t.train_window_ring, fault)
        return t
    return build


@pytest.mark.parametrize("fault", ["state_unchanged", "loss_altered"])
def test_broken_timed_path_is_not_correct(fault):
    from benchmark.drivers import train_epochs
    r = train_epochs.run(ctx(1, build_trainer=broken_trainer(fault)))
    assert not r["correct"], r["compared"]


@pytest.mark.parametrize("fault,chips", [("drop_half", 1), ("freeze", 1),
                                         ("skip_sync", 4)])
def test_fault_in_the_reference_reads_past_the_limits(fault, chips):
    """Half of the batch left out (the mean taken over the rest), a step
    that returns its state unchanged, and the exchange between chips left
    out, planted in the reference put in the program's place."""
    from benchmark import correct
    from benchmark.drivers import train_epochs
    c = ctx(chips)
    train, test = train_epochs.make_data(c["seed"], c["config"], chips)
    sound = train_epochs.reference_record(
        c["manifest"], c["cell"], c["config"], c["seed"], train, test)
    faulty = train_epochs.reference_record(
        c["manifest"], c["cell"], c["config"], c["seed"], train, test,
        **{fault: True})
    ok, table = correct.decide(correct.numbers(faulty, sound)["numbers"],
                               LIMITS)
    assert not ok, table


def test_lower_precision_control_is_not_correct():
    """The program's own bf16 path in the program's place."""
    from benchmark.drivers import train_epochs

    def bf16(config, traffic, seed, telemetry, data_dir):
        return train_epochs.build_trainer(config, traffic, seed, telemetry,
                                          data_dir, precision="bf16")
    r = train_epochs.run(ctx(1, build_trainer=bf16))
    assert not r["correct"], r["compared"]
