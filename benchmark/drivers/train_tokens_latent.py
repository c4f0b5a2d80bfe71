"""Traffic kind `train-tokens-latent`: `train_tokens.py`'s unit (an epoch of
the token stream through `Trainer.train_model(e)`, then `test_model()`) for
a decoder trained on plain next-token prediction, with everything that is
ONE MODEL'S read from the traffic file instead of held here as constants:

  share           {field of the model's Shape: configuration key}: what
                  this chip holds, as `models.get_model` takes it
  scopes          the named scopes the model's programs carry, inner scopes
                  first (an instruction belongs to the first scope its
                  op_name holds)
  kernel_scopes   {prefix of an instruction's name: scope}: kernels that
                  carry a name of their own
  counters        the program's counters a traced run's record keeps
  readers         the module whose `scope_instructions(text, scopes,
                  kernel_scopes)` classes a loaded module's instructions

The data, the trainer's construction on it, the unit, the reference's call
and the memory reading are `train_tokens.py`'s own functions, as for
`train_tokens_causal.py`, whose `run` this one is but for those five.  It
is meant as the LAST copy: a `benchmark` PR that folds the token drivers
into one (ROADMAP, the benchmark queue) has in this file the form the
other two cells' traffic files would take.

A cell of this kind brings what `train_tokens.py`'s docstring lists, with
`kind: train-tokens-latent` and the five keys above in its traffic file,
the reference's `follow` taking its model's faults, and limits from
`benchmark/calibrate_latent.py`.
"""

from __future__ import annotations

import gc
import importlib
import os
import time

from benchmark import correct as correctlib
from benchmark.drivers import train_epochs as base
from benchmark.drivers.train_tokens import (          # noqa: F401
    device_memory_peak, loaded_module_texts, make_data, make_unit,
    reference_record, trainer_on)
from benchmark.window import run_window

STEPS = base.STEPS


def share(config: dict, traffic: dict) -> dict:
    """What this chip holds, as `models.get_model` takes it."""
    out = {field: config[key] for field, key in traffic["share"].items()}
    return {k: tuple(v) if isinstance(v, list) else v for k, v in out.items()}


def build_trainer(config: dict, traffic: dict, seed: int, telemetry,
                  data_dir: str, precision: str = None):
    """The Trainer as cli.py's main() builds it for a decoder."""
    from cs744_ddp_tpu import models
    from cs744_ddp_tpu.ops import sgd
    from cs744_ddp_tpu.train.loop import Trainer
    opt = config["optimizer"]
    chips = traffic["chips"]
    if precision is None:
        precision = {"float32": "f32", "bfloat16": "bf16"}[config["dtype"]]
    return Trainer(
        model=models.get_model(config["model"], **share(config, traffic)),
        strategy=traffic["strategy"], num_devices=chips,
        global_batch=config["per_chip_batch"] * chips, data_dir=data_dir,
        seed=int(seed), init_seed=int(traffic["weights_seed"]),
        precision=precision,
        limit_train_batches=config["steps_per_epoch"],
        sgd_cfg=sgd.SGDConfig(lr=config["lr"], momentum=opt["momentum"],
                              weight_decay=opt["weight_decay"]),
        telemetry=telemetry, log=lambda msg: None)


def run(ctx: dict) -> dict:
    """One run of one cell; `ctx` and the result as `train_epochs.run`."""
    import jax
    from benchmark import trace as tracelib
    from benchmark.readers import lm
    from cs744_ddp_tpu.obs import NULL, Telemetry
    from cs744_ddp_tpu.utils import compcache

    cell, config, traffic = ctx["cell"], ctx["config"], ctx["traffic"]
    seed, trace = ctx["seed"], ctx["trace"]
    chips = cell["chips"]
    phases = ctx["phases"]
    compcache.enable_persistent_compilation_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles = base.CompileCounter().install()
    phases.mark("program_imports")

    train, heldout = make_data(seed, config, traffic, chips)
    phases.mark("data_from_seed")

    telemetry = Telemetry(None) if trace else NULL
    trainer = trainer_on(ctx.get("build_trainer", build_trainer), config,
                         traffic, seed, telemetry, train, heldout,
                         ctx["out_dir"])
    phases.mark("trainer_state")

    program = base.first_steps(trainer)
    phases.mark("first_steps_and_eval")

    sequences = config["per_chip_batch"] * chips * config["steps_per_epoch"]
    unit = make_unit(trainer, sequences, traffic["stream_units"],
                     jax.profiler.TraceAnnotation if trace else None)
    fence = lambda: jax.block_until_ready(trainer.state)
    warm = [unit(i) for i in range(traffic["warmup_units"])]
    fence()
    program["loss"] = warm[0]["first_losses"]
    phases.mark("warmup_units")

    probe = base.GcProbe()
    gc.callbacks.append(probe)
    c0 = compiles.snapshot()
    totals0 = dict(telemetry.counter_totals())
    seconds = ctx["seconds"]
    trace_dir = None
    if trace:
        seconds = min(seconds, traffic["trace_seconds"])
        trace_dir = os.path.join(ctx["out_dir"], f"trace-seed{seed}")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        window = run_window(
            unit, seconds, clock=time.perf_counter, fence=fence,
            first_index=len(warm),
            min_units=traffic["trace_min_units"] if trace else 1)
    finally:
        if trace:
            jax.profiler.stop_trace()
    gc.callbacks.remove(probe)
    c1 = compiles.snapshot()
    setup_s = window.t_open - ctx["t_start"]

    counters = {
        "compiles_in_window": c1[0] - c0[0],
        "compile_seconds_in_window": c1[1] - c0[1],
        "compiles_in_setup": c0[0], "compile_seconds_in_setup": c0[1],
        "cache": compcache.cache_stats(),
        "gc_in_window": probe.events,
    }
    devices = list(trainer.mesh.devices.flat)
    memory = device_memory_peak(devices)
    matmul_by_module = None
    if trace:
        totals = telemetry.counter_totals()
        for name in traffic["counters"]:
            counters[name] = totals.get(name, 0) - totals0.get(name, 0)
        texts = loaded_module_texts(devices)
        matmul_by_module = {name: lm.matmul_instructions(text)
                            for name, text in texts.items()}
        # run.py reads the trace after this returns and deletes it before a
        # reader runs: the time under each named scope is taken here
        readers = importlib.import_module(traffic["readers"])
        counters["scope_seconds"] = lm.scope_seconds(
            tracelib.read_xplane(tracelib.find_xplane(trace_dir)),
            {name: readers.scope_instructions(
                text, tuple(traffic["scopes"]), traffic["kernel_scopes"])
             for name, text in texts.items()},
            traffic["modules"]["train"])

    del unit, fence
    trainer = None
    gc.collect()
    t_ref = time.perf_counter()
    reference = reference_record(ctx["manifest"], cell, config, traffic,
                                 seed, train, heldout)
    compared = correctlib.numbers(program, reference)
    ok, table = correctlib.decide(
        compared["numbers"],
        ctx.get("limits") or base.load_limits(cell["name"]))
    reference_s = time.perf_counter() - t_ref

    return {
        "correct": ok, "compared": table,
        "compared_detail": compared,
        "program": {k: program[k] for k in
                    ("loss", "loss_single_steps", "eval_loss",
                     "eval_correct")},
        "reference": {k: reference[k] for k in
                      ("loss", "eval_loss", "eval_correct")},
        "attempted": int(window.total("steps")),
        "failed": int(window.total("failed")),
        "window": window, "warmup": warm, "setup_s": setup_s,
        "setup_breakdown": phases.rows, "reference_s": reference_s,
        "counters": counters, "memory_peak_bytes": memory["peak_bytes"],
        "memory": memory, "matmul_by_module": matmul_by_module,
        "trace_dir": trace_dir, "chips": chips,
    }
