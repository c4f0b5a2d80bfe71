"""Traffic kind `train-tokens`: repeated epochs of a token stream on one
`Trainer`.  It does for a decoder what `train_epochs.py` does for the image
models, on the same path: `train_model(e)` (staging of the epoch's own
sequences, the scanned window with the donated state and the metric ring,
the ring drain) and then `test_model()`.

A unit is one epoch: `steps_per_epoch` steps of `per_chip_batch` sequences
in one scanned window, then the evaluation on the held-out sequences.  An
"image" of `train_img_s_chip` is one SEQUENCE whose optimizer step
finished.  The stream never recurs: unit `e` trains on sequences
`n*e .. n*e + n - 1` of the seed's stream (benchmark/tokengen.py), handed to
the trainer the way a user hands over a tokenised corpus:
`<data_dir>/tokens/train.npy` and `heldout.npy`.

HOW TO ADD A TOKEN-TRAFFIC CELL (benchmark/README.md cannot say it yet):

  configs/<config>.json   the published config.json's keys (the catalog's
                          `config`, cuts listed under `reduced`), plus what
                          the run needs: `model` (a name of
                          cs744_ddp_tpu.models), `experts_held`, `seq_len`,
                          `block_length`, `per_chip_batch`,
                          `steps_per_epoch`, `heldout_sequences`, `lr`,
                          `optimizer`, `dtype`, `eval_key`, `published`,
                          `deployment`, `assumed`, and `layer_table`.
  layer_table             benchmark/flops.py's own "fc" rows, ONE SEQUENCE =
                          ONE IMAGE: each projection
                          ["fc", positions, 1, in, out, 1]; the two attention
                          products with the mean number of ALLOWED keys a
                          position as `in` and heads*head_dim as `out`; the
                          experts at the rows expected under even routing;
                          the head on the rows that reach it.
  configs/<config>.py     the plain reference: `follow(config, *, seed,
                          weights_seed, world, per_chip_batch, train,
                          heldout, steps, **faults)` -> the record
                          benchmark/correct.py compares.
  traffic/<name>.json     `kind: train-tokens`, `strategy`, `chips`,
                          `warmup_units`, `trace_seconds`, `trace_min_units`,
                          `modules`, `stream_units` (how many units of data
                          a run generates; a run that needs more fails
                          rather than recur) and `weights_seed`: EVERY run's
                          initial weights come from this seed, and `--seed`
                          draws the tokens, the noising and nothing else, so
                          that a cell's rate does not move with its router's
                          lean (PERF.md section 6, PR 29).
  limits/<cell>.json      what `benchmark/calibrate_tokens.py <cell> --limits
                          --write` derives from its studies on the chip; no
                          value in it is set by hand.

What it uses of the program, besides what `train_epochs.py` lists:
`models.get_model(name, **share)`, `Trainer(init_seed=...,
limit_train_batches=...)`, `.last_epoch_extras`, `.objective`,
`data.tokens` through `data_dir`, and the named scopes `attn_blockdiff`,
`moe_route`, `moe_experts`, `lm_head` in the compiled modules' metadata.
"""

from __future__ import annotations

import gc
import os
import shutil
import time

import numpy as np

from benchmark import correct as correctlib
from benchmark import manifest as mf, tokengen
from benchmark.drivers import train_epochs as base
from benchmark.window import run_window

STEPS = base.STEPS
SCOPES = ("attn_blockdiff", "moe_route", "moe_experts", "lm_head")


def make_data(seed: int, config: dict, traffic: dict, chips: int):
    """(the stream's first `stream_units` units [N, L], held-out [M, L])."""
    per_unit = config["per_chip_batch"] * chips * config["steps_per_epoch"]
    return (tokengen.make_stream(seed, per_unit * traffic["stream_units"],
                                 config["seq_len"], config["vocab_size"]),
            tokengen.make_heldout(seed, config["heldout_sequences"] * chips,
                                  config["seq_len"], config["vocab_size"]))


def share(config: dict) -> dict:
    """What this chip holds, as `models.get_model` takes it."""
    return dict(layers=config["num_hidden_layers"],
                held=tuple(config["experts_held"]),
                vocab=config["vocab_size"], seq_len=config["seq_len"],
                block=config["block_length"])


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
        model=models.get_model(config["model"], **share(config)),
        strategy=traffic["strategy"], num_devices=chips,
        global_batch=config["per_chip_batch"] * chips, data_dir=data_dir,
        seed=int(seed), init_seed=int(traffic["weights_seed"]),
        precision=precision,
        limit_train_batches=config["steps_per_epoch"],
        sgd_cfg=sgd.SGDConfig(lr=config["lr"], momentum=opt["momentum"],
                              weight_decay=opt["weight_decay"]),
        telemetry=telemetry, log=lambda msg: None)


def trainer_on(build, config, traffic, seed, telemetry, train, heldout,
               scratch_dir: str):
    """Build the trainer on the generated tokens (written as the files its
    loader reads, deleted afterwards: the train file is memory-mapped, and
    an unlinked file stays readable) and check it holds exactly those."""
    root = os.path.join(scratch_dir, f"data-seed{seed}-{os.getpid()}")
    shutil.rmtree(root, ignore_errors=True)
    os.makedirs(os.path.join(root, "tokens"))
    try:
        np.save(os.path.join(root, "tokens", "train.npy"), train)
        np.save(os.path.join(root, "tokens", "heldout.npy"), heldout)
        trainer = build(config, traffic, seed, telemetry, root)
    finally:
        shutil.rmtree(root, ignore_errors=True)
    if not (trainer.real_data
            and np.array_equal(trainer.train_split.tokens, train)
            and np.array_equal(trainer.test_split.tokens, heldout)):
        raise RuntimeError("the trainer does not hold the generated tokens")
    return trainer


def make_unit(trainer, sequences: int, stream_units: int, annotate=None):
    """`train_epochs.make_unit` plus the objective's totals of the epoch
    (routed rows, masked tokens) from the ring the epoch drained anyway."""
    unit = base.make_unit(trainer, sequences, annotate)

    def with_extras(i: int) -> dict:
        if i >= stream_units:
            raise RuntimeError(
                f"unit {i}: the run's stream holds {stream_units} units "
                f"(traffic `stream_units`); its data would recur")
        rec = unit(i)
        rec.update(trainer.last_epoch_extras)
        return rec
    return with_extras


def reference_record(manifest: dict, cell: dict, config: dict, traffic: dict,
                     seed: int, train, heldout, **faults) -> dict:
    mod = mf.load_module_from_path(
        mf.reference_path(manifest, cell["config"]),
        "benchmark_reference_"
        + cell["config"].replace("-", "_").replace(".", "_"))
    return mod.follow(config, seed=int(seed),
                      weights_seed=int(traffic["weights_seed"]),
                      world=cell["chips"],
                      per_chip_batch=config["per_chip_batch"],
                      train=train, heldout=heldout, steps=STEPS, **faults)


def device_memory_peak(devices) -> dict:
    """Bytes the fullest chip holds WHILE A WINDOW RUNS: the live buffers
    at the window's close (state, staged epoch, held-out set) plus the
    largest scratch a running program reserved (`peak_bytes_reserved`).
    `train_epochs.device_memory_peak` adds the allocator's
    `peak_bytes_in_use` instead, which here is the set-up's: `first_steps`
    holds the seed's 5.2 GB state twice for a moment while it puts it back
    (10.3 GB), with no program running, and that sum (19.0 GB) is more than
    the chip has."""
    stats = [d.memory_stats() or {} for d in devices]
    peak = max(s.get("bytes_in_use", 0) + s.get("peak_bytes_reserved", 0)
               for s in stats)
    return {"peak_bytes": int(peak), "allocator": stats[0]}


def loaded_module_texts(devices) -> dict:
    """{module name: HLO text} of every program loaded on the device."""
    out = {}
    for ex in devices[0].client.live_executables():
        for m in ex.hlo_modules():
            out[m.name] = out.get(m.name, "") + "\n" + m.to_string()
    return out


def run(ctx: dict) -> dict:
    """One run of one cell; `ctx` and the result as `train_epochs.run`."""
    import jax
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
        for name in ("host_round_trips", "moe_rows_local",
                     "moe_rows_expected", "tokens_masked"):
            counters[name] = totals.get(name, 0) - totals0.get(name, 0)
        texts = loaded_module_texts(devices)
        matmul_by_module = {name: lm.matmul_instructions(text)
                            for name, text in texts.items()}
        # run.py reads the trace after this returns and deletes it before a
        # reader runs: the time under each named scope is taken here
        from benchmark import trace as tracelib
        counters["scope_seconds"] = lm.scope_seconds(
            tracelib.read_xplane(tracelib.find_xplane(trace_dir)),
            {name: lm.scope_instructions(text, SCOPES)
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
