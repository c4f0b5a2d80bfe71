"""Traffic kind `train-epochs`: repeated epochs on one `Trainer`.

A unit is one epoch as `Trainer.run` runs it: `train_model(e)` (staging
lookup, every 20-iteration window with its ring drain, the ragged tail
step) and then `test_model()`.  The trainer is built as `cli.py` builds it.

This module is the one place that touches the program.  What it uses, so a
later PR knows what the cells hold fixed:

  public    Trainer(...) keywords of cli.py, .train_model(epoch) ->
            timers with .losses, .test_model() -> (loss, correct, acc),
            .state (params / bn_state / opt_state.momentum), .train_split /
            .test_split setters, cifar10.Split, sgd.SGDConfig,
            obs.Telemetry(None).counter_totals(),
            compcache.enable_persistent_compilation_cache()
  internal  (the first three steps only, `first_steps`):
            ._stage_train_epoch(0), ._make_ring_device(),
            .train_window_ring called with a one-step length array, .seed
            (the default path: device-side augmentation, metric ring on)

Set-up builds ONE trainer, drives it from the seed through its first three
steps on the window's own callable and staged feed (one step a call, since
a scanned window hides the states between its steps), evaluates, puts the
seed's initial state back, runs the warm-up units (whose first three
losses, from the timed window program itself, are compared too) and hands
that same trainer to the window.
"""

from __future__ import annotations

import contextlib
import gc
import math
import os
import pickle
import shutil
import time

import numpy as np

from benchmark import correct as correctlib
from benchmark import datagen, manifest as mf
from benchmark.window import run_window

STEPS = 3


class CompileCounter:
    """Counts XLA compile requests (a persistent-cache hit is one too: a
    program that first meets a shape inside the window shows either way)."""

    def __init__(self):
        self.count = 0
        self.seconds = 0.0

    def install(self):
        from jax import monitoring
        from jax._src import dispatch
        event = dispatch.BACKEND_COMPILE_EVENT

        def listen(name, secs, **kw):
            if name == event:
                self.count += 1
                self.seconds += secs
        monitoring.register_event_duration_secs_listener(listen)
        return self

    def snapshot(self):
        return self.count, self.seconds


class GcProbe:
    """Garbage collections of the interpreter while it is in
    `gc.callbacks`: a full collection over a JAX process's heap stops the
    host for tens of milliseconds, so the per-run file says when a slow
    unit was one."""

    def __init__(self):
        self.events = []        # [generation, seconds]
        self._t0 = None

    def __call__(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.events.append([info["generation"],
                                time.perf_counter() - self._t0])
            self._t0 = None


def device_memory_peak(devices) -> dict:
    """Peak bytes on the fullest chip, from its allocator.  On this runtime
    `peak_bytes_in_use` holds the live buffers (state, staged epoch, eval
    set: 457 MB for vgg11) and the scratch a running program owns is kept
    apart under `peak_bytes_reserved` (7.05 GB, the compiled window's
    `temp`); the chip holds both while the window runs, and the allocator's
    own `largest_free_block_bytes` is the limit less their sum."""
    stats = [d.memory_stats() or {} for d in devices]
    peak = max(s.get("peak_bytes_in_use", 0) + s.get("peak_bytes_reserved", 0)
               for s in stats)
    return {"peak_bytes": int(peak), "allocator": stats[0]}


def loaded_modules_matmul(devices) -> dict:
    """{module name: instructions that hold a convolution or a dot}, from
    the text of every program loaded on the device (trace.py)."""
    from benchmark import trace as tracelib
    out = {}
    for ex in devices[0].client.live_executables():
        for m in ex.hlo_modules():
            out.setdefault(m.name, set()).update(
                tracelib.matmul_instructions(m.to_string()))
    return out


def make_data(seed: int, config: dict, chips: int):
    """((train images, labels), (test images, labels)) from the seed."""
    return (datagen.make_split(
                seed, config["train_images_per_chip"] * chips, 0),
            datagen.make_split(
                seed, config["test_images_per_chip"] * chips, 1))


def write_cifar_dir(root: str, train, test) -> None:
    """The generated inputs as the python-pickle batches the program's
    loader reads (`cifar-10-batches-py`: five train files and one test
    file, rows of 3072 bytes in CHW order): the trainer gets its data the
    way a user with the real set hands it over, through `data_dir`, and
    does not generate its own synthetic stand-in (15-25 s that would serve
    no request)."""
    d = os.path.join(root, "cifar-10-batches-py")
    os.makedirs(d)

    def dump(name, images, labels):
        rows = np.ascontiguousarray(images.transpose(0, 3, 1, 2)).reshape(
            len(labels), 3072)
        with open(os.path.join(d, name), "wb") as f:
            pickle.dump({b"data": rows, b"labels": labels.tolist()}, f,
                        protocol=4)
    n = len(train[1])
    per = -(-n // 5)
    for i in range(5):
        dump(f"data_batch_{i + 1}", train[0][i * per:(i + 1) * per],
             train[1][i * per:(i + 1) * per])
    dump("test_batch", *test)


def build_trainer(config: dict, traffic: dict, seed: int, telemetry,
                  data_dir: str, precision: str = None):
    """The Trainer as cli.py's main() builds it, for this configuration."""
    from cs744_ddp_tpu.ops import sgd
    from cs744_ddp_tpu.train.loop import Trainer
    opt = config["optimizer"]
    chips = traffic["chips"]
    if precision is None:
        precision = {"float32": "f32", "bfloat16": "bf16"}[config["dtype"]]
    return Trainer(
        model=config["model"], strategy=traffic["strategy"],
        num_devices=chips, global_batch=config["per_chip_batch"] * chips,
        data_dir=data_dir, seed=int(seed), augment=True, precision=precision,
        sgd_cfg=sgd.SGDConfig(lr=config["lr"], momentum=opt["momentum"],
                              weight_decay=opt["weight_decay"]),
        telemetry=telemetry, log=lambda msg: None)


def trainer_on(build, config, traffic, seed, telemetry, train, test,
               scratch_dir: str):
    """Build the trainer on the generated inputs (written for its loader,
    read back by it, deleted) and check it holds exactly those."""
    root = os.path.join(scratch_dir, f"data-seed{seed}-{os.getpid()}")
    shutil.rmtree(root, ignore_errors=True)
    try:
        write_cifar_dir(root, train, test)
        trainer = build(config, traffic, seed, telemetry, root)
    finally:
        shutil.rmtree(root, ignore_errors=True)
    if not (trainer.real_data
            and np.array_equal(trainer.train_split.images, train[0])
            and np.array_equal(trainer.train_split.labels, train[1])
            and np.array_equal(trainer.test_split.images, test[0])
            and np.array_equal(trainer.test_split.labels, test[1])):
        raise RuntimeError("the trainer does not hold the generated inputs")
    return trainer


def tree_norms(tree) -> dict:
    import jax
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(k): float(np.sqrt(np.sum(np.square(
        np.asarray(v, np.float64))))) for k, v in flat}


def first_steps(trainer) -> dict:
    """Drive the trainer's own window callable through the first STEPS
    steps of epoch 0, one step a call, on its own staged feed; evaluate;
    put the initial state back.  Returns the program's half of the record
    correct.py compares (the losses are replaced later by the timed
    window's own)."""
    import jax
    import jax.numpy as jnp
    state0 = jax.device_get(trainer.state)
    imgs, labs, _tail = trainer._stage_train_epoch(0)
    key = jax.random.fold_in(jax.random.PRNGKey(trainer.seed), 0)
    one = jnp.zeros((1,), jnp.int8)
    ring = trainer._make_ring_device()
    rec = {}
    for k in range(STEPS):
        trainer.state, ring = trainer.train_window_ring(
            trainer.state, ring, key, imgs, labs, jnp.int32(k), one)
        if k == 0:
            rec["momentum1_norms"] = tree_norms(
                jax.device_get(trainer.state.opt_state.momentum))
    losses = [float(x) for x in np.asarray(ring[0])[:STEPS, 0]]
    p3 = jax.device_get(trainer.state.params)
    rec["dparam_norms"] = tree_norms(
        jax.tree.map(lambda a, b: np.asarray(a, np.float64) - b,
                     p3, state0.params))
    rec["loss_single_steps"] = losses
    rec["eval_loss"], rec["eval_correct"], _ = trainer.test_model()
    trainer.state = jax.tree.map(
        lambda h, d: jax.device_put(h, d.sharding), state0, trainer.state)
    return rec


def make_unit(trainer, images: int, annotate=None):
    """unit(i): epoch i on the trainer.  Train and eval seconds apart."""
    clock = time.perf_counter

    def span(name):
        return annotate(name) if annotate else contextlib.nullcontext()

    def unit(i: int) -> dict:
        t0 = clock()
        with span("train_model"):
            timers = trainer.train_model(i)
        t1 = clock()
        with span("test_model"):
            trainer.test_model()
        t2 = clock()
        losses = timers.losses
        return {"epoch": i, "train_s": t1 - t0, "eval_s": t2 - t1,
                "steps": len(losses),
                "images": images,
                "failed": sum(1 for x in losses if not math.isfinite(x)),
                "first_losses": [float(x) for x in losses[:STEPS]]}
    return unit


def images_per_epoch(trainer) -> int:
    """Images whose optimizer step an epoch finishes: every row the
    sampler deals (the wrap-padded split), full batches and ragged tail."""
    n = len(trainer.train_split.labels)
    return -(-n // trainer.world) * trainer.world


def reference_record(manifest: dict, cell: dict, config: dict, seed: int,
                     train, test, **faults) -> dict:
    from benchmark.reference import common as ref
    mod = mf.load_module_from_path(
        mf.reference_path(manifest, cell["config"]),
        "benchmark_reference_" + cell["config"].replace("-", "_").replace(".", "_"))
    init_fn, apply_fn = mod.make(config)
    return ref.follow(init_fn, apply_fn, config, seed=int(seed),
                      world=cell["chips"],
                      per_chip_batch=config["per_chip_batch"],
                      train=train, test=test, steps=STEPS, **faults)


def load_limits(cell_name: str) -> dict:
    return mf.load_json(
        os.path.join(mf.HERE, "limits", cell_name + ".json"))["limits"]


def run(ctx: dict) -> dict:
    """One run of one cell.  `ctx`: manifest, cell, config, traffic, seed,
    seconds, trace, t_start, phases, out_dir.  Returns what run.py prints."""
    import jax
    from cs744_ddp_tpu.obs import NULL, Telemetry
    from cs744_ddp_tpu.utils import compcache

    cell, config, traffic = ctx["cell"], ctx["config"], ctx["traffic"]
    seed, trace = ctx["seed"], ctx["trace"]
    chips = cell["chips"]
    phases = ctx["phases"]
    # The program's cache set-up (JAX_COMPILATION_CACHE_DIR if set, else
    # <checkout>/.jax_cache), then every program persisted, however fast it
    # compiled: three of five sat under the program's 2 s threshold and
    # recompiled on every warm start (PERF.md).
    compcache.enable_persistent_compilation_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles = CompileCounter().install()
    phases.mark("program_imports")

    train, test = make_data(seed, config, chips)
    phases.mark("data_from_seed")

    telemetry = Telemetry(None) if trace else NULL
    trainer = trainer_on(ctx.get("build_trainer", build_trainer), config,
                         traffic, seed, telemetry, train, test,
                         ctx["out_dir"])
    phases.mark("trainer_state")

    program = first_steps(trainer)
    phases.mark("first_steps_and_eval")

    unit = make_unit(trainer, images_per_epoch(trainer),
                     jax.profiler.TraceAnnotation if trace else None)
    fence = lambda: jax.block_until_ready(trainer.state)
    warm = [unit(i) for i in range(traffic["warmup_units"])]
    fence()
    program["loss"] = warm[0]["first_losses"]
    phases.mark("warmup_units")

    probe = GcProbe()
    gc.callbacks.append(probe)
    c0 = compiles.snapshot()
    rt0 = telemetry.counter_totals().get("host_round_trips", 0)
    seconds = ctx["seconds"]
    trace_dir = None
    if trace:
        seconds = min(seconds, traffic["trace_seconds"])
        trace_dir = os.path.join(ctx["out_dir"], f"trace-seed{seed}")
        # device and host spans only: Python's own call tracer would log
        # every function the trainer enters and slow the host it measures
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
    if trace:
        counters["host_round_trips"] = \
            telemetry.counter_totals().get("host_round_trips", 0) - rt0
    devices = list(trainer.mesh.devices.flat)
    memory = device_memory_peak(devices)
    matmul_by_module = loaded_modules_matmul(devices) if trace else None

    # The reference runs only now: the window has closed, the peak has been
    # read, and the program's state and staged data are freed first.
    del unit, fence
    trainer = None
    gc.collect()
    t_ref = time.perf_counter()
    reference = reference_record(ctx["manifest"], cell, config, seed,
                                 train, test)
    compared = correctlib.numbers(program, reference)
    ok, table = correctlib.decide(
        compared["numbers"], ctx.get("limits") or load_limits(cell["name"]))
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
