"""Set-up as spans (train/loop.py ``trainer_init``, utils/compcache.py):
the trainer's construction and its phases, one ``xla_compile`` span and one
``programs_built`` count for every backend compile with the span that
caused it as parent, ``cached`` from the persistent cache, and nothing at
all registered where no enabled recorder exists.
"""

import json
import os
import subprocess
import sys

import pytest
from jax import monitoring

from cs744_ddp_tpu.obs import Telemetry
from cs744_ddp_tpu.utils import compcache

from test_loop_spans import make_trainer

TESTS = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(TESTS)
PHASES = ("load_splits", "init_state")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def test_trainer_init_and_every_compile_of_the_first_epoch(tmp_path, mesh4):
    compiles = []

    def count(event, secs, **kw):
        if event == COMPILE_EVENT:
            compiles.append(kw.get("fun_name"))
    monitoring.register_event_duration_secs_listener(count)
    try:
        tel = Telemetry()
        tr = make_trainer(tmp_path, mesh4, tel, 200)
        tr.train_model(0)
        tr.test_model()
    finally:
        monitoring.unregister_event_duration_listener(count)
    spans = [r for r in tel.records if r["kind"] == "span"]
    by_id = {s["id"]: s for s in spans}
    (init,) = [s for s in spans if s["name"] == "trainer_init"]
    assert "parent_id" not in init
    for name in PHASES:
        (phase,) = [s for s in spans if s["name"] == name]
        assert phase["parent_id"] == init["id"]
        assert init["t_ns"] <= phase["t_ns"]
    built = [s for s in spans if s["name"] == "xla_compile"]
    assert compiles and [s["program"] for s in built] == compiles
    for s in built:
        assert isinstance(s["cached"], bool)   # the suite's cache is on
        parent = by_id[s["parent_id"]]         # the span that caused it
        slack = 1_000_000                      # jax's clock against ours
        assert parent["t_ns"] - slack <= s["t_ns"]
        assert s["t_ns"] + s["dur_ns"] \
            <= parent["t_ns"] + parent["dur_ns"] + slack
    # (the state's small programs compile here only in a fresh process: the
    # probe below)
    parents = {by_id[s["parent_id"]]["name"] for s in built}
    assert {"compile_warmup", "eval_dispatch"} <= parents
    counted = [r for r in tel.records if r["kind"] == "counter"
               and r["name"] == "programs_built"]
    assert [(c["program"], c["cached"]) for c in counted] \
        == [(s["program"], s["cached"]) for s in built]
    assert tel.counter_totals()["programs_built"] == len(built)
    for name in ("jax_trace", "jax_lower"):
        assert [s for s in spans if s["name"] == name and s["program"]]


_PROBE = """
import json, os, sys
sys.path[:0] = [sys.argv[1], sys.argv[2]]
import jax
import jax._src.monitoring
from cs744_ddp_tpu import obs
from cs744_ddp_tpu.obs import NULL, Telemetry
from cs744_ddp_tpu.parallel import make_mesh
from cs744_ddp_tpu.train.loop import Trainer
from cs744_ddp_tpu.utils import compcache
from tinynet import tiny_cnn
compcache.enable_persistent_compilation_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

def epoch(telemetry, batch=64):
    tr = Trainer(model=tiny_cnn(), strategy="ddp", mesh=make_mesh(2),
                 global_batch=batch, data_dir=sys.argv[3], augment=False,
                 limit_train_batches=2, limit_eval_batches=1,
                 log=lambda s: None, telemetry=telemetry)
    tr.train_model(0)
    tr.test_model()

def registered():
    return compcache._listen_span in \\
        jax._src.monitoring.get_event_time_span_listeners()

out = {}
epoch(NULL, batch=32)    # other shapes: the cache stays cold for 64
out["null"] = {"registered": registered(), "log": len(obs.span_log())}
jax.clear_caches()
for k in range(2):
    tel = Telemetry()
    epoch(tel)
    spans = [r for r in tel.records if r["kind"] == "span"]
    names = {r["id"]: r["name"] for r in spans}
    built = [r for r in spans if r["name"] == "xla_compile"]
    out[f"cached{k}"] = [r.get("cached") for r in built]
    out[f"parents{k}"] = sorted({names.get(r.get("parent_id")) or "-"
                                 for r in built})
    jax.clear_caches()      # the next trainer goes to the persistent cache
out["registered"] = registered()
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def probe(tmp_path_factory):
    """One fresh interpreter (the suite's own has recorders attached and
    its cache placed): a trainer through NULL, then two with recorders on
    a persistent cache in a new directory."""
    tmp = tmp_path_factory.mktemp("setup_spans")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp / "cache"),
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    proc = subprocess.run(
        [sys.executable, "-c", _PROBE, REPO, TESTS, str(tmp / "data")],
        env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-1500:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_null_registers_no_listener_and_logs_nothing(probe):
    assert probe["null"] == {"registered": False, "log": 0}
    assert probe["registered"]          # the first recorder put it up


def test_a_warm_persistent_cache_reads_cached(probe):
    cold, warm = probe["cached0"], probe["cached1"]
    assert cold and False in cold        # compiled into an empty cache
    assert warm and set(warm) == {True}  # every program loaded
    # the state's initial values are small eager programs of their own
    for k in range(2):
        assert {"init_state", "compile_warmup", "eval_dispatch"} \
            <= set(probe[f"parents{k}"])


def test_a_recorder_without_jax_attaches_nothing(monkeypatch):
    monkeypatch.delitem(sys.modules, "jax")
    before = list(compcache._recorders)
    Telemetry()
    assert compcache._recorders == before
