"""Structured telemetry subsystem (obs/): recorder, summary math, wiring.

Covers the whole contract the subsystem makes:

  * ``percentile`` / ``summarize_events`` against hand-computed values;
  * JSONL schema round-trip through a file-backed run directory
    (manifest.json / events.jsonl / summary.json);
  * span nesting, thread-local span stacks (the host-augment producer
    thread), and error capture;
  * the disabled path: ``NULL`` makes ZERO file writes and cannot
    accumulate per-step state (``__slots__ = ()``);
  * ``WindowedTimers`` emits step events ALONGSIDE the reference-parity
    print schedule, never instead of it;
  * Trainer wiring: manifest fields, compile_warmup/eval spans, collective
    counters, epoch gauges, host-augment pipeline spans and queue gauge;
  * the CLI ``--telemetry-out`` flag end to end, with the summary
    recomputed from the raw events and compared to summary.json;
  * the native-loader failure path surfacing in ``load_error()`` (what the
    manifest records);
  * tools/telemetry_report.py rendering, including the interrupted-run
    (no summary.json) recompute path.
"""

import builtins
import json
import os
import re
import threading

import pytest

from cs744_ddp_tpu import cli
from cs744_ddp_tpu.obs import (NULL, NULL_SPAN, NullTelemetry, Telemetry,
                               git_sha, percentile, read_run,
                               summarize_events)
from cs744_ddp_tpu.train.loop import Trainer
from cs744_ddp_tpu.utils.metrics import WindowedTimers

from tinynet import tiny_cnn


# -- percentile / summary math ------------------------------------------------

def test_percentile_hand_computed():
    xs = [4.0, 9.0, 1.0, 6.0, 10.0, 3.0, 7.0, 2.0, 8.0, 5.0]  # shuffled 1..10
    assert percentile(xs, 50) == pytest.approx(5.5)
    assert percentile(xs, 95) == pytest.approx(9.55)
    assert percentile(xs, 99) == pytest.approx(9.91)
    assert percentile(xs, 0) == 1.0
    assert percentile(xs, 100) == 10.0
    assert percentile([7.25], 95) == 7.25          # single sample
    with pytest.raises(ValueError):
        percentile([], 50)


def test_summarize_events_hand_computed():
    steady = [i / 1000.0 for i in range(1, 11)]    # 1..10 ms
    events = []
    for i, t in enumerate(steady):
        events.append({"kind": "step", "epoch": 0, "iter": i + 21,
                       "loss": float(i), "step_time_s": t, "steady": True})
    # Warmup steps: counted in num_steps and losses, NOT in steady stats.
    events.append({"kind": "step", "epoch": 0, "iter": 1, "loss": 99.0,
                   "step_time_s": 5.0, "steady": False})
    events.append({"kind": "span", "name": "host_augment",
                   "dur_ns": 500_000_000})
    events.append({"kind": "span", "name": "host_augment",
                   "dur_ns": 250_000_000})
    events.append({"kind": "counter", "name": "c", "inc": 2, "total": 2})
    events.append({"kind": "counter", "name": "c", "inc": 3, "total": 5})

    s = summarize_events(events, global_batch=64, note="extra-field")
    assert s["num_events"] == len(events)
    assert s["num_steps"] == 11
    assert s["num_steady_steps"] == 10
    stt = s["steady_step_time_s"]
    assert stt["p50"] == pytest.approx(0.0055)
    assert stt["p95"] == pytest.approx(0.00955)
    assert stt["p99"] == pytest.approx(0.00991)
    assert stt["min"] == 0.001 and stt["max"] == 0.010
    assert stt["mean"] == pytest.approx(sum(steady) / 10)
    assert s["steady_images_per_sec"] == \
        pytest.approx(64 * 10 / sum(steady))
    assert s["final_loss"] == 99.0                 # last step RECORDED
    assert s["mean_loss"] == pytest.approx((sum(range(10)) + 99.0) / 11)
    assert s["spans"]["host_augment"] == {"count": 2, "total_s": 0.75}
    assert s["counters"]["c"] == 5                 # final total, not the sum
    assert s["global_batch"] == 64 and s["note"] == "extra-field"


# -- recorder: file round-trip, spans, null path ------------------------------

def test_file_backed_round_trip(tmp_path):
    d = str(tmp_path / "run")
    tel = Telemetry(d)
    tel.write_manifest({"model": "tiny", "strategy": "ddp"})
    tel.step(epoch=0, iter=1, loss=2.5, step_time=0.01, steady=False)
    tel.step(epoch=0, iter=2, loss=1.5, step_time=0.02,
             forward_time=0.008, steady=True)
    tel.gauge("queue_depth", 3, window=1)
    tel.counter("bytes", inc=10)
    tel.counter("bytes", inc=5)
    with tel.span("eval"):
        pass
    summary = tel.finalize(global_batch=8)

    manifest, events, read_summary = read_run(d)
    assert manifest["schema_version"] == 1
    assert manifest["model"] == "tiny" and manifest["strategy"] == "ddp"
    assert "created_at" in manifest
    assert read_summary == summary

    # One JSON object per line, schema keys per kind.
    with open(os.path.join(d, "events.jsonl")) as f:
        lines = [json.loads(l) for l in f]
    assert lines == events
    by_kind = {}
    for e in events:
        by_kind.setdefault(e["kind"], []).append(e)
    assert {k: len(v) for k, v in by_kind.items()} == \
        {"step": 2, "gauge": 1, "counter": 2, "span": 1}
    for e in by_kind["step"]:
        assert {"t", "epoch", "iter", "loss", "step_time_s",
                "steady"} <= e.keys()
    assert by_kind["step"][1]["forward_time_s"] == 0.008
    assert by_kind["gauge"][0] == {"kind": "gauge", "name": "queue_depth",
                                   "t": by_kind["gauge"][0]["t"], "value": 3,
                                   "window": 1}
    assert [c["total"] for c in by_kind["counter"]] == [10, 15]
    # a span is written once: ids and nanosecond clocks, no duplicates
    assert {"name", "id", "t_ns", "dur_ns"} <= by_kind["span"][0].keys()
    assert not {"t", "dur_s", "depth", "parent"} & by_kind["span"][0].keys()

    assert summary["num_steps"] == 2 and summary["num_steady_steps"] == 1
    assert summary["counters"] == {"bytes": 15}
    assert summary["steady_step_time_s"]["p50"] == 0.02


def test_span_nesting_and_thread_local_stack():
    tel = Telemetry()                               # in-memory
    with tel.span("outer"):
        # Producer-thread spans must not inherit the main thread's stack.
        def worker():
            with tel.span("worker"):
                pass
        t = threading.Thread(target=worker)
        t.start()
        t.join()
        with tel.span("inner", window=3):
            pass
    recs = {r["name"]: r for r in tel.records if r["kind"] == "span"}
    assert "parent_id" not in recs["worker"]
    assert recs["inner"]["parent_id"] == recs["outer"]["id"]
    assert recs["inner"]["window"] == 3            # attrs pass through
    assert "parent_id" not in recs["outer"]
    assert all(r["dur_ns"] >= 0 for r in recs.values())


def test_span_records_error_and_reraises():
    tel = Telemetry()
    with pytest.raises(ValueError):
        with tel.span("boom"):
            raise ValueError("x")
    (rec,) = tel.records
    assert rec["error"] == "ValueError"


def test_null_recorder_makes_no_writes_and_holds_no_state(monkeypatch):
    assert isinstance(NULL, NullTelemetry)
    assert NULL.enabled is False
    assert NullTelemetry.__slots__ == ()
    # No attribute can ever be attached -> per-step state CANNOT grow.
    with pytest.raises(AttributeError):
        NULL.records = []

    opened = []
    real_open = builtins.open
    monkeypatch.setattr(builtins, "open",
                        lambda *a, **k: (opened.append(a),
                                         real_open(*a, **k))[1])
    for _ in range(50):
        NULL.step(epoch=0, iter=1, loss=1.0, step_time=0.1)
        NULL.gauge("g", 1)
        NULL.counter("c")
        with NULL.span("s"):
            pass
    NULL.write_manifest({"model": "x"})
    assert NULL.finalize(global_batch=64) is None
    assert opened == []                            # zero file writes
    # The span context manager is a shared singleton — no per-call alloc.
    assert NULL.span("a") is NULL.span("b") is NULL_SPAN
    # The chunked-staging spans ride the same path: attrs must not force
    # an allocation either (the producer thread calls these per chunk).
    assert NULL.span("chunk_put", batches=3, last=True) is NULL_SPAN
    assert NULL.span("chunk_wait") is NULL_SPAN
    NULL.gauge("window_chunks_pending", 2)         # still zero writes
    assert opened == []


def test_git_sha_returns_repo_head():
    sha = git_sha(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert sha is None or re.fullmatch(r"[0-9a-f]{40}", sha)
    assert git_sha("/") is None or isinstance(git_sha("/"), str)


# -- WindowedTimers: events alongside the parity prints -----------------------

def test_windowed_timers_emit_alongside_unchanged_prints():
    def drive(timers):
        for i in range(45):
            timers.record(0.5 + i, 0.01, 0.004)
        timers.record(99.0, 0.20, steady=False)    # ragged-tail sample

    plain_lines, tel_lines = [], []
    drive(WindowedTimers(plain_lines.append))
    tel = Telemetry()
    drive(WindowedTimers(tel_lines.append, telemetry=tel, epoch=2))

    # The parity surface: the print schedule is IDENTICAL with telemetry on.
    assert tel_lines == plain_lines
    assert any("Training loss after 20 iterations is" in l
               for l in plain_lines)

    steps = [r for r in tel.records if r["kind"] == "step"]
    assert len(steps) == 46
    assert [s["iter"] for s in steps] == list(range(1, 47))
    assert all(s["epoch"] == 2 for s in steps)
    # Steady flag mirrors the timers' own warmup/steady rules exactly.
    assert all(not s["steady"] for s in steps[:20])
    assert all(s["steady"] for s in steps[20:45])
    assert not steps[45]["steady"]
    assert steps[0]["forward_time_s"] == 0.004
    assert "forward_time_s" not in steps[45]


# -- Trainer wiring -----------------------------------------------------------

def _normalize(lines):
    """Blank out wall-clock values — the only nondeterministic content in
    the reference print schedule (loss lines are seed-deterministic)."""
    return [re.sub(r"is [0-9.e+-]+$", "is <t>", l) if "time" in l else l
            for l in lines]


def test_trainer_stdout_parity_and_event_stream(tmp_path, mesh4):
    def run(telemetry):
        lines = []
        tr = Trainer(model=tiny_cnn(), strategy="allreduce", mesh=mesh4,
                     global_batch=64, data_dir=str(tmp_path), augment=False,
                     limit_train_batches=25, limit_eval_batches=2,
                     log=lines.append, telemetry=telemetry)
        tr.run(1)
        return lines

    plain = run(NULL)
    tel = Telemetry()
    instrumented = run(tel)
    # Byte-identical print schedule modulo wall-clock values.
    assert _normalize(instrumented) == _normalize(plain)

    recs = tel.records
    steps = [r for r in recs if r["kind"] == "step"]
    assert len(steps) == 25
    assert [s["iter"] for s in steps] == list(range(1, 26))
    span_names = {r["name"] for r in recs if r["kind"] == "span"}
    assert "compile_warmup" in span_names
    assert "eval" in span_names
    gauge_names = {r["name"] for r in recs if r["kind"] == "gauge"}
    assert "epoch_time_s" in gauge_names
    # Static collective telemetry from the lowered step (emitted once).
    counter_names = {r["name"] for r in recs if r["kind"] == "counter"}
    assert any(n.startswith("collective_") for n in counter_names) or \
        "collective_stats_error" in gauge_names

    man = tel.manifest
    assert man["strategy"] == "allreduce"
    assert man["world_size"] == 4
    assert man["global_batch"] == 64
    assert set(man["native_loader"]) == {"available", "error"}
    for key in ("model", "jax_version", "backend", "device_kind",
                "precision", "git_sha", "seed"):
        assert key in man

    summary = tel.finalize(global_batch=64)
    assert summary["num_steps"] == 25
    assert 0 < summary["num_steady_steps"] <= 5    # beyond the warmup window
    stt = summary["steady_step_time_s"]
    assert stt["min"] <= stt["p50"] <= stt["p95"] <= stt["p99"] <= stt["max"]


def test_trainer_host_augment_pipeline_telemetry(tmp_path, mesh4):
    tel = Telemetry()
    tr = Trainer(model=tiny_cnn(), strategy="allreduce", mesh=mesh4,
                 global_batch=64, data_dir=str(tmp_path), augment=True,
                 host_augment=True, limit_train_batches=4,
                 log=lambda s: None, telemetry=tel)
    tr.train_model(0)
    spans = [r for r in tel.records if r["kind"] == "span"]
    by_name = {}
    for s in spans:
        by_name.setdefault(s["name"], []).append(s)
    # Producer-thread work is visible: the stochastic transform and the
    # per-chunk device puts (chunk_put superseded prefetch_put for staged
    # full batches when staging went chunked; prefetch_put remains on the
    # per-step tail path only).
    assert by_name["host_augment"]
    assert by_name["chunk_put"]
    assert all(s["batches"] >= 1 for s in by_name["chunk_put"])
    assert any(s["last"] for s in by_name["chunk_put"])  # window boundary
    # The producer thread has its own span stack: these are top-level.
    assert all("parent_id" not in s for s in by_name["host_augment"])
    assert all("parent_id" not in s for s in by_name["chunk_put"])
    # Consumer-side stall probe + pipeline gauges.
    assert by_name["chunk_wait"]
    depths = [r["value"] for r in tel.records
              if r["kind"] == "gauge" and r["name"] == "prefetch_queue_depth"]
    assert depths and all(d >= 0 for d in depths)
    pending = [r["value"] for r in tel.records
               if r["kind"] == "gauge" and r["name"] == "window_chunks_pending"]
    assert pending and all(p >= 1 for p in pending)


# -- CLI end to end -----------------------------------------------------------

def test_cli_telemetry_out_end_to_end(tmp_path, capsys, mesh4):
    """The acceptance path: a --telemetry-out run writes all three
    artifacts; the summary is exactly recomputable from the raw events; the
    reference-parity stdout schedule is unchanged."""
    out = str(tmp_path / "tel")
    cli.main(["--strategy", "ddp", "--model", "vgg11",
              "--batch-size", "64", "--num-devices", "4",
              "--epochs", "1", "--data-dir", str(tmp_path),
              "--limit-train-batches", "3", "--limit-eval-batches", "2",
              "--no-augment", "--telemetry-out", out])
    stdout = capsys.readouterr().out
    # The parity schedule — same asserts as the non-telemetry smoke test.
    assert "Size of training set is 782" in stdout
    assert "Training time after 1 epoch is" in stdout
    assert "Test set: Average loss:" in stdout
    assert out not in stdout                       # recorder prints nothing

    assert sorted(os.listdir(out)) == ["events.jsonl", "manifest.json",
                                       "summary.json"]
    manifest, events, summary = read_run(out)
    assert manifest["model"] == "vgg11"
    assert manifest["strategy"] == "ddp"
    assert manifest["world_size"] == 4
    assert manifest["global_batch"] == 64
    assert manifest["schema_version"] == 1

    kinds = {e["kind"] for e in events}
    assert kinds <= {"step", "span", "gauge", "counter"}
    steps = [e for e in events if e["kind"] == "step"]
    assert [s["iter"] for s in steps] == [1, 2, 3]
    assert all(s["epoch"] == 0 for s in steps)

    # summary.json is a pure function of the event log — recompute and
    # compare EXACTLY (percentile math included).
    assert summarize_events(events, global_batch=64) == summary


# -- native loader failure path (what the manifest surfaces) ------------------

def test_native_load_error_is_captured_and_warned(monkeypatch, tmp_path):
    from cs744_ddp_tpu.data import native
    monkeypatch.setattr(native, "_SO_PATH", str(tmp_path / "nope.so"))
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_load_attempted", False)
    monkeypatch.setattr(native, "_load_error", None)
    with pytest.warns(RuntimeWarning, match="native host loader unavailable"):
        assert native.load_library(build=False) is None
    assert native.available() is False
    assert "OSError" in native.load_error()
    # NumPy fallback still serves the data path while degraded.
    import numpy as np
    ds = np.arange(2 * 32 * 32 * 3, dtype=np.uint8).reshape(2, 32, 32, 3)
    np.testing.assert_array_equal(native.gather(ds, np.array([1, 0])),
                                  ds[[1, 0]])


# -- report tool --------------------------------------------------------------

def _make_run_dir(tmp_path):
    d = str(tmp_path / "run")
    tel = Telemetry(d)
    tel.write_manifest({"model": "tiny", "strategy": "ddp", "world_size": 4,
                        "global_batch": 64,
                        "native_loader": {"available": True, "error": None}})
    for i in range(1, 24):
        tel.step(epoch=0, iter=i, loss=2.0 / i, step_time=0.01,
                 steady=i > 20)
    tel.gauge("prefetch_queue_depth", 2)
    tel.counter("collective_all-reduce_count", 34)
    with tel.span("eval"):
        pass
    tel.finalize(global_batch=64)
    return d


def test_telemetry_report_renders_run_dir(tmp_path, monkeypatch, capsys):
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.syspath_prepend(os.path.join(repo, "tools"))
    import telemetry_report

    d = _make_run_dir(tmp_path)
    text = telemetry_report.render(d)
    assert "== run manifest ==" in text
    assert "tiny" in text and "ddp" in text
    assert "native_loader" in text and "available" in text
    assert "23 (3 steady)" in text
    assert "eval" in text
    assert "collective_all-reduce_count" in text
    assert "prefetch_queue_depth" in text

    assert telemetry_report.main([d, "--json"]) == 0
    parsed = json.loads(capsys.readouterr().out)
    assert parsed["num_steps"] == 23

    # Interrupted run: no summary.json — the report recomputes from events.
    os.remove(os.path.join(d, "summary.json"))
    text = telemetry_report.render(d)
    assert "23 (3 steady)" in text
    assert telemetry_report.main([d, "--json"]) == 0
    reparsed = json.loads(capsys.readouterr().out)
    assert reparsed["num_steady_steps"] == 3
    assert reparsed["global_batch"] == 64          # pulled from the manifest


def test_telemetry_report_renders_loop_section(tmp_path, monkeypatch):
    """``== loop ==``: per span name of the default windowed path its
    count, median, longest and total per epoch, and the spans that ran 30 ms
    or more over their name's median; absent where no such span was
    recorded."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.syspath_prepend(os.path.join(repo, "tools"))
    import telemetry_report

    ids = iter(range(1, 100))

    def record(name, dur_ms, epoch, parent=None):
        rec = {"kind": "span", "name": name, "id": next(ids),
               "t_ns": 10 ** 18 + epoch, "dur_ns": int(dur_ms * 1e6),
               "epoch": epoch}
        if parent:
            rec["parent_id"] = parent["id"]
        return rec

    events = []
    for epoch in range(4):
        train = record("epoch_train", 700, epoch)
        window = record("train_window", 650, epoch, train)
        events += [train, window,
                   record("window_dispatch", 2, epoch, window),
                   record("window_drain", 600, epoch, window),
                   record("window_host", 41.5 if epoch == 2 else 1.5, epoch,
                          train),
                   record("eval_fetch", 45, epoch)]
    events.append({"kind": "span", "name": "host_augment", "t_ns": 10 ** 9,
                   "dur_ns": 9 * 10 ** 9})  # not a span of this section
    lines = telemetry_report._loop_lines(events)
    assert lines[0] == "== loop (dispatch-loop spans, 4 epoch(s)) =="
    rows = {l.split()[0]: l.split() for l in lines[2:] if l.strip()}
    assert rows["window_host"][1:] == ["4", "1.500", "ms", "41.500", "ms",
                                       "11.500", "ms"]
    assert rows["window_drain"][1:] == ["4", "600.000", "ms", "600.000",
                                        "ms", "600.000", "ms"]
    assert "host_augment" not in rows
    (slow,) = [l for l in lines if l.startswith("  slow:")]
    assert slow == ("  slow: window_host 41.500 ms (+40.000 over its "
                    "median) epoch 2 parent epoch_train")
    # a run directory from a real recorder renders the section; one
    # without these spans renders as before
    d = str(tmp_path / "loop")
    tel = Telemetry(d)
    with tel.span("epoch_train", epoch=0):
        with tel.span("stage_lookup", epoch=0):
            pass
    tel.finalize()
    text = telemetry_report.render(d)
    assert "== loop (dispatch-loop spans, 1 epoch(s)) ==" in text
    assert "stage_lookup" in text.split("== loop")[1]
    assert telemetry_report._loop_lines(events[-1:]) == []
    assert telemetry_report._loop_lines([]) == []


def test_telemetry_report_renders_moe_section(monkeypatch):
    """``== moe ==``: per epoch the rows routed here against the even share,
    and the rows of the dropless buffer touched over them; "n/a" for a run
    recorded before the program counted the touched rows."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.syspath_prepend(os.path.join(repo, "tools"))
    import telemetry_report
    counter = lambda name, inc, epoch: {"kind": "counter", "name": name,
                                        "inc": inc, "epoch": epoch}
    events = [counter("moe_rows_local", 9000.0, 0),
              counter("moe_rows_expected", 8192.0, 0),
              counter("moe_rows_touched", 16384.0, 0),
              counter("tokens_masked", 2560.0, 0),
              counter("moe_rows_local", 8000.0, 1),
              counter("moe_rows_expected", 8192.0, 1)]
    lines = telemetry_report._moe_lines(events)
    assert lines[0] == "== moe (per epoch) =="
    assert lines[1] == ("  epoch 0: rows here 9,000 of 8,192 expected (share "
                        "1.0986), buffer rows touched 16,384 (touched / live "
                        "1.820), masked tokens 2,560")
    assert "touched / live n/a" in lines[2]
    assert telemetry_report._moe_lines(events[3:4]) != []
    assert telemetry_report._moe_lines([]) == []
