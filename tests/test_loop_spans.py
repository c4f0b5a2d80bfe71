"""The dispatch loop's spans (train/loop.py default path, test_model) and
the span record (obs/telemetry.py): the tree an epoch yields, the counters
at the same boundaries, the record's clocks, the twin in the profiler's
trace, the process-wide span log, and the disabled path.
"""

import threading

import pytest

from cs744_ddp_tpu import obs
from cs744_ddp_tpu.data import cifar10
from cs744_ddp_tpu.obs import NULL, NULL_SPAN, Telemetry, span_log, telemetry
from cs744_ddp_tpu.train.loop import Trainer

from tinynet import tiny_cnn

# name -> parent name, for one epoch of the default path (README
# "Observability"); compile_warmup appears only where an epoch compiles.
TRAIN_TREE = {
    "epoch_train": None,
    "stage_lookup": "epoch_train",
    "ring_alloc": "epoch_train",
    "train_window": "epoch_train",
    "window_dispatch": "train_window",
    "window_drain": "train_window",
    "window_host": "epoch_train",
    "obs_emit": "window_host",
    "tail_step": "epoch_train",
    "tail_dispatch": "tail_step",
    "tail_fetch": "tail_step",
}
EVAL_TREE = {
    "eval": None,
    "eval_stage_lookup": "eval",
    "eval_dispatch": "eval",
    "eval_fetch": "eval",
}
WARMUP_PARENTS = {"stage_lookup", "tail_dispatch"}


def make_trainer(tmp_path, mesh, recorder, n_train, **kw):
    tr = Trainer(model=tiny_cnn(), strategy="ddp", mesh=mesh,
                 global_batch=64, data_dir=str(tmp_path), augment=False,
                 limit_eval_batches=2, log=lambda s: None,
                 telemetry=recorder, **kw)
    tr.train_split = cifar10.Split(tr.train_split.images[:n_train],
                                   tr.train_split.labels[:n_train])
    return tr


def spans_of(recorder, epoch=None):
    return [r for r in recorder.records if r["kind"] == "span"
            and (epoch is None or r.get("epoch", -1) == epoch)]


def counts(recorder, name, epoch):
    return sum(r["inc"] for r in recorder.records
               if r["kind"] == "counter" and r["name"] == name
               and r.get("epoch") == epoch)


# 25 batches: windows of 20 and 5, no tail.  200 images: one window of 3 and
# a ragged tail of 8.
@pytest.mark.parametrize("n_train,windows,tail", [(64 * 25, 2, False),
                                                  (200, 1, True)])
@pytest.mark.parametrize("ring", [20, 0])
def test_epoch_yields_exactly_the_span_tree(tmp_path, mesh4, n_train, windows,
                                            tail, ring):
    tel = Telemetry()
    tr = make_trainer(tmp_path, mesh4, tel, n_train, metrics_ring=ring)
    for epoch in (0, 1):            # epoch 0 compiles, epoch 1 does not
        tr.train_model(epoch)
        tr.test_model()
    for epoch in (0, 1):
        spans = spans_of(tel, epoch)
        by_id = {s["id"]: s for s in spans}
        assert len(by_id) == len(spans)
        expect = dict(TRAIN_TREE, **EVAL_TREE)
        if not tail:
            expect = {k: v for k, v in expect.items()
                      if not k.startswith("tail_")}
        if not ring:
            del expect["ring_alloc"]
        names = [s["name"] for s in spans]
        if epoch == 1:
            assert "compile_warmup" not in names
        assert set(names) - {"compile_warmup"} == set(expect)
        for s in spans:
            parent = by_id.get(s.get("parent_id"))
            if s["name"] == "compile_warmup":
                assert parent["name"] in WARMUP_PARENTS
                continue
            if s["name"] == "obs_emit" and parent["name"] == "epoch_train":
                assert epoch == 0       # the collective statistics, once
                continue
            want = expect[s["name"]]
            if want is None:
                assert "parent_id" not in s
                continue
            assert parent["name"] == want
            assert parent["id"] < s["id"]
            # children lie inside their parents in time
            assert parent["t_ns"] <= s["t_ns"]
            assert s["t_ns"] + s["dur_ns"] \
                <= parent["t_ns"] + parent["dur_ns"]
        per_window = ("train_window", "window_dispatch", "window_drain",
                      "window_host", "obs_emit")
        for name in expect:
            assert names.count(name) == (windows if name in per_window else 1) \
                + (name == "obs_emit" and epoch == 0)
        # one program in flight at a time: dispatches and fetches alternate
        marks = [n for n in names if n.endswith(("_dispatch", "_drain",
                                                 "_fetch"))]
        assert marks == ["window_dispatch", "window_drain"] * windows \
            + ["tail_dispatch", "tail_fetch"] * tail \
            + ["eval_dispatch", "eval_fetch"]
        # the counters at the same boundaries: a checked identity
        n = windows + tail + 1
        assert counts(tel, "dispatches", epoch) == n
        assert counts(tel, "host_round_trips", epoch) == n
    totals = tel.counter_totals()
    assert totals["dispatches"] == totals["host_round_trips"] \
        == 2 * (windows + tail + 1)
    sites = {r["site"] for r in tel.records
             if r["kind"] == "counter" and r["name"] == "dispatches"}
    assert sites == {"window", "eval"} | ({"tail"} if tail else set())


def test_train_window_keeps_its_attributes_and_eval_its_units_epoch(
        tmp_path, mesh4):
    tel = Telemetry()
    tr = make_trainer(tmp_path, mesh4, tel, 200)
    tr.test_model()                 # before any train_model: no epoch yet
    assert {s["epoch"] for s in spans_of(tel) if "epoch" in s} == {None}
    tr.train_model(7)
    tr.test_model()
    (win,) = [s for s in spans_of(tel, 7) if s["name"] == "train_window"]
    assert (win["strategy"], win["start"], win["batches"]) == ("ddp", 0, 3)
    assert [s["name"] for s in spans_of(tel, 7)][-4:] == [
        "eval_stage_lookup", "eval_dispatch", "eval_fetch", "eval"]


def test_span_record_fields_and_clocks():
    import time
    tel = Telemetry()
    before = time.time_ns()
    with tel.span("outer", epoch=3):
        with tel.span("inner"):
            time.sleep(0.002)
    tel.span_event("waited", time.time(), 0.25, trace_id=9)
    with tel.span("open"):          # an interval recorded after the fact
        tel.span_event("over", time.time(), 0.001)  # under the open span
    after = time.time_ns()
    inner, outer, event, over, open_ = spans_of(tel)
    assert (outer["id"], inner["id"], event["id"]) == (1, 2, 3)
    assert inner["parent_id"] == 1
    assert "parent_id" not in outer and "parent_id" not in event
    assert over["parent_id"] == open_["id"]
    for s in (inner, outer, event, over, open_):
        assert isinstance(s["t_ns"], int) and isinstance(s["dur_ns"], int)
        assert before <= s["t_ns"] <= after          # the Unix clock
        # written once: no seconds, depth or parent name beside them
        assert not {"t", "dur_s", "depth", "parent"} & s.keys()
    assert inner["dur_ns"] >= 2_000_000
    assert outer["dur_ns"] >= inner["dur_ns"]
    assert event["dur_ns"] == 250_000_000 and event["trace_id"] == 9
    # ids are per recorder
    other = Telemetry()
    with other.span("x"):
        pass
    assert spans_of(other)[0]["id"] == 1


def test_span_ends_never_run_backwards_within_a_thread(tmp_path, mesh4):
    tel = Telemetry()
    tr = make_trainer(tmp_path, mesh4, tel, 200)

    def worker():                   # another thread has its own order
        for _ in range(50):
            with tel.span("worker", epoch=-1):
                pass
    t = threading.Thread(target=worker)
    t.start()
    tr.train_model(0)
    tr.test_model()
    t.join()
    # the loop's own spans (jax's compile spans carry jax's clock)
    for mine in (True, False):
        ends = [s["t_ns"] + s["dur_ns"] for s in spans_of(tel)
                if "epoch" in s and (s["name"] == "worker") != mine]
        assert len(ends) >= 15
        assert ends == sorted(ends)   # records are emitted as spans close


def test_span_opens_a_twin_of_the_same_name_in_the_profiler(monkeypatch):
    import jax
    seen = []

    class Twin:
        def __init__(self, name, **kw):
            self.name = name
            seen.append(("init", name, kw))

        def __enter__(self):
            seen.append(("enter", self.name))

        def __exit__(self, *exc):
            seen.append(("exit", self.name))

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Twin)
    tel = Telemetry()
    with tel.span("window_dispatch", epoch=1):
        seen.append(("body",))
    tel.span_event("over", 1.0, 0.5)    # an interval that is over: no twin
    assert seen == [("init", "window_dispatch", {"span_id": 1}),
                    ("enter", "window_dispatch"), ("body",),
                    ("exit", "window_dispatch")]
    # no jax in the process (the report tool): no twin, same record
    monkeypatch.delitem(__import__("sys").modules, "jax")
    del seen[:]
    with tel.span("quiet"):
        pass
    assert seen == [] and spans_of(tel)[-1]["name"] == "quiet"


def test_twin_lands_in_a_real_profiler_trace(tmp_path):
    import glob
    import jax
    from jax.profiler import ProfileData
    tel = Telemetry()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tel.span("window_host", epoch=0):
            jax.block_until_ready(jax.numpy.ones((8,)) + 1)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    planes = list(ProfileData.from_file(path).planes)
    twins = [e for plane in planes for line in plane.lines
             for e in line.events if e.name == "window_host"]
    (rec,) = [s for s in spans_of(tel) if s["name"] == "window_host"]
    assert len(twins) == 1 and dict(twins[0].stats)["span_id"] == rec["id"]
    # The xplane counts from the session's start, which it records in Unix
    # nanoseconds: on that clock the twin starts within a millisecond of
    # the span's t_ns (a few microseconds, on an idle machine).
    (env,) = [p for p in planes if p.name == "Task Environment"]
    t0 = dict(env.stats)["profile_start_time"]
    assert abs(t0 + twins[0].start_ns - rec["t_ns"]) < 1_000_000
    assert abs(twins[0].duration_ns - rec["dur_ns"]) < 1_000_000


def test_span_log_is_bounded_newest_last_and_shared(monkeypatch):
    monkeypatch.setattr(telemetry, "_SPAN_LOG",
                        type(telemetry._SPAN_LOG)(maxlen=5))
    assert telemetry._SPAN_LOG.maxlen == 5 and telemetry.SPAN_LOG_MAX >= 4096
    a, b = Telemetry(), Telemetry()
    for i in range(4):
        with a.span("a", i=i):
            pass
        with b.span("b", i=i):
            pass
    b.span_event("event", 1.0, 0.1)
    a.gauge("g", 1)                 # only spans go to the log
    a.counter("c")
    log = span_log()
    assert [(s["name"], s.get("i")) for s in log] == [
        ("a", 2), ("b", 2), ("a", 3), ("b", 3), ("event", None)]
    assert len(log) == 5
    log.clear()                     # a copy: the log itself is untouched
    assert len(span_log()) == 5
    assert obs.span_log is span_log
    del a, b                        # it outlives the recorders
    assert len(span_log()) == 5


def test_null_appends_nothing_to_the_span_log(tmp_path, mesh4):
    before = span_log()
    with NULL.span("window_dispatch", epoch=1) as s:
        assert s is NULL_SPAN
    NULL.span_event("x", 0.0, 1.0)
    tr = make_trainer(tmp_path, mesh4, NULL, 200)
    tr.train_model(0)
    tr.test_model()
    assert span_log() == before
    assert NULL.__slots__ == () and NULL_SPAN.__slots__ == ()


class Exploding:
    """enabled=False recorder whose every other attribute fails the test:
    the disabled loop must not touch the recorder, so it builds no span,
    counter or gauge arguments either."""

    enabled = False

    def __getattr__(self, name):
        raise AssertionError(f"telemetry.{name} touched while disabled")


@pytest.mark.parametrize("ring", [20, 0])
def test_disabled_loop_never_touches_the_recorder(tmp_path, mesh4, ring):
    tr = make_trainer(tmp_path, mesh4, NULL, 200, metrics_ring=ring)
    lines = []
    tr.log = lines.append
    tr.telemetry = Exploding()      # every call site of the default path:
    for epoch in (0, 1):            # compiling (epoch 0) and warm (epoch 1)
        timers = tr.train_model(epoch)
        timers.telemetry = tr.telemetry
        tr.test_model()
    assert len(timers.losses) == 4
    assert sum("Test set: Average loss" in l for l in lines) == 2
