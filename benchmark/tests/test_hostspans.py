"""The readers of the program's spans (readers/hostspans.py): the
arithmetic on hand-made spans, and the CPU rehearsal with an enabled
recorder, where the two metrics read a number.
"""

import pytest

from benchmark.readers import hostspans as hs

MS = 1_000_000


def span(name, t_ms, dur_ms, epoch, id=0, parent_id=None):
    return {"kind": "span", "name": name, "id": id, "parent_id": parent_id,
            "t_ns": int(t_ms * MS), "dur_ns": int(dur_ms * MS),
            "epoch": epoch}


def unit(epoch, t0):
    """One unit on a 100 ms grid: dispatch 2 ms, drain until +60, host work
    (with 1 ms of the observer's) until the tail's dispatch ends at +65,
    its fetch at +80, the evaluation's dispatch at +83, its fetch at +95."""
    return [
        span("window_dispatch", t0, 2, epoch, id=10 * epoch + 1),
        span("window_drain", t0 + 2, 58, epoch),
        span("obs_emit", t0 + 61, 1, epoch),
        span("tail_dispatch", t0 + 63, 2, epoch, id=10 * epoch + 2),
        span("tail_fetch", t0 + 65, 15, epoch),
        span("eval_dispatch", t0 + 82, 1, epoch, id=10 * epoch + 3),
        span("eval_fetch", t0 + 83, 12, epoch),
    ]


SPANS = unit(2, 0) + unit(3, 100) + unit(4, 200)


def test_host_gaps_run_from_a_fetch_to_the_end_of_the_next_dispatch():
    gaps = hs.host_gaps(SPANS)
    assert [(lo // MS, hi // MS, e) for lo, hi, e in gaps[:3]] == [
        (60, 65, 2), (80, 83, 2), (95, 102, 3)]     # the last crosses units
    assert len(gaps) == 8       # the first dispatch follows no fetch


def test_host_gap_subtracts_the_observer_and_leaves_out_the_first_unit():
    by_epoch = hs.host_gap_ns_by_epoch(SPANS)
    # unit 2: (5 - 1) + 3; units 3 and 4 also own the gap that leads in: + 7
    assert {e: v / MS for e, v in by_epoch.items()} == {2: 7, 3: 14, 4: 14}
    assert hs.median_ms(by_epoch, [2, 3, 4]) == 14
    assert hs.median_ms(by_epoch, [2]) is None
    assert hs.median_ms({}, [2, 3, 4]) is None


def test_dispatch_time_leaves_out_a_compile_it_waited_for():
    spans = SPANS + [span("compile_warmup", 163.2, 1.5, 3, parent_id=32),
                     span("compile_warmup", 10, 40, 3, parent_id=999)]
    by_epoch = hs.dispatch_ns_by_epoch(spans)
    assert {e: v / MS for e, v in by_epoch.items()} == {2: 5, 3: 3.5, 4: 5}
    assert hs.median_ms(by_epoch, [2, 3, 4]) == 4.25


def test_window_spans_takes_the_newest_run_of_the_units_epochs():
    older = unit(2, -1000) + unit(3, -900)          # an earlier trainer's
    warm = unit(0, -200) + unit(1, -100)
    foreign = {"kind": "span", "name": "serve_request", "t_ns": 5,
               "dur_ns": 1}
    log = older + warm + SPANS[:7] + [foreign] + SPANS[7:] + unit(5, 300)
    assert hs.window_spans(log, [2, 3, 4]) == SPANS
    assert hs.window_spans(log, [7]) == []
    assert hs.window_spans([], [2]) == []


class FakeRun:
    def __init__(self, units):
        from benchmark.window import Window
        self.window = Window(units, 0.0, 1.0)
        self.trace = {}


def test_readers_return_none_on_an_empty_log(monkeypatch):
    from cs744_ddp_tpu.obs import telemetry
    monkeypatch.setattr(telemetry, "_SPAN_LOG", type(telemetry._SPAN_LOG)())
    run = FakeRun([{"epoch": 2}, {"epoch": 3}])
    assert hs.host_gap_ms_per_epoch(run) is None
    assert hs.dispatch_ms_per_epoch(run) is None


def test_readers_return_none_on_a_program_without_the_log(monkeypatch):
    from cs744_ddp_tpu import obs
    monkeypatch.delattr(obs, "span_log")
    run = FakeRun([{"epoch": 2}, {"epoch": 3}])
    assert hs.host_gap_ms_per_epoch(run) is None
    assert hs.dispatch_ms_per_epoch(run) is None


def test_rehearsal_with_an_enabled_recorder_reads_the_span_metrics():
    """The driver on the CPU mesh with the trainer recording (as the traced
    run's does): the two metrics that need no device trace read a number,
    dispatches = fetches per unit, and an untraced run appends nothing."""
    from benchmark.drivers import train_epochs
    from cs744_ddp_tpu import obs
    from test_rehearsal import ctx

    before = len(obs.span_log())
    quiet = train_epochs.run(ctx(1))
    assert len(obs.span_log()) == before
    assert hs.host_gap_ms_per_epoch(FakeRun(quiet["window"].units)) is None

    recorder = obs.Telemetry(None)

    def recording(config, traffic, seed, telemetry, data_dir):
        return train_epochs.build_trainer(config, traffic, seed, recorder,
                                          data_dir)
    r = train_epochs.run(ctx(1, build_trainer=recording, seconds=0.5))
    assert r["correct"], r["compared"]
    run = FakeRun(r["window"].units)
    assert len(run.window.units) >= 2
    gap = hs.host_gap_ms_per_epoch(run)
    dispatch = hs.dispatch_ms_per_epoch(run)
    assert 0 < dispatch < gap
    epochs = [u["epoch"] for u in run.window.units]
    spans = hs.window_spans(obs.span_log(), epochs)
    for e in epochs:
        names = [s["name"] for s in spans if s["epoch"] == e]
        assert sum(n in hs.DISPATCHES for n in names) \
            == sum(n in hs.FETCHES for n in names) == 3
    totals = recorder.counter_totals()
    assert totals["dispatches"] == totals["host_round_trips"]
