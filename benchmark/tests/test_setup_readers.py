"""The set-up readers (readers/setup.py) on hand-made spans: which spans
are set-up, the unions they take, and None wherever nothing is there to
read (an untraced run, a program without the spans or without the log).
"""

import collections

import pytest

from benchmark.readers import setup as su

MS = 1_000_000
NAMES = ("setup.trainer_s", "setup.trace_lower_s", "setup.compile_s",
         "setup.cache_load_s", "setup.programs_built")


def span(name, t_ms, dur_ms, **attrs):
    return {"kind": "span", "name": name, "id": 0, "t_ns": int(t_ms * MS),
            "dur_ns": int(dur_ms * MS), **attrs}


def unit(epoch, t0):
    return [span("window_dispatch", t0, 2, epoch=epoch),
            span("window_drain", t0 + 2, 58, epoch=epoch),
            span("eval", t0 + 60, 30, epoch=epoch)]


# Set-up: the trainer (with two compiles inside, one a load), a one-step
# program traced (a nested trace inside it) and compiled outside any span,
# then warm-up unit 0, whose first window compiles the timed program
# (traced, lowered, a compile inside the trace) and loads the eval program.
SETUP = [
    span("xla_compile", 10, 5, program="init", cached=False),
    span("xla_compile", 20, 3, program="init2", cached=True),
    span("trainer_init", 0, 100),
    span("jax_trace", 110, 40, program="one_step"),
    span("jax_trace", 120, 10, program="nested"),
    span("jax_lower", 150, 20, program="one_step"),
    span("xla_compile", 170, 30, program="one_step"),   # the cache is off
    span("jax_trace", 300, 50, program="window"),
    span("xla_compile", 310, 5, program="eager", cached=False),
    span("jax_lower", 350, 25, program="window"),
    span("xla_compile", 375, 100, program="window", cached=False),
    span("compile_warmup", 290, 190, epoch=0),
    span("xla_compile", 500, 8, program="evaluate", cached=True),
] + unit(0, 480)
WINDOW = unit(1, 600) + unit(2, 700)
AFTER = [span("jax_trace", 900, 50, program="reference"),
         span("xla_compile", 950, 60, program="reference", cached=False)]


class FakeRun:
    def __init__(self, epochs):
        from benchmark.window import Window
        self.window = Window([{"epoch": e} for e in epochs], 0.0, 1.0)
        self.trace = {}


@pytest.fixture
def log(monkeypatch):
    """Point the program's span log at the spans a test hands over."""
    from cs744_ddp_tpu.obs import telemetry

    def use(spans):
        monkeypatch.setattr(telemetry, "_SPAN_LOG",
                            collections.deque(spans))
    return use


def test_setup_is_what_ends_before_the_window_warm_up_included():
    got = su.setup_spans(SETUP + WINDOW + AFTER, [1, 2])
    assert got == SETUP
    assert su.setup_spans(SETUP + WINDOW, [7]) == []


def test_union_counts_an_overlap_once():
    assert su.union_ns([]) == 0
    assert su.union_ns(SETUP[3:5]) == 40 * MS        # nested: the outer
    assert su.union_ns([span("a", 0, 10), span("b", 5, 10),
                        span("c", 30, 1)]) == 16 * MS


def test_the_five_readers_on_a_set_up(log):
    log(SETUP + WINDOW + AFTER)
    run = FakeRun([1, 2])
    assert su.trainer_s(run) == pytest.approx(0.1)
    # trace + lowering: 110-170 and 300-375, less the compile at 310-315
    assert su.trace_lower_s(run) == pytest.approx(0.130)
    # compiled: 5 + 30 (cache off: no verdict) + 5 + 100; loaded: 3 + 8
    assert su.compile_s(run) == pytest.approx(0.140)
    assert su.cache_load_s(run) == pytest.approx(0.011)
    assert su.programs_built(run) == 6               # not the reference's


def test_the_newest_trainer_is_read(log):
    log([span("trainer_init", -500, 40)] + SETUP + WINDOW)
    assert su.trainer_s(FakeRun([1, 2])) == pytest.approx(0.1)


def test_the_recorders_own_lowering_is_left_out(log):
    """With a recorder the first epoch lowers the step once more for its
    collective statistics, under `obs_emit`: not what set-up costs
    untraced."""
    stats = [span("obs_emit", 292, 30, id=7, epoch=0),
             span("jax_trace", 293, 10, program="step", parent_id=7),
             span("jax_lower", 303, 15, program="step", parent_id=7)]
    log(SETUP + stats + WINDOW)
    run = FakeRun([1, 2])
    assert su.trace_lower_s(run) == pytest.approx(0.130)
    assert su.programs_built(run) == 6


def test_a_set_up_that_loads_everything_reads_zero_compile(log):
    loaded = [dict(s, cached=True) if s["name"] == "xla_compile" else s
              for s in SETUP]
    log(loaded + WINDOW)
    run = FakeRun([1, 2])
    assert su.compile_s(run) == 0.0
    assert su.cache_load_s(run) == pytest.approx(0.151)


def test_none_where_nothing_is_read(log, monkeypatch):
    readers = [getattr(su, n.split(".")[1]) for n in NAMES]
    log([])                                          # an untraced run
    assert [r(FakeRun([1, 2])) for r in readers] == [None] * 5
    # a parent: the loop's spans, no trainer_init and no jax spans
    log([s for s in SETUP if "program" not in s
         and s["name"] != "trainer_init"] + WINDOW)
    assert [r(FakeRun([1, 2])) for r in readers] == [None] * 5
    log(SETUP + WINDOW)                              # no unit of the window
    assert [r(FakeRun([5])) for r in readers] == [None] * 5
    from cs744_ddp_tpu import obs
    monkeypatch.delattr(obs, "span_log")             # a program without it
    assert [r(FakeRun([1, 2])) for r in readers] == [None] * 5


@pytest.mark.parametrize("name", NAMES)
def test_the_metrics_resolve_and_every_cell_reports_them(name):
    from benchmark import manifest as mf
    manifest = mf.load()
    metric, = [m for m in manifest["per_layer"] if m["name"] == name]
    assert metric["moves"] == "setup_s" and "workloads" not in metric
    assert metric["source"] == "program_span"
    for cell in manifest["workloads"]:
        assert metric in mf.cell_metrics(manifest, cell["name"], "per_layer")
    assert mf.load_reader(name) is getattr(su, name.split(".")[1])
