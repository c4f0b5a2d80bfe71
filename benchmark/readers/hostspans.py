"""Dispatch loop (train/loop.py), from the program's own spans.

The program's recorder keeps every span an enabled recorder emits in a
process-wide bounded log (`cs744_ddp_tpu.obs.span_log()`), so these readers
reach the spans after the driver has dropped the trainer.  A span is a dict
with `name`, `id`, `parent_id`, `t_ns` (Unix nanoseconds), `dur_ns` and
`epoch` (the unit it belongs to).  An untraced run records through `NULL`
and a program without the log has no `span_log`: every reader then returns
None.

What these readers cannot do is lay a host gap over the device's gaps: the
xplane counts its times from the profiler session's start (the stat
`profile_start_time` of its "Task Environment" plane, in Unix nanoseconds),
which `benchmark/trace.py` does not keep (PERF.md section 7).

The loop has one program in flight at a time: a `*_dispatch` span ends when
the jitted call returns, a `window_drain` / `*_fetch` span when the value is
on the host.  From the end of a fetch to the end of the next dispatch
nothing is in flight: a HOST GAP, which belongs to the unit of the dispatch
that closes it.  The arithmetic below runs (and is tested) on plain dicts.
"""

from __future__ import annotations

import statistics

DISPATCHES = ("window_dispatch", "tail_dispatch", "eval_dispatch")
FETCHES = ("window_drain", "tail_fetch", "eval_fetch")
OBSERVER = "obs_emit"           # work the untraced run does not do
COMPILE = "compile_warmup"


def _end(s: dict) -> int:
    return s["t_ns"] + s["dur_ns"]


def window_spans(log, epochs) -> list:
    """The newest run of spans in `log` (oldest first) whose `epoch` is one
    of `epochs`: this window's, not those an earlier trainer of the same
    process left under the same epoch numbers (the warm-up units' spans lie
    between).  Spans without an epoch (another subsystem's) are passed
    over."""
    epochs = set(epochs)
    out = []
    for s in reversed(log):
        if "epoch" not in s:
            continue
        if s["epoch"] in epochs:
            out.append(s)
        elif out:
            break
    return out[::-1]


def host_gaps(spans) -> list:
    """[(start_ns, end_ns, epoch)]: from the end of each fetch to the end of
    the dispatch that follows it, with the epoch of that dispatch."""
    marks = sorted((s for s in spans if s["name"] in DISPATCHES + FETCHES),
                   key=_end)
    return [(_end(a), _end(b), b["epoch"])
            for a, b in zip(marks, marks[1:])
            if a["name"] in FETCHES and b["name"] in DISPATCHES]


def _overlap(s: dict, lo: int, hi: int) -> int:
    return max(0, min(hi, _end(s)) - max(lo, s["t_ns"]))


def host_gap_ns_by_epoch(spans) -> dict:
    """{epoch: host-gap nanoseconds less the observer's spans inside}."""
    observer = [s for s in spans if s["name"] == OBSERVER]
    out = {}
    for lo, hi, epoch in host_gaps(spans):
        own = sum(_overlap(s, lo, hi) for s in observer)
        out[epoch] = out.get(epoch, 0) + (hi - lo) - own
    return out


def dispatch_ns_by_epoch(spans) -> dict:
    """{epoch: nanoseconds inside the dispatch spans, less any compile
    they waited for}."""
    ids = {s["id"]: s["epoch"] for s in spans if s["name"] in DISPATCHES}
    out = {}
    for s in spans:
        if s["name"] in DISPATCHES:
            out[s["epoch"]] = out.get(s["epoch"], 0) + s["dur_ns"]
        elif s["name"] == COMPILE and s.get("parent_id") in ids:
            epoch = ids[s["parent_id"]]
            out[epoch] = out.get(epoch, 0) - s["dur_ns"]
    return out


def median_ms(by_epoch: dict, epochs):
    """Median over the window's units, the first left out (it follows a
    fence, not a unit); None where no unit has a reading."""
    vals = [by_epoch[e] for e in epochs[1:] if e in by_epoch]
    return statistics.median(vals) / 1e6 if vals else None


def _window(run) -> tuple:
    """(this window's spans, its units' epochs); no spans in an untraced
    run or on a program without the log."""
    from cs744_ddp_tpu import obs
    if not hasattr(obs, "span_log"):
        return [], []
    epochs = [u.get("epoch") for u in run.window.units]
    return window_spans(obs.span_log(), epochs), epochs


def host_gap_ms_per_epoch(run):
    """What the host spends with nothing in flight, as the timed run would
    spend it."""
    spans, epochs = _window(run)
    return median_ms(host_gap_ns_by_epoch(spans), epochs)


def dispatch_ms_per_epoch(run):
    """The enqueue cost alone: the part of a host gap no reordering
    removes."""
    spans, epochs = _window(run)
    return median_ms(dispatch_ns_by_epoch(spans), epochs)
