"""From the profiler's trace to numbers: the benchmark's own reduction.

The interval arithmetic (`union`, `span`, `intersect`, `complement`) is
copied from `tools/perf_occupancy.py`, whose reader needs TensorFlow's
`xplane_pb2` (not installed); this one reads the `.xplane.pb` with
`jax.profiler.ProfileData`, imported only inside `read_xplane`, so the
arithmetic and the reduction run (and are tested) on plain tuples.

A trace, as the reduction sees it:

  {"devices": {ordinal: {"ops": [(name, start_ns, dur_ns)],
                         "async": [(name, start_ns, dur_ns)],
                         "modules": [(name, start_ns, dur_ns)]}},
   "host": [(name, start_ns, dur_ns)]}      # the benchmark's annotations

Device busy time is the union of the leaf operations' intervals on the
"XLA Ops" line.  Containers (`while`, `conditional`, `call`) cover their
bodies and the `-start`/`-done` halves of an asynchronous operation are
markers, so neither counts as the device being busy by itself.  An
asynchronous operation's whole span (start to done) is on the "Async XLA
Ops" line; a collective's time is read there, or from "XLA Ops" where the
compiler left it synchronous.
"""

from __future__ import annotations

import collections
import glob
import os
import re

ANNOTATIONS = ("train_model", "test_model")
_CONTAINER_OPS = ("while", "conditional", "call")
_COLLECTIVE = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")


# -- interval arithmetic ------------------------------------------------------

def union(intervals):
    out = []
    for s, t in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t)
        else:
            out.append([s, t])
    return out


def span(intervals):
    return sum(t - s for s, t in intervals)


def intersect(a, b):
    """Total overlap between two interval unions."""
    i = j = tot = 0
    while i < len(a) and j < len(b):
        s = max(a[i][0], b[j][0])
        t = min(a[i][1], b[j][1])
        if t > s:
            tot += t - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def complement(intervals, t0, t1):
    out = []
    prev = t0
    for s, t in intervals:
        if s > prev:
            out.append([prev, min(s, t1)])
        prev = max(prev, t)
    if t1 > prev:
        out.append([prev, t1])
    return [g for g in out if g[1] > g[0]]


# -- names --------------------------------------------------------------------

def op_of(name: str) -> tuple:
    """(instruction, opcode) of an "XLA Ops" event name: either the bare
    instruction (`fusion.445`, `%select-and-scatter.64`) or the HLO line
    (`%fusion.445 = f32[..] fusion(..), kind=kOutput`)."""
    m = re.match(r"\s*%?([\w.\-]+)", name)
    inst = m.group(1) if m else name
    m = re.search(r"=\s*[^=]*?\s([a-z][\w\-]*)\(", name)
    if m:
        return inst, m.group(1)
    return inst, re.sub(r"[.\d]+$", "", inst)


def is_async_marker(inst: str, opcode: str) -> bool:
    return any(x.endswith(("-start", "-done")) for x in (inst, opcode))


def is_collective(inst: str, opcode: str) -> bool:
    return any(c in x for c in _COLLECTIVE for x in (inst, opcode))


def matmul_instructions(hlo_text: str) -> set:
    """Names of the instructions of a compiled module that hold a
    convolution or a dot: those two opcodes themselves, and every fusion
    whose fused computation contains one.  The trace names an operation by
    its instruction; what a fusion holds is only in the module's text."""
    comp = None
    holds = set()           # computations that contain a convolution / dot
    calls = []              # (instruction, opcode, called computation)
    for line in hlo_text.splitlines():
        m = re.match(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*->.*\{\s*$", line)
        if m:
            comp = m.group(1)
            continue
        m = re.match(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*[^=]*?\s"
                     r"([a-z][\w\-]*)\(", line)
        if not m:
            continue
        inst, opcode = m.groups()
        if opcode in ("convolution", "dot"):
            holds.add(comp)
            calls.append((inst, opcode, None))
        c = re.search(r"calls=%?([\w.\-]+)", line)
        if c:
            calls.append((inst, opcode, c.group(1)))
    return {inst for inst, opcode, called in calls
            if called is None or called in holds}


def module_name(event_name: str) -> str:
    """`jit_window(13450855693301201896)` -> `jit_window`."""
    return event_name.split("(", 1)[0]


# -- reading ------------------------------------------------------------------

def find_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def read_xplane(path: str) -> dict:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = {"devices": {}, "host": []}
    for plane in pd.planes:
        m = re.match(r"^/device:TPU:(\d+)$", plane.name)
        if m:
            dev = {"ops": [], "async": [], "modules": []}
            for line in plane.lines:
                if line.name == "XLA Ops":
                    dev["ops"] = [(e.name, e.start_ns, e.duration_ns)
                                  for e in line.events]
                elif line.name == "Async XLA Ops":
                    dev["async"] = [(e.name, e.start_ns, e.duration_ns)
                                    for e in line.events]
                elif line.name == "XLA Modules":
                    dev["modules"] = [(e.name, e.start_ns, e.duration_ns)
                                      for e in line.events]
            out["devices"][int(m.group(1))] = dev
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                out["host"] += [(e.name, e.start_ns, e.duration_ns)
                                for e in line.events
                                if e.name in ANNOTATIONS]
    return out


def dump_xplane(path: str, per_line: int = 12) -> list:
    """A look at a trace by hand: planes, lines, the first distinct events
    of each with their stats."""
    from jax.profiler import ProfileData
    rows = []
    for plane in ProfileData.from_file(path).planes:
        rows.append(f"PLANE {plane.name}")
        for line in plane.lines:
            evs = list(line.events)
            rows.append(f"  LINE {line.name!r} events={len(evs)}")
            seen = set()
            for e in evs:
                key = re.sub(r"\d+", "#", e.name)[:60]
                if key in seen:
                    continue
                seen.add(key)
                rows.append(f"    {e.name[:300]!r} start={e.start_ns} "
                            f"dur={e.duration_ns} stats={dict(e.stats)}")
                if len(seen) >= per_line:
                    break
    return rows


# -- reduction ----------------------------------------------------------------

def leaf_ops(dev: dict):
    """The operations that are the device working: no containers, no
    asynchronous markers."""
    for name, start, dur in dev["ops"]:
        inst, opcode = op_of(name)
        if opcode in _CONTAINER_OPS or is_async_marker(inst, opcode):
            continue
        yield name, start, dur


def attribute_gap(gap, host) -> str:
    """The annotation that covers at least half of an idle gap, else
    "between_units" (the harness's own loop between two annotations)."""
    best, name = 0.5 * (gap[1] - gap[0]), "between_units"
    for hname, start, dur in host:
        ov = min(gap[1], start + dur) - max(gap[0], start)
        if ov >= best:
            best, name = ov, hname
    return name


def _module_of(modules_sorted, starts, t):
    import bisect
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and t < modules_sorted[i][1] + modules_sorted[i][2]:
        return module_name(modules_sorted[i][0])
    return None


def summarize(trace: dict, matmul_by_module: dict = None,
              module_kinds: dict = None) -> dict:
    """Everything the per-layer readers take from the trace, in seconds.
    Per device, then averaged over the devices used.  `matmul_by_module`:
    {module name: names of its instructions that hold a convolution or a
    dot} (`matmul_instructions` of each compiled module); without it no
    operation is classed and the readers of the classes stay silent.
    `module_kinds`: {"train": [module names], "eval": [...]}, from the
    traffic file."""
    matmul_by_module = matmul_by_module or {}
    kind_of = {name: kind for kind, names in (module_kinds or {}).items()
               for name in names}
    per_dev = []
    for ordinal in sorted(trace["devices"]):
        dev = trace["devices"][ordinal]
        ops = list(leaf_ops(dev))
        if not ops:
            continue
        busy_u = union([[s, s + d] for _, s, d in ops])
        t0, t1 = busy_u[0][0], busy_u[-1][1]
        modules = sorted(dev["modules"], key=lambda m: m[1])
        starts = [m[1] for m in modules]
        by_name = collections.Counter()
        classed_ns = coll_ns = 0
        coll_iv, other_iv, mm_iv = [], [], []
        for name, s, d in ops:
            inst, opcode = op_of(name)
            by_name[inst] += d
            if is_collective(inst, opcode):
                coll_ns += d
                coll_iv.append([s, s + d])
                continue
            other_iv.append([s, s + d])
            known = matmul_by_module.get(_module_of(modules, starts, s))
            if known is not None:
                classed_ns += d
                if inst in known:
                    mm_iv.append([s, s + d])
        for name, s, d in dev.get("async", ()):
            if is_collective(*op_of(name)):
                coll_ns += d
                coll_iv.append([s, s + d])
        mods = {"train": [], "eval": []}
        for name, s, d in modules:
            kind = kind_of.get(module_name(name))
            if kind in mods:
                mods[kind].append([s, s + d])
        mod_busy = {k: intersect(union(v), busy_u) for k, v in mods.items()}
        mm_u = union(mm_iv)
        gaps = complement(busy_u, t0, t1)
        exposed = span(union(coll_iv)) - intersect(union(coll_iv),
                                                   union(other_iv))
        per_dev.append({
            "ordinal": ordinal,
            "busy_s": span(busy_u) / 1e9,
            "ops_s": sum(d for _, _, d in ops) / 1e9,
            "classed_s": classed_ns / 1e9,
            "matmul_s": span(mm_u) / 1e9,
            "matmul_train_s": intersect(mm_u, union(mods["train"])) / 1e9,
            "collective_s": coll_ns / 1e9,
            "collective_exposed_s": exposed / 1e9,
            "train_module_busy_s": mod_busy["train"] / 1e9,
            "eval_module_busy_s": mod_busy["eval"] / 1e9,
            "top_ops": [[n, d / 1e9] for n, d in by_name.most_common(10)],
            "gaps": sorted(gaps, key=lambda g: g[0] - g[1])[:10],
            "gap_total_s": span(gaps) / 1e9,
        })
    if not per_dev:
        return {"devices": []}
    mean = lambda k: sum(d[k] for d in per_dev) / len(per_dev)
    d0 = per_dev[0]
    return {
        "devices": per_dev,
        "busy_s": mean("busy_s"),
        "ops_s": mean("ops_s"),
        "classed_s": mean("classed_s"),
        "matmul_s": mean("matmul_s"),
        "matmul_train_s": mean("matmul_train_s"),
        "train_module_busy_s": mean("train_module_busy_s"),
        "eval_module_busy_s": mean("eval_module_busy_s"),
        "collective_s_dev0": d0["collective_s"],
        "collective_exposed_s_dev0": d0["collective_exposed_s"],
        "gap_total_s_dev0": d0["gap_total_s"],
        "breakdown": {
            "device_ops": d0["top_ops"],
            "idle_gaps": [[attribute_gap(g, trace["host"]),
                           (g[1] - g[0]) / 1e9] for g in d0["gaps"]],
        },
    }


if __name__ == "__main__":
    import sys
    print("\n".join(dump_xplane(sys.argv[1])))
