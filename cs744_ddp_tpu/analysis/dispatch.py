"""Static host-round-trip certifier (round 13).

The ring buffer (round 8) made per-epoch host round-trips a COUNTED
quantity (``host_round_trips`` telemetry counter, CI-pinned), but the
pin is only as good as the run that produced it.  This module derives
the same number STATICALLY — a closed form over the lowered programs'
scan trip counts and the trainer's dispatch structure — a compile-time
certificate instead of a runtime observation.

The dispatch structure being certified (train/loop.py):

* ``step`` path: one blocking ``_fetch_step`` per batch
  (``step_fetch``), plus one fetch for a ragged tail batch, plus one
  ``eval`` fetch per ``test_model()``;
* ``window``/``host_window`` paths: one fetch per window dispatch —
  windows cut at WINDOW boundaries, so ``ceil(nbatches / window)``
  dispatches per epoch (``window_fetch``, or ``window_drain`` when the
  metrics ring defers the fetch to the drain), plus tail batch + eval
  as above.  The per-step metric writes inside the window are pure
  device-side ring updates — the audit's host-sync rule certifies the
  scanned body has no host transfer, which is what makes the closed
  form exact rather than an estimate.

From the HLO side, each windowed program must actually BE a windowed
program: its scan trip count (``costmodel.cost_report().trip_counts``)
must include the window size the trainer will dispatch, and its
donation set must be non-empty (a non-donating "windowed" program
round-trips the state through host memory every window — the exact
regression this certificate exists to catch).

``certify_zoo`` runs the certificate over an audited zoo
(``audit_zoo(..., collect_hlo=True)``); tests pin the static bound
against the live ``host_round_trips`` counter EXACTLY for every path.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, Iterable, List, Optional, Tuple

from .pylint_rules import LintFinding

#: Counter sites the trainer attributes round-trips to.
TRIP_SITES = ("step_fetch", "window_fetch", "window_drain", "eval")

#: Paths whose epoch cost is one fetch per WINDOW dispatch.
WINDOWED_PATHS = ("window", "host_window")

#: Serving-ladder zoo prefixes (``serve/b{bucket}/{precision}`` and the
#: hot-swap recert twin).  A serving rung must be STRAIGHT-LINE: one
#: dispatch = one fetch, no internal scan trips.  That is the premise of
#: the pipelined scheduler's two-in-flight bound — if a rung hid a host
#: round-trip inside a loop, overlapping two of them would serialize on
#: the host and the occupancy accounting would lie.
SERVING_PATHS = ("serve", "serve_swap")


def serving_inflight_bound() -> int:
    """The static per-replica in-flight dispatch bound (= the scheduler's
    ``PIPELINE_SLOTS`` = the ``StagedIngest`` arena depth).  Tests pin the
    runtime occupancy (``max_serving_inflight``) against this exactly."""
    from ..serve.scheduler import PIPELINE_SLOTS
    return PIPELINE_SLOTS


def max_serving_inflight(records: Iterable[Dict]) -> int:
    """Max observed pipeline occupancy from a recording telemetry's
    ``serve_inflight`` gauges — the runtime half of the bound pin (0 when
    the run never pipelined)."""
    m = 0
    for r in records:
        if r.get("kind") == "gauge" and r.get("name") == "serve_inflight":
            m = max(m, int(r.get("value", 0)))
    return m


def epoch_round_trip_bound(path: str, nbatches: int, window: int = 0, *,
                           tail_batch: bool = False,
                           include_eval: bool = False) -> int:
    """Closed-form host round-trips for ONE epoch of ``nbatches`` full
    batches on ``path`` (+1 for a ragged tail batch, which always runs
    per-step; +1 for the post-epoch eval fetch).  This is an upper bound
    that the runtime counter meets exactly: every dispatch fetches once
    and nothing else touches the host (audited)."""
    if nbatches < 0 or (path in WINDOWED_PATHS and window <= 0):
        raise ValueError(f"bad bound query: path={path!r} "
                         f"nbatches={nbatches} window={window}")
    if path == "step":
        trips = nbatches
    elif path in WINDOWED_PATHS:
        trips = math.ceil(nbatches / window)
    elif path == "eval":
        trips = 1 if nbatches else 0
    else:
        raise ValueError(f"unknown dispatch path {path!r}")
    return trips + (1 if tail_batch else 0) + (1 if include_eval else 0)


@dataclass
class ProgramCert:
    """Static dispatch facts for one lowered program."""

    program: str                  # zoo name, e.g. "train/window/ddp"
    path: str                     # "step" | "window" | "host_window" | ...
    scan_trips: Tuple[int, ...]   # every while-loop trip count in the HLO
    donated: int                  # donated entry parameters (the floor)

    @property
    def window(self) -> Optional[int]:
        """The program's window size: its largest scan trip count."""
        return max(self.scan_trips) if self.scan_trips else None


def _split_zoo_name(name: str) -> Tuple[str, str]:
    """zoo program name -> (path, strategy)."""
    parts = name.split("/")
    if parts[0] == "train" and len(parts) == 3:
        return parts[1], parts[2]
    if parts[0] == "eval":
        return "eval", "eval"
    return parts[0], "/".join(parts[1:])


def certify_program(name: str, hlo_text: str) -> ProgramCert:
    from . import costmodel, hlo_ir
    rep = costmodel.cost_report(hlo_text, name)
    module = hlo_ir.parse(hlo_text)
    path, _ = _split_zoo_name(name)
    return ProgramCert(
        program=name, path=path,
        scan_trips=tuple(sorted(rep.trip_counts.values())),
        donated=module.donated_param_count())


def check_cert(cert: ProgramCert, *, expect_window: Optional[int] = None
               ) -> List[LintFinding]:
    """Static conformance of one program: a windowed program must scan
    the window it claims and must donate its carried state."""
    findings: List[LintFinding] = []
    if cert.path in WINDOWED_PATHS or cert.path == "eval":
        if not cert.scan_trips:
            findings.append(LintFinding(
                "dispatch-no-scan", cert.program, 0,
                f"{cert.program} lowers to a straight-line program — a "
                f"windowed path must scan its window on device, or every "
                f"step round-trips the host"))
        elif expect_window is not None \
                and expect_window not in cert.scan_trips:
            findings.append(LintFinding(
                "dispatch-window-mismatch", cert.program, 0,
                f"{cert.program} scans {list(cert.scan_trips)} trips but "
                f"the trainer dispatches windows of {expect_window} — the "
                f"closed-form round-trip bound would be wrong"))
    if cert.path in WINDOWED_PATHS and cert.donated == 0:
        findings.append(LintFinding(
            "dispatch-donation-zero", cert.program, 0,
            f"{cert.program} donates no entry parameters — the carried "
            f"state bounces through host memory every window"))
    if cert.path in SERVING_PATHS and cert.scan_trips:
        findings.append(LintFinding(
            "dispatch-serving-scan", cert.program, 0,
            f"{cert.program} scans {list(cert.scan_trips)} trips — a "
            f"serving rung must be straight-line (one dispatch = one "
            f"fetch), or the pipelined two-in-flight bound is unsound"))
    return findings


def certify_zoo(result, *, window: int, nbatches: int,
                include_eval: bool = True) -> Dict:
    """The full certificate over an audited zoo (requires
    ``audit_zoo(..., collect_hlo=True)``).  Returns a JSON-ready record:
    per-program window/donation facts and the static per-epoch
    round-trip bound for ``nbatches`` full batches, plus any findings.
    """
    if not getattr(result, "hlo", None):
        raise ValueError("audit result carries no HLO text; re-run "
                         "audit_zoo(..., collect_hlo=True)")
    programs: Dict[str, Dict] = {}
    findings: List[LintFinding] = []
    for name in sorted(result.hlo):
        cert = certify_program(name, result.hlo[name])
        expect = window if cert.path in WINDOWED_PATHS + ("eval",) else None
        findings.extend(check_cert(cert, expect_window=expect))
        entry: Dict = {"path": cert.path, "window": cert.window,
                       "donated": cert.donated}
        if cert.path in ("step",) + WINDOWED_PATHS:
            entry["epoch_round_trips"] = epoch_round_trip_bound(
                cert.path, nbatches, window, include_eval=include_eval)
        programs[name] = entry
    return {
        "window": window,
        "nbatches": nbatches,
        "include_eval": include_eval,
        "programs": programs,
        "findings": [{"rule": f.rule, "program": f.path,
                      "message": f.message} for f in findings],
        "clean": not findings,
    }


def count_runtime_trips(records: Iterable[Dict]) -> Dict[str, int]:
    """Per-site totals of the live ``host_round_trips`` counter from a
    recording telemetry's event list — the number the static bound must
    meet exactly."""
    sites: Dict[str, int] = {}
    for r in records:
        if r.get("kind") == "counter" and r.get("name") == "host_round_trips":
            site = r.get("site", "?")
            sites[site] = sites.get(site, 0) + int(r.get("inc", 1))
    return sites


def total_runtime_trips(records: Iterable[Dict]) -> int:
    return sum(count_runtime_trips(records).values())
