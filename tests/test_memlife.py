"""Static HBM liveness certifier (round 20).

* ``hlo_ir.type_bytes`` / ``result_bytes`` — structural byte sizes,
  tuple-recursive and layout/tiling-tolerant, pinned on a committed
  fixture and proven DIFFERENTIALLY against the legacy regex summer
  (``stats.bytes_of_type``) over every committed fixture.
* ``memlife.mem_report`` — the liveness sweep: peak bytes pinned by hand
  on the committed donated/undonated window pair; the donation delta IS
  the carried state bytes; while trip counts do not multiply the peak
  (steady-state model); donation must round-trip as an aliased-bytes
  equality.
* ``audit`` integration — the ``peak-memory`` rule fails a program over
  its ``hbm_budget_bytes`` contract and passes under it; every audited
  program carries ``peak_mib`` in its stats.
* Differential vs the executable — the static peak must never sit under
  XLA's ``memory_analysis()`` temp+output floor (checked on a REAL
  compiled window) and the synthetic unsound/unmoored paths fire.
* Runtime cross-check — a real windowed train run's ``memory`` gauge
  (live device bytes) stays under the window's static certificate.
* Repo self-checks — v5e literals single-sourced, fixture invariants
  hold, and both produce ``lint_graft --json``-shaped findings on
  seeded violations.
* ``tools/telemetry_report.py`` — the ``== memory ==`` section renders
  measured-vs-certified and stays absent for runs with no signal.
"""

import glob
import json
import os
import types

import pytest

from cs744_ddp_tpu import models as model_zoo
from cs744_ddp_tpu.analysis import audit as auditlib
from cs744_ddp_tpu.analysis import hlo_ir, memlife, stats
from cs744_ddp_tpu.obs import Telemetry
from cs744_ddp_tpu.train.loop import Trainer

from tinynet import tiny_cnn

ASSETS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "assets", "hlo")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DONATED = open(os.path.join(REPO, memlife.FIXTURE_DONATED)).read()
UNDONATED = open(os.path.join(REPO, memlife.FIXTURE_UNDONATED)).read()


# ---------------------------------------------------------------------------
# structural byte sizes (satellite: hlo_ir.type_bytes / result_bytes)
# ---------------------------------------------------------------------------

def test_type_bytes_pins():
    # Layout + tiling annotations are size-irrelevant and ignored.
    assert hlo_ir.type_bytes("f32[128,64]{1,0:T(8,128)}") == 128 * 64 * 4
    assert hlo_ir.type_bytes("u8[2,32,32,3]{3,2,1,0}") == 2 * 32 * 32 * 3
    assert hlo_ir.type_bytes("bf16[3,5]") == 30
    assert hlo_ir.type_bytes("f32[]") == 4
    # Size-less leaves contribute nothing.
    assert hlo_ir.type_bytes("token[]") == 0
    assert hlo_ir.type_bytes(None) == 0
    # Tuples recurse; nesting and scalar members included.
    assert hlo_ir.type_bytes("(f32[2,3], (s32[4], pred[]))") == 24 + 16 + 1


def test_result_bytes_fixture_pins():
    mod = hlo_ir.parse(
        open(os.path.join(ASSETS, "memlife_types.hlo")).read())
    by = {i.name: i for i in mod.entry_computation.instructions.values()}
    assert hlo_ir.result_bytes(by["big"]) == 32768
    assert hlo_ir.result_bytes(by["img"]) == 6144
    assert hlo_ir.result_bytes(by["half"]) == 30
    assert hlo_ir.result_bytes(by["tok"]) == 0
    assert hlo_ir.result_bytes(by["pair"]) == 41


def test_result_bytes_differential_vs_legacy():
    """Old == new on EVERY instruction of every committed fixture: the
    structural recursion and the legacy regex sum must agree, or one of
    them mis-sizes real lowerings."""
    total = 0
    for path in sorted(glob.glob(os.path.join(ASSETS, "*.hlo"))):
        mod = hlo_ir.parse(open(path).read())
        for ins in mod.instructions():
            assert hlo_ir.result_bytes(ins) == \
                stats.bytes_of_type(ins.result_type), \
                f"{os.path.basename(path)}:{ins.name} {ins.result_type}"
            total += 1
    assert total > 100   # the sweep actually covered the corpus


def test_dtype_bytes_single_copy():
    """stats aliases the canonical table — same object, not a fork."""
    assert stats._DTYPE_BYTES is hlo_ir.DTYPE_BYTES


# ---------------------------------------------------------------------------
# liveness sweep: hand-pinned peaks on the committed window pair
# ---------------------------------------------------------------------------
# Both fixtures: w0/m0 = f32[64,10] (2560 B each), i0 = s32[] (4 B) carried
# through a 4-trip while.  Donated: params 5124 + while spike (body fresh
# carry 5124 + cond pred/consts 4) = 10252.  Undonated: + a 5124 B
# carry-copy (XLA's copy-insertion for a live caller-held operand).

def test_liveness_pins_donated():
    rep = memlife.mem_report(DONATED, "fixture/donated")
    assert rep.peak_bytes == 10252
    assert rep.param_bytes == 5124
    assert rep.donated_bytes == 5124
    assert rep.carry_bytes == 5124
    assert rep.undonated_copy_bytes == 0
    assert rep.peak_mib == pytest.approx(10252 / 2**20)
    assert rep.top_sets and rep.top_sets[0]["live_bytes"] == 10252
    members = dict(rep.top_sets[0]["members"])
    assert members["w0"] == 2560 and members["i0"] == 4


def test_liveness_donation_delta_is_carry_bytes():
    """The tentpole's proof obligation: donated vs undonated twins differ
    by EXACTLY the carried state bytes — donation proven in bytes, not
    by attribute presence."""
    don = memlife.mem_report(DONATED, "fixture/donated")
    und = memlife.mem_report(UNDONATED, "fixture/undonated")
    assert und.peak_bytes == 15376
    assert und.undonated_copy_bytes == 5124
    assert und.peak_bytes - don.peak_bytes == und.undonated_copy_bytes


def test_liveness_steady_state_trip_invariance():
    """A while body's peak is charged ONCE (steady state): multiplying
    the trip count 100x must not move the static peak."""
    hot = DONATED.replace("constant(4)", "constant(400)")
    assert "constant(400)" in hot
    assert memlife.mem_report(hot, "hot").peak_bytes == \
        memlife.mem_report(DONATED, "don").peak_bytes


def test_donation_alias_equality():
    # The committed donated fixture round-trips: every donated param leaf
    # has a same-size output leaf to alias.
    mod = hlo_ir.parse(DONATED)
    assert memlife.donation_alias_findings(mod, "fixture/donated") == []
    # Seeded violation: donates an f32[8] but outputs only an f32[4] —
    # the donation cannot round-trip in place.
    bad = hlo_ir.parse("""\
HloModule bad_donor, buffer_donor={ (0, {}) }

ENTRY main {
  p = f32[8] parameter(0)
  ROOT s = f32[4] slice(p), slice={[0:4]}
}
""")
    msgs = memlife.donation_alias_findings(bad, "bad")
    assert msgs and "cannot round-trip" in msgs[0]


# ---------------------------------------------------------------------------
# audit integration: the peak-memory rule and the per-program stat
# ---------------------------------------------------------------------------

def test_audit_peak_memory_rule_budget():
    over = auditlib.audit_program(UNDONATED, auditlib.ProgramContract(
        name="mem/fixture", hbm_budget_bytes=10_000))
    assert over.rules["peak-memory"] == "fail"
    assert any(f.rule == "peak-memory" for f in over.findings)
    under = auditlib.audit_program(UNDONATED, auditlib.ProgramContract(
        name="mem/fixture", hbm_budget_bytes=2**20))
    assert under.rules["peak-memory"] == "pass"
    assert under.stats["peak_mib"] == pytest.approx(15376 / 2**20, abs=1e-3)


def test_audit_default_budget_is_chip_capacity():
    """hbm_budget_bytes=0 means the single-sourced v5e capacity — the
    fixture sits miles under it."""
    rep = auditlib.audit_program(DONATED, auditlib.ProgramContract(
        name="mem/fixture"))
    assert rep.rules["peak-memory"] == "pass"


# ---------------------------------------------------------------------------
# differential vs the executable: never under XLA's own accounting
# ---------------------------------------------------------------------------

def test_check_against_compiled_synthetic_paths():
    rep = memlife.mem_report(DONATED, "fixture/donated")
    # Unsound: compiled floor above the static peak.
    ms = types.SimpleNamespace(temp_size_in_bytes=20_000,
                               output_size_in_bytes=5_000,
                               argument_size_in_bytes=0)
    bad = memlife.check_against_compiled(rep, ms)
    assert bad and "UNDER the compiled floor" in bad[0]
    # Unmoored: windowed bound far beyond band x compiled total.
    ms2 = types.SimpleNamespace(temp_size_in_bytes=10,
                                output_size_in_bytes=10,
                                argument_size_in_bytes=10)
    loose = memlife.check_against_compiled(rep, ms2, windowed=True)
    assert loose and "unmoored" in loose[0]
    # Sane stats: clean.
    ms3 = types.SimpleNamespace(temp_size_in_bytes=5_000,
                                output_size_in_bytes=5_124,
                                argument_size_in_bytes=5_124)
    assert memlife.check_against_compiled(rep, ms3, windowed=True) == []


def test_static_bound_covers_real_compiled_window():
    """Lower AND compile the real train window; the static peak must
    clear ``memory_analysis()``'s temp+output floor and stay within the
    declared band — the certifier's soundness contract on a living
    executable, not just fixtures."""
    model_zoo.register_model("tiny", tiny_cnn)
    lowered, name = memlife.lower_window(
        "tiny", world=4, window=3, global_batch=64)
    rep = memlife.mem_report(auditlib._hlo_text(lowered), name)
    ms = lowered.compile().memory_analysis()
    floor = ((getattr(ms, "temp_size_in_bytes", 0) or 0)
             + (getattr(ms, "output_size_in_bytes", 0) or 0))
    assert rep.peak_bytes >= floor
    assert memlife.check_against_compiled(rep, ms, windowed=True) == []


# ---------------------------------------------------------------------------
# runtime cross-check: measured residency under the certificate
# ---------------------------------------------------------------------------

def test_runtime_memory_gauge_under_certificate(tmp_path, mesh4):
    """A real windowed run's per-boundary ``memory`` gauge (live device
    bytes) must sit under the window program's static peak — the
    certificate bounds what the process actually holds."""
    model_zoo.register_model("tiny", tiny_cnn)
    tel = Telemetry()
    tr = Trainer(model=tiny_cnn(), strategy="ddp", mesh=mesh4,
                 global_batch=64, data_dir=str(tmp_path), augment=True,
                 limit_train_batches=9, limit_eval_batches=2,
                 log=lambda s: None, telemetry=tel)
    tr.train_model(0)
    gauges = [r["value"] for r in tel.records
              if r["kind"] == "gauge" and r["name"] == "memory"]
    assert gauges, "windowed path emitted no memory gauge"
    assert all("host_rss_peak_mib" in g for g in gauges)
    measured = max(g.get("device_live_mib", 0.0) for g in gauges)
    rep = memlife.window_mem_report(
        "tiny", world=4, window=3, global_batch=64)
    assert 0 < measured <= rep.peak_bytes / 2**20, \
        f"measured {measured} MiB vs certified {rep.peak_mib} MiB"


# ---------------------------------------------------------------------------
# repo self-checks: single-sourced constants, fixture invariants
# ---------------------------------------------------------------------------

def _mini_repo(tmp_path, extra_py=None):
    """A minimal repo tree satisfying the single-source checker."""
    home = tmp_path / "cs744_ddp_tpu" / "analysis" / "costmodel.py"
    home.parent.mkdir(parents=True)
    home.write_text("V5E_BF16_PEAK_FLOPS = 197e12\n"
                    "V5E_HBM_BYTES_PER_S = 819e9\n"
                    "V5E_ICI_BYTES_PER_S = 200e9\n"
                    "V5E_HBM_CAPACITY_BYTES = 16 * 2**30\n")
    for rel, text in (extra_py or {}).items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(text)
    return str(tmp_path)


def test_constants_single_source_repo_and_seeded(tmp_path):
    # The real repo is clean (also enforced by lint_graft + cli).
    assert memlife.check_constants_single_source(REPO) == []
    # Seeded duplicate literal and capacity reassignment both fire.
    root = _mini_repo(tmp_path, {
        "cs744_ddp_tpu/fork.py":
            "PEAK = 197e12\nV5E_HBM_CAPACITY_BYTES = 8 * 2**30\n"})
    findings = memlife.check_constants_single_source(root)
    assert {f.rule for f in findings} == {"memory-constants"}
    msgs = "\n".join(f.message for f in findings)
    assert "197e12" in msgs and "reassigned" in msgs
    # Findings carry the lint_graft --json shape (rule/path/line/message).
    f = findings[0]
    json.dumps({"rule": f.rule, "file": f.path, "line": f.line,
                "message": f.message})
    assert f.line > 0


def test_fixture_invariants_repo_and_seeded(tmp_path):
    assert memlife.check_fixture_invariants(REPO) == []
    # Missing fixtures -> findings, not a crash.
    missing = memlife.check_fixture_invariants(str(tmp_path))
    assert len(missing) == 2
    assert all(f.rule == "memory-fixture" for f in missing)
    # Seeded drift: both files undonated -> the donation delta no longer
    # equals the carried bytes, the invariant breaks loudly.
    for rel in (memlife.FIXTURE_DONATED, memlife.FIXTURE_UNDONATED):
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(UNDONATED)
    assert memlife.check_fixture_invariants(str(tmp_path)) != []


def test_check_memory_composes_both(tmp_path):
    assert memlife.check_memory(REPO) == []
    # A broken tree surfaces findings from BOTH halves through the one
    # entry point lint_graft/cli call.
    root = _mini_repo(tmp_path, {"cs744_ddp_tpu/fork.py": "X = 819e9\n"})
    rules = {f.rule for f in memlife.check_memory(root)}
    assert rules == {"memory-constants", "memory-fixture"}


# ---------------------------------------------------------------------------
# telemetry report: the == memory == section
# ---------------------------------------------------------------------------

def test_telemetry_report_memory_section(tmp_path, monkeypatch):
    monkeypatch.syspath_prepend(os.path.join(REPO, "tools"))
    import telemetry_report
    events = [
        {"kind": "gauge", "name": "memory", "t": 1.0, "epoch": 0,
         "value": {"host_rss_peak_mib": 512.3, "device_live_mib": 17.9,
                   "device_live_arrays": 42}},
        {"kind": "gauge", "name": "memory", "t": 2.0, "epoch": 1,
         "value": {"host_rss_peak_mib": 530.0, "device_live_mib": 18.1,
                   "device_live_arrays": 40}},
    ]
    (tmp_path / "events.jsonl").write_text(
        "".join(json.dumps(e) + "\n" for e in events))
    (tmp_path / "manifest.json").write_text(json.dumps({
        "model": "tiny",
        "audit": {"clean": True, "n_programs": 1, "n_findings": 0,
                  "n_waived": 0,
                  "programs": {"train/window/ddp": {
                      "rules": {"peak-memory": "pass"},
                      "chain_depth": 1, "peak_mib": 18.214}},
                  "findings": [], "waived": []},
    }))
    out = telemetry_report.render(str(tmp_path))
    assert "== memory (measured vs certified) ==" in out
    assert "max      18.10 MiB" in out
    assert "train/window/ddp" in out
    assert "measured within certificate" in out
    # Over-certificate measurement flips the verdict line.
    (tmp_path / "events.jsonl").write_text(json.dumps({
        "kind": "gauge", "name": "memory", "t": 1.0,
        "value": {"device_live_mib": 99.0}}) + "\n")
    assert "EXCEEDS the certified peak" in \
        telemetry_report.render(str(tmp_path))
    # Absent-safe: no gauges, no audit record -> no section.
    (tmp_path / "events.jsonl").write_text("")
    (tmp_path / "manifest.json").write_text(json.dumps({"model": "tiny"}))
    assert "== memory" not in telemetry_report.render(str(tmp_path))
