"""Static-analysis subsystem tests (cs744_ddp_tpu/analysis/).

Four layers, each pinned here:

* ``hlo_ir``   — the structural HLO parser: round-trips every committed
  fixture in tests/assets/hlo/ and agrees DIFFERENTIALLY with the legacy
  regex implementation (kept in utils/hlo_stats as the oracle) on both
  print forms, called computations, async pairs and metadata-poisoned
  modules.
* ``audit``    — the rule engine: every rule catches a deliberately
  seeded violation AND passes the real shipped-program zoo (tiny model,
  4-device CPU mesh) — the acceptance bar is a CLEAN audit of every
  program this repo dispatches, with the strategy depth ladder
  (ddp < allreduce < gather) certified on the lowered programs.
* ``pylint_rules`` / ``tools/lint_graft.py`` — the AST lint: each rule
  fires on a synthetic violation, waivers suppress, and the repo itself
  lints clean (tier-1 gate).
* thread-safety regressions the lint's ``lock-ownership`` rule found
  (MicroBatcher.start) and the Watchdog cancel-vs-fire race, locked in
  behaviorally.
* round-13 whole-program verification — the lock-order deadlock
  detector (``lockgraph``: repo graph certified acyclic on the declared
  partial order, ``*_locked`` caller-holds verified), wire-protocol
  schema conformance (``wire_schema`` against the ``serve/wire.py``
  table, including a deliberately mismatched encoder fixture and the
  corruption sweep), and the static host-round-trip certifier
  (``dispatch``: closed-form bounds matched EXACTLY against the live
  ``host_round_trips`` counter on all three dispatch paths), folded
  into one tier-1 gate (``test_repo_static_verification``).
"""

import glob
import json
import os
import threading
import time
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from cs744_ddp_tpu import models as model_zoo
from cs744_ddp_tpu.analysis import audit as auditlib
from cs744_ddp_tpu.analysis import dispatch as dispatchlib
from cs744_ddp_tpu.analysis import (hlo_ir, lockgraph, memlife,
                                    pylint_rules, stats, wire_schema)
from cs744_ddp_tpu.obs import Telemetry
from cs744_ddp_tpu.serve import wire
from cs744_ddp_tpu.train.loop import Trainer
from cs744_ddp_tpu.utils import hlo_stats as legacy

from tinynet import tiny_cnn

ASSETS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "assets", "hlo")
FIXTURES = sorted(glob.glob(os.path.join(ASSETS, "*.hlo")))
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def setup_module(module):
    model_zoo.register_model("tiny", tiny_cnn)


def _read(path: str) -> str:
    with open(path) as fh:
        return fh.read()


# ---------------------------------------------------------------------------
# hlo_ir: parser round-trip + differential vs the legacy regex oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("path", FIXTURES, ids=os.path.basename)
def test_parser_round_trip(path):
    """parse -> to_text -> parse preserves the accounting-relevant
    structure on every committed fixture (both print forms)."""
    txt = _read(path)
    mod = hlo_ir.parse(txt)
    rt = hlo_ir.parse(mod.to_text())
    assert stats.collective_stats(rt) == stats.collective_stats(mod)
    assert (stats.collective_chain_depth(rt)
            == stats.collective_chain_depth(mod))
    assert rt.donated_param_count() == mod.donated_param_count()
    assert set(rt.computations) == set(mod.computations)


@pytest.mark.parametrize("path", FIXTURES, ids=os.path.basename)
def test_differential_ir_vs_legacy_regex(path):
    """The IR implementation must agree with the legacy regex oracle on
    every committed fixture — the adapter contract of utils/hlo_stats."""
    txt = _read(path)
    assert stats.collective_stats(txt) == legacy.legacy_collective_stats(txt)
    assert (stats.collective_chain_depth(txt)
            == legacy.legacy_collective_chain_depth(txt))
    assert stats.bytes_of_type("(f32[64,10]{1,0}, bf16[3]{0}, token[])") \
        == legacy.legacy_bytes_of_type(
            "(f32[64,10]{1,0}, bf16[3]{0}, token[])")


# Pinned per-fixture numbers: a parser regression that silently changes
# the accounting (rather than erroring) fails here even if old == new.
_FIXTURE_PINS = {
    "train_window_bare.hlo": {"total": 6, "depth": 4},
    "train_window_sigil.hlo": {"total": 6, "depth": 4},
    # Collective inside a fused computation, a called computation and a
    # custom-call's called_computations; depth SUMS operand chains with
    # callee-internal depth across fusion -> call -> custom-call.
    "called_comp.hlo": {"total": 3, "depth": 4,
                        "counts": {"all-reduce": 3}},
    # Async start/done pairs counted once each (start: count, done:
    # bytes), chained all-reduce -> all-gather.
    "async_pair.hlo": {"total": 2, "depth": 2, "mib": 0.07,
                       "counts": {"all-reduce": 1, "all-gather": 1}},
    # op_name strings naming other instructions, braces and escaped
    # quotes inside source_file paths: none of it may poison the graph.
    "metadata_heavy.hlo": {"total": 2, "depth": 2,
                           "counts": {"all-reduce": 2}},
}


@pytest.mark.parametrize("name", sorted(_FIXTURE_PINS), ids=str)
def test_fixture_pins(name):
    txt = _read(os.path.join(ASSETS, name))
    pin = _FIXTURE_PINS[name]
    s = stats.collective_stats(txt)
    assert s["total_count"] == pin["total"], s
    assert stats.collective_chain_depth(txt) == pin["depth"]
    if "counts" in pin:
        assert {op: e["count"] for op, e in s["ops"].items()} \
            == pin["counts"], s
    if "mib" in pin:
        assert s["total_result_mib"] == pin["mib"], s


def test_parser_called_computations():
    mod = hlo_ir.parse(_read(os.path.join(ASSETS, "called_comp.hlo")))
    entry = mod.computations["main"]
    assert mod.entry == "main"
    assert list(entry.instructions["fus"].called) == ["fused_reduce"]
    assert list(entry.instructions["c"].called) == ["helper_call"]
    assert list(entry.instructions["cc"].called) == ["helper_call"]
    assert entry.instructions["cc"].attr("custom_call_target") \
        == '"my_target"'
    assert entry.root.name == "out"
    # Bodies referenced by while show up too (the host-sync rule's input).
    sig = hlo_ir.parse(_read(os.path.join(ASSETS,
                                          "train_window_sigil.hlo")))
    w = sig.computations["main.4"].instructions["w"]
    assert sorted(w.called) == ["wbody.2", "wcond.3"]


def test_parser_donation_header():
    txt = ("HloModule donate, buffer_donor={ (0, {}), (1, {}) }, "
           "entry_computation_layout={(f32[4]{0},f32[4]{0})->f32[4]{0}}\n"
           "\n"
           "ENTRY main {\n"
           "  p0 = f32[4] parameter(0)\n"
           "  p1 = f32[4] parameter(1)\n"
           "  ROOT s = f32[4] add(p0, p1)\n"
           "}\n")
    assert hlo_ir.parse(txt).donated_param_count() == 2
    bare = txt.replace("buffer_donor={ (0, {}), (1, {}) }, ", "")
    assert hlo_ir.parse(bare).donated_param_count() == 0


# ---------------------------------------------------------------------------
# audit: every rule catches a seeded violation (positive) and stays quiet
# on conforming programs (negative)
# ---------------------------------------------------------------------------

_CHAIN3 = """\
HloModule chain3

radd {
  x = f32[] parameter(0)
  y = f32[] parameter(1)
  ROOT s = f32[] add(x, y)
}

ENTRY main {
  p = f32[64] parameter(0)
  a1 = f32[64] all-reduce(p), channel_id=1, to_apply=radd
  a2 = f32[64] all-reduce(a1), channel_id=2, to_apply=radd
  a3 = f32[64] all-reduce(a2), channel_id=3, to_apply=radd
  ROOT o = f32[64] add(a3, a3)
}
"""


def _contract(**kw):
    kw.setdefault("name", "t/prog")
    return auditlib.ProgramContract(**kw)


def _rules_of(report):
    return {r for r, v in report.rules.items() if v == "fail"}


def test_rule_collective_contract_seeded():
    # single/world-1 programs must be collective-free.
    r = auditlib.audit_program(_CHAIN3, _contract(strategy="single"))
    assert _rules_of(r) == {"collective-contract"}
    # ddp with fewer buckets than leaves must NOT serialize per leaf:
    # a 3-deep chain against nbuckets=1/nleaves=3 is the fusion win lost.
    r = auditlib.audit_program(_CHAIN3, _contract(
        strategy="ddp", world=4, nleaves=3, nbuckets=1))
    assert _rules_of(r) == {"collective-contract"}
    assert "fusion win lost" in r.findings[0].message
    # gather needs all-gathers; an all-reduce-only program fails.
    r = auditlib.audit_program(_CHAIN3, _contract(
        strategy="gather", world=4, nleaves=2))
    assert _rules_of(r) == {"collective-contract"}


def test_rule_collective_contract_conforming():
    # The same chain IS a conforming per-param allreduce tier.
    r = auditlib.audit_program(_CHAIN3, _contract(
        strategy="allreduce", world=4, nleaves=3))
    assert r.passed, r.findings
    assert r.stats["collectives"] == {"all-reduce": 3}
    assert r.stats["chain_depth"] == 3
    # And a genuinely collective-free program audits clean as single.
    clean = ("HloModule empty\n\nENTRY main {\n"
             "  ROOT p = f32[4] parameter(0)\n}\n")
    assert auditlib.audit_program(clean, _contract(strategy="single")).passed


_WIRE = """\
HloModule wire

radd {
  x = DT[] parameter(0)
  y = DT[] parameter(1)
  ROOT s = DT[] add(x, y)
}

ENTRY main {
  p = DT[64] parameter(0)
  q = DT[64] parameter(1)
  a1 = DT[64] all-reduce(p), channel_id=1, to_apply=radd
  a2 = DT[64] all-reduce(q), channel_id=2, to_apply=radd
  ROOT o = DT[64] add(a1, a2)
}
"""


def test_rule_overlap_contract_seeded():
    # A 3-deep post-backward chain is exactly what the overlap tier must
    # NOT lower — same fused count as ddp, but fully serialized.
    r = auditlib.audit_program(_CHAIN3, _contract(
        strategy="overlap", world=4, nleaves=3, nbuckets=3))
    assert _rules_of(r) == {"collective-contract"}
    assert "must not chain" in r.findings[0].message
    # Two INDEPENDENT all-reduces (chain depth 1) conform.
    r = auditlib.audit_program(_WIRE.replace("DT", "f32"), _contract(
        strategy="overlap", world=4, nleaves=2, nbuckets=2))
    assert r.passed, r.findings
    # Fewer reduces than buckets: a bucket went unsynced.
    assert not auditlib.audit_program(
        _WIRE.replace("DT", "f32"), _contract(
            strategy="overlap", world=4, nleaves=3, nbuckets=3)).passed


_GATED = """\
HloModule gated

radd {
  x = f32[] parameter(0)
  y = f32[] parameter(1)
  ROOT s = f32[] add(x, y)
}

ENTRY main {
  a = f32[8,8] parameter(0)
  b = f32[8,8] parameter(1)
  d1 = f32[8,8] dot(a, b), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  d2 = f32[8,8] dot(b, a), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  SRC
  ar = f32[8,8] all-reduce(red), channel_id=1, to_apply=radd
  ROOT o = f32[8,8] add(ar, SINK)
}
"""


def test_rule_overlap_dot_cone_seeded():
    """The overlap tier's scheduling evidence: at least one collective's
    operand cone must exclude part of the backward — a collective gated
    on EVERY dot cannot have been issued early."""
    allgated = (_GATED.replace("SRC", "red = f32[8,8] add(d1, d2)")
                .replace("SINK", "ar"))
    r = auditlib.audit_program(allgated, _contract(
        strategy="overlap", world=4, nleaves=1, nbuckets=1))
    assert _rules_of(r) == {"collective-contract"}
    assert "operand cone" in r.findings[0].message
    # The same program with the reduce gated on d1 only: d2 is outside
    # the cone, so the collective COULD overlap it — conforming.
    partial = (_GATED.replace("SRC", "red = f32[8,8] add(d1, d1)")
               .replace("SINK", "d2"))
    assert auditlib.audit_program(partial, _contract(
        strategy="overlap", world=4, nleaves=1, nbuckets=1)).passed


def test_rule_compressed_bytes_seeded():
    c2 = dict(strategy="compress-bf16", world=4, nleaves=2,
              param_bytes=512, compress_ratio=2.0)
    # An uncompressed f32 wire (512 B) against the 2x contract: caught.
    r = auditlib.audit_program(_WIRE.replace("DT", "f32"), _contract(**c2))
    assert _rules_of(r) == {"collective-contract"}
    assert "compression is not real" in r.findings[0].message
    # The genuine bf16 wire (256 B = param_bytes/2): conforming.
    assert auditlib.audit_program(_WIRE.replace("DT", "bf16"),
                                  _contract(**c2)).passed, "bf16 wire"
    # int8 contract (4x): bf16 wire fails, s8 wire (128 B) passes.
    c4 = dict(c2, strategy="compress-int8", compress_ratio=4.0)
    assert not auditlib.audit_program(_WIRE.replace("DT", "bf16"),
                                      _contract(**c4)).passed
    assert auditlib.audit_program(_WIRE.replace("DT", "s8"),
                                  _contract(**c4)).passed
    # Declared aux allowance (BN pmeans, int8 scale pmax) is excluded
    # from the gradient wire before the ratio is enforced.
    assert auditlib.audit_program(
        _WIRE.replace("DT", "f32"),
        _contract(**dict(c2, aux_bytes=256))).passed
    # Every leaf must still be reduced.
    assert not auditlib.audit_program(
        _WIRE.replace("DT", "bf16"),
        _contract(**dict(c2, nleaves=3))).passed


_LEAK = """\
HloModule leak

ENTRY main {
  a = bf16[8,8] parameter(0)
  b = bf16[8,8] parameter(1)
  ROOT d = DT[8,8] dot(a, b), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}
"""


def test_rule_dtype_leak():
    bad = auditlib.audit_program(_LEAK.replace("DT", "f32"),
                                 _contract(precision="bf16"))
    assert _rules_of(bad) == {"dtype-leak"}
    assert "dot" in bad.findings[0].message
    ok = auditlib.audit_program(_LEAK.replace("DT", "bf16"),
                                _contract(precision="bf16"))
    assert ok.passed, ok.findings
    # An f32-declared program may dot in f32 — the rule is bf16-only.
    assert auditlib.audit_program(_LEAK.replace("DT", "f32"),
                                  _contract(precision="f32")).passed


def test_rule_donation():
    # Both donated params need a same-size output leaf to alias (round 20:
    # donation is checked as aliased-bytes equality, not just leaf count).
    donated = ("HloModule m, buffer_donor={ (0, {}), (1, {}) }\n\n"
               "ENTRY main {\n  p0 = f32[4] parameter(0)\n"
               "  p1 = f32[4] parameter(1)\n"
               "  s = f32[4] add(p0, p1)\n"
               "  d = f32[4] multiply(p0, p1)\n"
               "  ROOT t = (f32[4], f32[4]) tuple(s, d)\n}\n")
    undonated = ("HloModule m\n\nENTRY main {\n"
                 "  p0 = f32[4] parameter(0)\n"
                 "  p1 = f32[4] parameter(1)\n"
                 "  ROOT s = f32[4] add(p0, p1)\n}\n")
    bad = auditlib.audit_program(undonated, _contract(
        donates_state=True, n_state_leaves=2))
    assert _rules_of(bad) == {"donation"}
    ok = auditlib.audit_program(donated, _contract(
        donates_state=True, n_state_leaves=2))
    assert ok.passed, ok.findings
    assert ok.stats["donated"] == 2
    # More state leaves than donated entries: still a miss.
    assert not auditlib.audit_program(donated, _contract(
        donates_state=True, n_state_leaves=3)).passed


_HOST_SYNC = """\
HloModule host_sync

wbody {
  p = f32[4] parameter(0)
  cb = f32[4] custom-call(p), custom_call_target="xla_ffi_python_cpu_callback"
  ROOT r = f32[4] add(cb, cb)
}

wcond {
  q = f32[4] parameter(0)
  ROOT lt = pred[] constant(false)
}

ENTRY main {
  a = f32[4] parameter(0)
  w = f32[4] while(a), body=wbody, condition=wcond
  ROOT out = f32[4] add(w, w)
}
"""


def test_rule_host_sync_hlo():
    bad = auditlib.audit_program(_HOST_SYNC, _contract())
    assert _rules_of(bad) == {"host-sync"}
    assert "wbody" in bad.findings[0].message
    # The same callback OUTSIDE any while body is legal (one-shot host
    # call at dispatch, not one per scanned step).
    flat = _HOST_SYNC.replace(
        "w = f32[4] while(a), body=wbody, condition=wcond",
        'w = f32[4] custom-call(a), custom_call_target='
        '"xla_ffi_python_cpu_callback"')
    assert auditlib.audit_program(flat, _contract()).passed


def test_rule_host_sync_jaxpr():
    clean_hlo = ("HloModule m\n\nENTRY main {\n"
                 "  ROOT p = f32[4] parameter(0)\n}\n")

    def cb(x):
        return np.asarray(x)

    def body_with_callback(xs):
        def step(c, x):
            y = jax.pure_callback(
                cb, jax.ShapeDtypeStruct(x.shape, x.dtype), x)
            return c + jnp.sum(y), None
        out, _ = jax.lax.scan(step, 0.0, xs)
        return out

    bad_jaxpr = jax.make_jaxpr(body_with_callback)(jnp.ones((3, 2)))
    bad = auditlib.audit_program(clean_hlo, _contract(), jaxpr=bad_jaxpr)
    assert _rules_of(bad) == {"host-sync"}
    assert "callback" in bad.findings[0].message

    def body_plain(xs):
        def step(c, x):
            return c + jnp.sum(x), None
        out, _ = jax.lax.scan(step, 0.0, xs)
        return out

    ok_jaxpr = jax.make_jaxpr(body_plain)(jnp.ones((3, 2)))
    assert auditlib.audit_program(clean_hlo, _contract(),
                                  jaxpr=ok_jaxpr).passed


_BAKED = """\
HloModule baked

ENTRY main {{
  c = f32[{N}]{{0}} constant({{...}})
  p = f32[{N}]{{0}} parameter(0)
  ROOT o = f32[{N}]{{0}} add(c, p)
}}
"""


def test_rule_baked_constants():
    big = _BAKED.format(N=400000)    # 1.6 MB > the 1 MiB default
    bad = auditlib.audit_program(big, _contract())
    assert _rules_of(bad) == {"baked-constants"}
    assert "1600000 bytes" in bad.findings[0].message
    # Under the threshold (or with a raised contract limit): clean.
    assert auditlib.audit_program(_BAKED.format(N=1000),
                                  _contract()).passed
    assert auditlib.audit_program(big, _contract(
        max_constant_bytes=1 << 21)).passed


def test_waivers():
    c = _contract(name="train/step/ddp", strategy="ddp", world=4,
                  nleaves=3, nbuckets=1)
    # Global waiver: finding moves to waived, program passes, rule is
    # recorded as waived (still visible in the manifest).
    r = auditlib.audit_program(_CHAIN3, c, waive=("collective-contract",))
    assert r.passed and r.waived
    assert r.rules["collective-contract"] == "waived"
    # Glob-scoped waiver only applies to matching program names.
    r = auditlib.audit_program(_CHAIN3, c,
                               waive=("collective-contract@serve/*",))
    assert not r.passed
    r = auditlib.audit_program(_CHAIN3, c,
                               waive=("collective-contract@train/*",))
    assert r.passed


def test_certify_ladder_seeded():
    ladder, findings = auditlib._certify_ladder(
        {"gather": 2, "allreduce": 6, "ddp": 1}, nleaves=6, nbuckets=1,
        program="strategy-ladder")
    assert len(findings) == 1 and "gather" in findings[0].message
    _, findings = auditlib._certify_ladder(
        {"gather": 12, "allreduce": 6, "ddp": 6}, nleaves=6, nbuckets=1,
        program="strategy-ladder")
    assert len(findings) == 1 and "ddp" in findings[0].message
    _, findings = auditlib._certify_ladder(
        {"gather": 12, "allreduce": 6, "ddp": 1}, nleaves=6, nbuckets=1,
        program="strategy-ladder")
    assert not findings


# ---------------------------------------------------------------------------
# audit: the real program zoo must be CLEAN (the PR's acceptance bar)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def zoo():
    model_zoo.register_model("tiny", tiny_cnn)
    return auditlib.audit_zoo(model="tiny", global_batch=64, window=3,
                              serve_buckets=(2,), num_devices=4,
                              collect_hlo=True)


def test_zoo_audits_clean(zoo):
    assert zoo.clean, "\n".join(zoo.format_lines())
    # 8 strategies x 3 train paths + eval + 1 serving bucket.
    assert len(zoo.reports) == 26
    names = {r.program for r in zoo.reports}
    assert "train/window/ddp" in names and "eval/window" in names
    assert "serve/b2/f32" in names
    assert "train/window/overlap" in names
    assert "train/window/compress-int8" in names
    assert "train/window/powersgd" in names


def test_zoo_depth_ladder(zoo):
    """The paper's cost ordering, certified on the lowered programs:
    bucketed ddp strictly shallower than per-param allreduce, which is
    strictly shallower than the two-phase gather tier."""
    lad = zoo.ladder
    assert lad["ddp"] < lad["allreduce"] < lad["gather"], lad
    assert lad["single"] == 0
    # tiny_cnn: 6 param leaves, one ~25 MB bucket — the depths are the
    # tiers' defining shape (2/leaf, 1/leaf, 1/bucket).
    assert lad["gather"] == 2 * lad["allreduce"]
    assert lad["ddp"] == 1
    # Round-7 tiers, recorded informatively alongside the certified trio:
    # overlap never chains (depth 1 regardless of bucket count); the
    # compressed tiers chain per leaf like allreduce (+1 for int8's
    # shared-scale pmax); powersgd's two-psum leaves sit deepest.
    assert lad["overlap"] == 1
    assert lad["compress-bf16"] == lad["allreduce"]
    assert lad["compress-int8"] == lad["allreduce"] + 1
    assert lad["powersgd"] >= lad["allreduce"]


def test_zoo_summary_shape(zoo):
    s = zoo.summary()
    assert s["clean"] and s["n_findings"] == 0
    assert s["n_programs"] == len(zoo.reports)
    assert set(s["programs"]["train/window/ddp"]["rules"]) \
        == set(auditlib.RULES)
    lines = zoo.format_lines()
    assert lines[-1].startswith("[audit] CLEAN")
    json.dumps(s)   # manifest-ready: JSON-serializable as-is


def test_zoo_bf16_clean():
    """The bf16 window program carries no f32 dot/conv leak — the
    dtype-leak rule passes on the real mixed-precision lowering."""
    res = auditlib.audit_zoo(model="tiny", global_batch=64, window=3,
                             precision="bf16", strategies=("ddp",),
                             paths=("window",), include_eval=False,
                             num_devices=4)
    assert res.clean, "\n".join(res.format_lines())
    assert res.reports[0].rules["dtype-leak"] == "pass"


# ---------------------------------------------------------------------------
# CLI wiring: --audit strict exit codes, manifest recording
# ---------------------------------------------------------------------------

def test_cli_audit_zoo_strict_clean(capsys):
    from cs744_ddp_tpu import cli
    cli.main(["--audit-zoo", "--audit", "strict", "--model", "tiny",
              "--batch-size", "64", "--num-devices", "4",
              "--serve-buckets", "2"])
    out = capsys.readouterr().out
    assert "[audit] CLEAN" in out
    assert "[audit] strategy depth ladder" in out


def test_cli_audit_strict_exits_2_on_finding(capsys):
    from cs744_ddp_tpu import cli
    from cs744_ddp_tpu.obs import NULL
    dirty = auditlib.AuditResult(reports=[auditlib.audit_program(
        _CHAIN3, _contract(strategy="single"))])
    assert not dirty.clean
    args = types.SimpleNamespace(audit="strict")
    with pytest.raises(SystemExit) as exc:
        cli._apply_audit(args, NULL, dirty)
    assert exc.value.code == 2
    # warn mode reports the same findings but never exits.
    args.audit = "warn"
    cli._apply_audit(args, NULL, dirty)
    assert "DIRTY" in capsys.readouterr().out


def test_record_audit_disabled_recorder_untouched():
    class Exploding:
        enabled = False

        def __getattr__(self, name):
            raise AssertionError(f"telemetry.{name} touched while disabled")

    res = auditlib.AuditResult(reports=[auditlib.audit_program(
        _CHAIN3, _contract(strategy="allreduce", world=4, nleaves=3))])
    auditlib.record_audit(Exploding(), res)   # must not raise


def test_record_audit_merges_into_manifest(tmp_path):
    from cs744_ddp_tpu.obs import Telemetry
    tel = Telemetry(str(tmp_path))
    tel.write_manifest({"model": "tiny", "mode": "test"})
    res = auditlib.AuditResult(reports=[auditlib.audit_program(
        _CHAIN3, _contract(strategy="allreduce", world=4, nleaves=3))])
    auditlib.record_audit(tel, res)
    manifest = json.loads((tmp_path / "manifest.json").read_text())
    assert manifest["model"] == "tiny"          # merged, not clobbered
    assert manifest["audit"]["clean"] is True
    assert manifest["audit"]["programs"]["t/prog"]["chain_depth"] == 3
    tel.finalize()


def test_telemetry_report_renders_audit(tmp_path, monkeypatch):
    monkeypatch.syspath_prepend(os.path.join(REPO, "tools"))
    import telemetry_report
    (tmp_path / "events.jsonl").write_text("")
    (tmp_path / "manifest.json").write_text(json.dumps({
        "model": "tiny",
        "audit": {"clean": False, "n_programs": 2, "n_findings": 1,
                  "n_waived": 0,
                  "programs": {
                      "train/window/ddp": {
                          "rules": {"collective-contract": "pass"},
                          "chain_depth": 1},
                      "train/step/single": {
                          "rules": {"collective-contract": "fail"},
                          "chain_depth": 3}},
                  "findings": [{"rule": "collective-contract",
                                "program": "train/step/single",
                                "message": "expected collective-free"}],
                  "waived": [],
                  "ladder": {"ddp": 1, "allreduce": 6, "gather": 12}},
    }))
    out = telemetry_report.render(str(tmp_path))
    assert "== program audit ==" in out
    assert "DIRTY: 2 programs, 1 findings" in out
    assert "FAIL collective-contract" in out
    assert "strategy depth ladder" in out
    # Tolerant when absent: a run with no audit record renders without
    # the section (older manifests unchanged).
    (tmp_path / "manifest.json").write_text(json.dumps({"model": "tiny"}))
    assert "program audit" not in telemetry_report.render(str(tmp_path))


# ---------------------------------------------------------------------------
# AST lint: each rule fires on a seeded violation; waivers suppress;
# the repo itself is clean
# ---------------------------------------------------------------------------

_SRC_UNFENCED = """\
import time

class T:
    def run(self, x):
        t0 = time.time()
        loss = self.train_window(x)
        return time.time() - t0
"""

_SRC_FENCED = """\
import time
import numpy as np

class T:
    def run(self, x):
        t0 = time.time()
        loss = np.asarray(self.train_window(x))
        return time.time() - t0
"""


def test_lint_unfenced_timing():
    bad = pylint_rules.lint_source(_SRC_UNFENCED, "bad.py")
    assert [f.rule for f in bad] == ["unfenced-timing"]
    assert bad[0].line == 6
    # A fence WRAPPING the dispatch synchronizes where it returns.
    assert pylint_rules.lint_source(_SRC_FENCED, "ok.py") == []
    # Timing with no dispatch inside is plain host timing: out of scope.
    host_only = _SRC_UNFENCED.replace("self.train_window(x)", "len(x)")
    assert pylint_rules.lint_source(host_only, "ok.py") == []
    # Round-7 overlap scheduling: timing a PER-BUCKET dispatch loop is the
    # same hazard — the loop queues every bucket's collective and the
    # timer stops before any of them ran.  The rule must see through the
    # loop nesting (the overlap tier's bucket walk is in the default lint
    # targets).
    bucketed = _SRC_UNFENCED.replace(
        "loss = self.train_window(x)",
        "for b in x:\n            loss = self.train_step(b)")
    bad = pylint_rules.lint_source(bucketed, "bad.py")
    assert [f.rule for f in bad] == ["unfenced-timing"]


_SRC_THREAD_JNP = """\
import threading
import jax.numpy as jnp

def worker(q):
    q.put(jnp.ones(3))

def start(q):
    return threading.Thread(target=worker, args=(q,)).start()
"""


def test_lint_thread_jnp():
    bad = pylint_rules.lint_source(_SRC_THREAD_JNP, "bad.py")
    assert [f.rule for f in bad] == ["thread-jnp"]
    ok = _SRC_THREAD_JNP.replace("jnp.ones(3)", "[1, 2, 3]")
    assert pylint_rules.lint_source(ok, "ok.py") == []
    # The same jnp use OUTSIDE any thread entry is fine.
    no_thread = _SRC_THREAD_JNP.replace("threading.Thread(target=worker, "
                                        "args=(q,)).start()", "worker")
    assert pylint_rules.lint_source(no_thread, "ok.py") == []


_SRC_UNLOCKED = """\
import threading

class Q:
    def __init__(self):
        self._lock = threading.Lock()
        self._items = []

    def put(self, x):
        with self._lock:
            self._items.append(x)

    def drain(self):
        self._items = []
"""


def test_lint_lock_ownership():
    bad = pylint_rules.lint_source(_SRC_UNLOCKED, "bad.py")
    assert [f.rule for f in bad] == ["lock-ownership"]
    assert bad[0].line == 13
    assert "drain" in bad[0].message
    ok = _SRC_UNLOCKED.replace(
        "    def drain(self):\n        self._items = []",
        "    def drain(self):\n        with self._lock:\n"
        "            self._items = []")
    assert pylint_rules.lint_source(ok, "ok.py") == []


def test_lint_waivers():
    waived = _SRC_UNLOCKED.replace(
        "    def drain(self):\n        self._items = []",
        "    def drain(self):\n"
        "        self._items = []   # lint: ok(lock-ownership)")
    assert pylint_rules.lint_source(waived, "w.py") == []
    generic = _SRC_UNLOCKED.replace(
        "    def drain(self):\n        self._items = []",
        "    def drain(self):\n        self._items = []   # lint: ok")
    assert pylint_rules.lint_source(generic, "w.py") == []
    # A waiver for a DIFFERENT rule does not suppress.
    wrong = _SRC_UNLOCKED.replace(
        "    def drain(self):\n        self._items = []",
        "    def drain(self):\n"
        "        self._items = []   # lint: ok(thread-jnp)")
    assert [f.rule for f in pylint_rules.lint_source(wrong, "w.py")] \
        == ["lock-ownership"]


_SRC_SPAN_BARE = """\
def emit(tel, t0, ctx):
    tel.span_event("sched_queue", t0, 0.01, bucket=4)
"""

_SRC_SPAN_SPLAT = """\
def emit(tel, t0, ctx):
    tel.span_event("sched_queue", t0, 0.01, bucket=4, **ctx.attrs())
"""


def test_lint_span_hygiene_traced_names():
    # A distributed-trace span without its join keys is invisible to the
    # cross-process aggregation — the rule catches the emit site.
    bad = pylint_rules.lint_source(_SRC_SPAN_BARE, "bad.py")
    assert [f.rule for f in bad] == ["span-hygiene"]
    assert "sched_queue" in bad[0].message
    # **ctx.attrs() splat satisfies it; so does an explicit trace_id=.
    assert pylint_rules.lint_source(_SRC_SPAN_SPLAT, "ok.py") == []
    explicit = _SRC_SPAN_BARE.replace("bucket=4", "trace_id=tid")
    assert pylint_rules.lint_source(explicit, "ok.py") == []
    # Splatting a LOCAL assigned from .attrs() counts too (the frontend
    # builds attrs dicts before adding reply fields).
    via_var = ("def emit(tel, t0, ctx):\n"
               "    attrs = ctx.attrs()\n"
               "    attrs['status'] = 'ok'\n"
               "    tel.span_event('frontend_request', t0, 0.01, **attrs)\n")
    assert pylint_rules.lint_source(via_var, "ok.py") == []
    # Non-traced span names are out of scope entirely.
    other = _SRC_SPAN_BARE.replace("sched_queue", "host_augment")
    assert pylint_rules.lint_source(other, "ok.py") == []


def test_lint_span_hygiene_batch_names_and_waiver():
    # Batch-level engine spans cover a whole dispatch: they need the
    # member batcher trace ids (traces=) instead of one trace_id.
    bad = ("def emit(tel, t0):\n"
           "    tel.span_event('serve_dispatch', t0, 0.01, bucket=8)\n")
    finds = pylint_rules.lint_source(bad, "bad.py")
    assert [f.rule for f in finds] == ["span-hygiene"]
    assert "traces=" in finds[0].message
    ok = bad.replace("bucket=8", "traces=list(ids)")
    assert pylint_rules.lint_source(ok, "ok.py") == []
    waived = bad.replace(
        "bucket=8)", "bucket=8)  # lint: ok(span-hygiene)")
    assert pylint_rules.lint_source(waived, "w.py") == []


def test_repo_lints_clean():
    """Tier-1 gate: the shipped tree carries none of the four hazards
    (same check tools/lint_graft.py runs standalone)."""
    targets = [os.path.join(REPO, t) for t in pylint_rules.DEFAULT_TARGETS]
    findings = pylint_rules.lint_paths(targets)
    assert findings == [], "\n".join(
        f"{f.path}:{f.line}: [{f.rule}] {f.message}" for f in findings)


def test_lint_graft_cli(tmp_path, monkeypatch, capsys):
    monkeypatch.syspath_prepend(os.path.join(REPO, "tools"))
    import lint_graft
    bad = tmp_path / "bad.py"
    bad.write_text(_SRC_UNLOCKED)
    assert lint_graft.main([str(bad)]) == 1
    out = capsys.readouterr().out
    assert "[lock-ownership]" in out and "1 finding(s)" in out
    ok = tmp_path / "ok.py"
    ok.write_text("x = 1\n")
    assert lint_graft.main([str(ok)]) == 0
    assert "lint_graft: clean" in capsys.readouterr().out


def test_lint_graft_cli_json(tmp_path, monkeypatch, capsys):
    """--json emits a machine-readable findings array (CI annotation)
    with exit codes unchanged: 1 on findings, 0 clean."""
    monkeypatch.syspath_prepend(os.path.join(REPO, "tools"))
    import lint_graft
    bad = tmp_path / "bad.py"
    bad.write_text(_SRC_UNLOCKED)
    assert lint_graft.main(["--json", str(bad)]) == 1
    payload = json.loads(capsys.readouterr().out)
    assert len(payload) == 1
    (f,) = payload
    assert set(f) == {"rule", "file", "line", "message"}
    assert f["rule"] == "lock-ownership" and f["line"] == 13
    assert f["file"].endswith("bad.py") and "drain" in f["message"]
    ok = tmp_path / "ok.py"
    ok.write_text("x = 1\n")
    assert lint_graft.main(["--json", str(ok)]) == 0
    assert json.loads(capsys.readouterr().out) == []


# ---------------------------------------------------------------------------
# Thread-safety regressions (satellite 2): the lock-ownership findings,
# fixed and locked in behaviorally
# ---------------------------------------------------------------------------

def test_microbatcher_lifecycle_locked():
    """start() historically wrote _stop/_worker without the condition —
    racing _enqueue's locked reads.  Now the whole transition happens
    under self._cond and the assertion-mode check enforces it."""
    from cs744_ddp_tpu.serve import InferenceEngine, MicroBatcher
    model_zoo.register_model("tiny", tiny_cnn)
    eng = InferenceEngine("tiny", buckets=(2, 4), seed=0)
    eng.startup()
    mb = MicroBatcher(eng, max_wait_ms=1.0)
    # The ownership assertion itself: outside the lock it trips, under
    # the lock it passes (the worker/enqueue paths call it while locked).
    with pytest.raises(AssertionError, match="without holding"):
        mb._assert_owned()
    with mb._cond:
        mb._assert_owned()
    with mb:
        with pytest.raises(RuntimeError, match="already started"):
            mb.start()
        rng = np.random.default_rng(0)
        img = rng.integers(0, 256, (2, 32, 32, 3), dtype=np.uint8)
        assert mb.submit(img).result(timeout=30).shape == (2, 10)
    # Stopped and drained: the queue rejects, and a restart works.
    with pytest.raises(RuntimeError, match="not running"):
        mb.submit(img)
    with mb:
        assert mb.submit(img).result(timeout=30).shape == (2, 10)


def test_watchdog_cancel_vs_fire_race():
    """Timer.cancel does not wait for an in-flight callback: a watchdog
    whose body already completed must NEVER count a timeout afterwards.
    __exit__ marks it cancelled under the lock; a late _fire is inert."""
    from cs744_ddp_tpu.ft.supervisor import Watchdog
    fired = []
    wd = Watchdog(10.0, on_timeout=fired.append)
    with wd:
        pass
    # Simulate the in-flight timer thread firing AFTER __exit__.
    wd._fire()
    assert not wd.fired and fired == []
    # The genuine-timeout path still works and fires exactly once.
    with Watchdog(0.005, on_timeout=fired.append) as wd2:
        deadline = time.time() + 5.0
        while not wd2.fired and time.time() < deadline:
            time.sleep(0.005)
    assert wd2.fired and len(fired) == 1
    wd2._fire()           # late duplicate after exit: still inert
    assert len(fired) == 1


_SRC_DECLARED = """\
import threading

class Coord:
    _lock_owned = ("world", "members")

    def __init__(self):
        self._lock = threading.Lock()
        self.world = 4
        self.members = (0, 1, 2, 3)

    def shrink(self):
        self.world = 1
"""


def test_lint_lock_owned_declaration_guards_from_first_write():
    """A class-level ``_lock_owned`` tuple declares attributes lock-owned
    even when NO locked write is in view — a new method mutating them
    unlocked fails before any locked counterpart exists (the elastic
    coordinator's membership contract)."""
    bad = pylint_rules.lint_source(_SRC_DECLARED, "bad.py")
    assert [f.rule for f in bad] == ["lock-ownership"]
    assert "shrink" in bad[0].message and "world" in bad[0].message
    ok = _SRC_DECLARED.replace(
        "    def shrink(self):\n        self.world = 1",
        "    def shrink(self):\n        with self._lock:\n"
        "            self.world = 1")
    assert pylint_rules.lint_source(ok, "ok.py") == []
    # Undeclared attributes keep the heuristic-only semantics: a write
    # that is never locked anywhere is not flagged.
    free = _SRC_DECLARED.replace('("world", "members")', '("members",)')
    assert pylint_rules.lint_source(free, "free.py") == []
    # __init__ stays exempt (construction happens-before sharing), and
    # non-literal declaration elements are ignored, not crashed on.
    dynamic = _SRC_DECLARED.replace('("world", "members")',
                                    '("members",) + EXTRA')
    assert pylint_rules.lint_source(
        "EXTRA = ()\n" + dynamic, "dyn.py") == []


def test_lint_lock_owned_declaration_needs_a_lock():
    # Without a lock attribute the rule (and the declaration) is inert.
    no_lock = "class C:\n    _lock_owned = ('x',)\n" \
              "    def f(self):\n        self.x = 1\n"
    assert pylint_rules.lint_source(no_lock, "n.py") == []


_SRC_ROUTER = """\
import threading

class Router:
    _lock_owned = ("_routed", "_failovers")

    def __init__(self):
        self._lock = threading.Lock()
        self._routed = 0
        self._failovers = 0

    def submit(self):
        with self._lock:
            self._routed += 1

    def _handle_death(self):
        self._failovers += 1
"""


def test_lint_lock_owned_covers_router_shape():
    """Round 9: the serving router's failover counter is bumped from a
    scheduler worker thread, not the caller's — an unlocked write in the
    death handler is exactly the race the declaration must catch."""
    bad = pylint_rules.lint_source(_SRC_ROUTER, "bad.py")
    assert [f.rule for f in bad] == ["lock-ownership"]
    assert "_handle_death" in bad[0].message \
        and "_failovers" in bad[0].message
    ok = _SRC_ROUTER.replace(
        "    def _handle_death(self):\n        self._failovers += 1",
        "    def _handle_death(self):\n        with self._lock:\n"
        "            self._failovers += 1")
    assert pylint_rules.lint_source(ok, "ok.py") == []


def test_serving_tier_declares_lock_ownership():
    """The live router/scheduler/frontend classes carry ``_lock_owned``
    declarations, so the repo-wide lint gate (test_repo_lints_clean)
    guards their mutable state from first write — not only after a
    locked counterpart exists somewhere."""
    from cs744_ddp_tpu.serve.frontend import FrontendClient, ServingFrontend
    from cs744_ddp_tpu.serve.router import ReplicaRouter
    from cs744_ddp_tpu.serve.scheduler import ServiceModel, SLOScheduler
    assert set(ReplicaRouter._lock_owned) >= {"_routed", "_failovers"}
    assert set(SLOScheduler._lock_owned) >= {"_pending", "_inflight",
                                             "_dead", "_stop"}
    assert set(ServiceModel._lock_owned) >= {"_ewma"}
    assert set(ServingFrontend._lock_owned) >= {"_conns", "_running"}
    assert set(FrontendClient._lock_owned) >= {"_futs", "_next_id"}


def test_zoo_shrunk_world_audits_clean():
    """Round 6: the program set the elastic ladder degrades INTO (world 2
    and the world-1 synchronous fallback) certifies against the same cost
    contracts as the full mesh — ``--audit-zoo`` passes for shrunk worlds."""
    for ndev in (2, 1):
        res = auditlib.audit_zoo(model="tiny", global_batch=64, window=3,
                                 strategies=("ddp",), paths=("window",),
                                 include_eval=False, num_devices=ndev)
        assert res.clean, "\n".join(res.format_lines())


# ---------------------------------------------------------------------------
# Round 13, analyzer 1: lock-order deadlock detector (analysis/lockgraph)
# ---------------------------------------------------------------------------

def _fmt(findings):
    return "\n".join(f"{f.path}:{f.line}: [{f.rule}] {f.message}"
                     for f in findings)


def test_repo_lock_graph_certified():
    """The whole-package lock graph is acyclic, every edge descends the
    declared partial order, and the known cross-subsystem edges are
    actually SEEN (an analyzer that went blind would pass vacuously)."""
    graph = lockgraph.build_repo_graph(REPO)
    assert lockgraph.check_graph(graph) == [], _fmt(lockgraph.check_graph(graph))
    # The five cross-object edges the threaded subsystems really take.
    for edge in (("WeightWatcher._lock", "SLOScheduler._cond"),
                 ("WeightWatcher._lock", "Telemetry._lock"),
                 ("AlertEngine._lock", "Telemetry._lock"),
                 ("MicroBatcher._cond", "Telemetry._lock"),
                 ("SLOScheduler._cond", "ServiceModel._lock")):
        assert edge in graph.edges, sorted(graph.edges)
    # Every lock the package owns has a declared rank, and every edge
    # descends it — the certificate BASELINE.md records.
    order = lockgraph.certified_order(graph)
    assert set(order) == graph.nodes
    for src, dst in graph.edges:
        assert order.index(src) < order.index(dst), (src, dst)
    summary = lockgraph.graph_summary(graph)
    json.dumps(summary)   # manifest/--verify-static ready
    assert summary["certified_order"] == order
    assert lockgraph.check_locks(REPO) == []


_SRC_ABBA = """\
import threading

class A:
    def __init__(self, peer):
        self._lock = threading.Lock()
        self.peer = peer

    def ping(self):
        with self._lock:
            self.peer.poke()

    def poked(self):
        with self._lock:
            pass

class B:
    def __init__(self, peer):
        self._lock = threading.Lock()
        self.peer = peer

    def poke(self):
        with self._lock:
            self.peer.poked()
"""


def test_lockgraph_detects_abba_cycle():
    """The seeded positive fixture: A holds its lock calling into B,
    B holds its lock calling back into A — the classic ABBA shape the
    detector exists for.  Both the cycle and the order violation fire."""
    finds = lockgraph.check_source(_SRC_ABBA, "abba.py",
                                   order=("A._lock", "B._lock"))
    rules = sorted(f.rule for f in finds)
    assert "lock-cycle" in rules and "lock-order-violation" in rules
    # With no declared order the edges are undeclared, and the cycle
    # still fires — acyclicity does not depend on the order table.
    finds = lockgraph.check_source(_SRC_ABBA, "abba.py", order=())
    rules = sorted(f.rule for f in finds)
    assert "lock-cycle" in rules and "lock-order-undeclared" in rules
    # Cutting the back-edge (B no longer calls into A) clears it.
    acyclic = _SRC_ABBA.replace("            self.peer.poked()",
                                "            pass")
    assert lockgraph.check_source(acyclic, "ok.py",
                                  order=("A._lock", "B._lock")) == []


_SRC_CALLER_HOLDS = """\
import threading

class W:
    def __init__(self):
        self._lock = threading.Lock()
        self.items = []

    def _drain_locked(self):
        self.items = []

    def good(self):
        with self._lock:
            self._drain_locked()

    def also_good_locked(self):
        self._drain_locked()

    def bad(self):
        self._drain_locked()
"""


def test_lockgraph_caller_holds_verification():
    """What makes the lint's *_locked exemption sound: every call site
    of a *_locked method must hold the class lock (directly, or by being
    *_locked itself).  An unlocked call is the seeded violation."""
    finds = lockgraph.check_source(_SRC_CALLER_HOLDS, "w.py", order=())
    assert [f.rule for f in finds] == ["lock-caller-holds"]
    assert "bad" in finds[0].message and "_drain_locked" in finds[0].message
    fixed = _SRC_CALLER_HOLDS.replace(
        "    def bad(self):\n        self._drain_locked()",
        "    def bad(self):\n        with self._lock:\n"
        "            self._drain_locked()")
    assert lockgraph.check_source(fixed, "w.py", order=()) == []


def test_lockgraph_cross_object_locked_call():
    src = _SRC_CALLER_HOLDS.replace(
        "    def bad(self):\n        self._drain_locked()",
        "    def bad(self):\n        pass") + """\

class Z:
    def __init__(self, w):
        self._lock = threading.Lock()
        self.w = w

    def steal(self):
        self.w._drain_locked()
"""
    finds = lockgraph.check_source(src, "z.py", order=())
    assert [f.rule for f in finds] == ["lock-cross-locked-call"]
    assert "Z.steal" in finds[0].message


def test_lockgraph_consistent_order_is_clean():
    src = """\
import threading

class Outer:
    def __init__(self, tel):
        self._lock = threading.Lock()
        self.tel = tel

    def tick(self):
        with self._lock:
            self.tel.bump()

class Inner:
    def __init__(self):
        self._lock = threading.Lock()

    def bump(self):
        with self._lock:
            pass
"""
    assert lockgraph.check_source(
        src, "ok.py", order=("Outer._lock", "Inner._lock")) == []
    # The same edge against the INVERTED declaration is a violation.
    finds = lockgraph.check_source(
        src, "bad.py", order=("Inner._lock", "Outer._lock"))
    assert [f.rule for f in finds] == ["lock-order-violation"]


# ---------------------------------------------------------------------------
# Round 13, satellite 1: the lint holding idioms that replaced waivers
# ---------------------------------------------------------------------------

_SRC_CONDACQ = """\
import threading

class P:
    def __init__(self):
        self._lock = threading.Lock()
        self.n = 0

    def bump(self):
        with self._lock:
            self.n += 1

    def poll(self):
        if not self._lock.acquire(blocking=False):
            return
        try:
            self.n += 1
        finally:
            self._lock.release()
"""


def test_lint_conditional_acquire_idiom():
    """The watcher's non-blocking poll: after a conditional
    ``.acquire()`` whose failure arm bails, the rest of the block runs
    held — no waiver needed.  A write BEFORE the acquire still races."""
    assert pylint_rules.lint_source(_SRC_CONDACQ, "ok.py") == []
    bad = _SRC_CONDACQ.replace(
        "    def poll(self):\n"
        "        if not self._lock.acquire(blocking=False):",
        "    def poll(self):\n"
        "        self.n += 1\n"
        "        if not self._lock.acquire(blocking=False):")
    finds = pylint_rules.lint_source(bad, "bad.py")
    assert [f.rule for f in finds] == ["lock-ownership"]
    assert "poll" in finds[0].message


_SRC_LOCKED_SUFFIX = """\
import threading

class S:
    def __init__(self):
        self._lock = threading.Lock()
        self.gen = 0

    def install(self):
        with self._lock:
            self.gen += 1
            self._reset_locked()

    def _reset_locked(self):
        self.gen = 0
"""


def test_lint_locked_suffix_idiom():
    """A ``*_locked`` method's body runs under the caller's lock by
    contract — the lint trusts the suffix (no waiver), and lockgraph
    verifies every call site (previous tests).  Without the suffix the
    same write is flagged."""
    assert pylint_rules.lint_source(_SRC_LOCKED_SUFFIX, "ok.py") == []
    assert lockgraph.check_source(_SRC_LOCKED_SUFFIX, "ok.py",
                                  order=()) == []
    bad = _SRC_LOCKED_SUFFIX.replace("_reset_locked", "_reset")
    finds = pylint_rules.lint_source(bad, "bad.py")
    assert [f.rule for f in finds] == ["lock-ownership"]
    assert "_reset" in finds[0].message


def test_no_lock_ownership_waivers_left():
    """Satellite 1's acceptance bar: the idioms above replaced every
    ``# lint: ok(lock-ownership)`` waiver in the tree."""
    hits = []
    for dirpath, dirnames, filenames in os.walk(
            os.path.join(REPO, "cs744_ddp_tpu")):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for name in filenames:
            if not name.endswith(".py"):
                continue
            path = os.path.join(dirpath, name)
            if "lint: ok(lock-ownership)" in _read(path):
                hits.append(path)
    assert hits == []


# ---------------------------------------------------------------------------
# Round 13, analyzer 2: wire-protocol schema conformance (wire_schema)
# ---------------------------------------------------------------------------

def test_repo_wire_schema_conformance():
    """Every pack/unpack site in the covered modules agrees with the
    serve/wire.py table, the live constants match it, and the schema
    summary is manifest-ready."""
    finds = wire_schema.check_wire(REPO)
    assert finds == [], _fmt(finds)
    assert wire.verify_runtime() == []
    summary = wire.schema_summary()
    json.dumps(summary)
    assert [f["fmt"] for f in summary["frames"]] == ["<IBBdH", "<IBBQdddiH"]
    assert {f["name"] for f in summary["frames"]} == {"request", "reply"}


_SRC_BAD_ENCODER = """\
import struct

_LEN = struct.Struct("<I")
_REQ = struct.Struct("<IBBdI")
"""


def test_wire_detects_mismatched_encoder():
    """The deliberately mismatched encoder: _REQ widened its count field
    (H -> I) without touching the schema table — the drift one peer
    ships and the other cannot parse."""
    finds = wire_schema.check_source(_SRC_BAD_ENCODER, "enc.py")
    assert [f.rule for f in finds] == ["wire-format-mismatch"]
    assert "_REQ" in finds[0].message and "<IBBdH" in finds[0].message
    fixed = _SRC_BAD_ENCODER.replace("<IBBdI", "<IBBdH")
    assert wire_schema.check_source(fixed, "enc.py") == []


def test_wire_detects_unregistered_and_tag_drift():
    src = ("import struct\n"
           "_SNEAK = struct.Struct(\"<QQ\")\n"
           "n = struct.calcsize(\"<QQ\")\n"
           "TAG_TRACE = 9\n"
           "TAG_NEW = 1\n"
           "TAG_DUP = 1\n")
    rules = sorted(f.rule for f in wire_schema.check_source(src, "m.py"))
    assert rules == ["wire-tag-dup", "wire-tag-mismatch",
                     "wire-unregistered-format", "wire-unregistered-format",
                     "wire-unregistered-tag", "wire-unregistered-tag"]


def test_wire_ext_parser_total_static_and_dynamic():
    """The optional-extension parser must be TOTAL — statically (no
    raise, every unpack length-guarded) and dynamically (exhaustive
    truncation + byte-flip sweep over the live function)."""
    raising = ("def unpack_ext(buf):\n"
               "    if len(buf) < 2:\n"
               "        raise ValueError('short')\n"
               "    return {}\n")
    finds = wire_schema.check_ext_parser_total(raising, "t.py")
    assert [f.rule for f in finds] == ["wire-ext-raise"]
    unguarded = ("def unpack_ext(buf):\n"
                 "    tag, n = _TLV_HEAD.unpack_from(buf, 0)\n"
                 "    return {tag: n}\n")
    finds = wire_schema.check_ext_parser_total(unguarded, "t.py")
    assert [f.rule for f in finds] == ["wire-ext-unguarded"]
    assert wire_schema.ext_parse_corruption_sweep() == []


# ---------------------------------------------------------------------------
# Round 13, analyzer 3: static host-round-trip certifier (dispatch)
# ---------------------------------------------------------------------------

def test_round_trip_closed_form():
    b = dispatchlib.epoch_round_trip_bound
    assert b("step", 25) == 25
    assert b("step", 25, include_eval=True) == 26
    assert b("window", 25, 20) == 2
    assert b("window", 25, 20, include_eval=True) == 3
    assert b("window", 25, 5) == 5
    assert b("host_window", 7, 3, tail_batch=True) == 4
    assert b("eval", 2) == 1 and b("eval", 0) == 0
    with pytest.raises(ValueError, match="bad bound query"):
        b("window", 5)             # windowed path needs a window
    with pytest.raises(ValueError, match="bad bound query"):
        b("step", -1)
    with pytest.raises(ValueError, match="unknown dispatch path"):
        b("warp", 5)


def test_dispatch_seeded_violations():
    """Each certificate rule catches its seeded regression: a windowed
    program that lowered straight-line, one scanning a different window
    than the trainer dispatches, and one that stopped donating."""
    flat = dispatchlib.ProgramCert("train/window/ddp", "window", (), 3)
    assert [f.rule for f in dispatchlib.check_cert(flat)] \
        == ["dispatch-no-scan"]
    drift = dispatchlib.ProgramCert("train/window/ddp", "window", (4,), 3)
    assert [f.rule for f in dispatchlib.check_cert(drift, expect_window=3)] \
        == ["dispatch-window-mismatch"]
    bounce = dispatchlib.ProgramCert("train/window/ddp", "window", (3,), 0)
    assert [f.rule for f in dispatchlib.check_cert(bounce, expect_window=3)] \
        == ["dispatch-donation-zero"]
    good = dispatchlib.ProgramCert("train/window/ddp", "window", (3, 4), 3)
    assert dispatchlib.check_cert(good, expect_window=3) == []
    assert good.window == 4 and flat.window is None


def test_zoo_dispatch_certificate(zoo):
    """The certificate over the real lowered zoo: every windowed program
    scans the dispatched window and donates; the closed-form bounds are
    recorded per program."""
    cert = dispatchlib.certify_zoo(zoo, window=3, nbatches=25)
    assert cert["clean"], json.dumps(cert["findings"], indent=2)
    progs = cert["programs"]
    assert set(progs) == set(zoo.hlo)
    win = progs["train/window/ddp"]
    assert win["path"] == "window" and win["donated"] > 0
    assert win["epoch_round_trips"] == dispatchlib.epoch_round_trip_bound(
        "window", 25, 3, include_eval=True) == 10
    assert progs["train/step/ddp"]["epoch_round_trips"] == 26
    assert progs["eval/window"]["path"] == "eval"
    assert "epoch_round_trips" not in progs["eval/window"]
    assert progs["serve/b2/f32"]["path"] == "serve"
    json.dumps(cert)
    with pytest.raises(ValueError, match="collect_hlo"):
        dispatchlib.certify_zoo(types.SimpleNamespace(hlo={}),
                                window=3, nbatches=25)


def _trip_trainer(tmp_path, mesh4, telemetry, **kw):
    return Trainer(model=tiny_cnn(), strategy="ddp", mesh=mesh4,
                   global_batch=64, data_dir=str(tmp_path), augment=False,
                   limit_train_batches=25, limit_eval_batches=2,
                   log=lambda s: None, telemetry=telemetry, **kw)


def test_static_round_trip_bound_matches_runtime_exactly(tmp_path, mesh4):
    """ISSUE 13's acceptance bar: the static closed form equals the live
    ``host_round_trips`` counter EXACTLY on all three dispatch paths —
    ring-buffer windowed, plain windowed, and per-step."""
    from cs744_ddp_tpu.utils.metrics import WINDOW
    nbatches = 25
    windowed = dispatchlib.epoch_round_trip_bound(
        "window", nbatches, WINDOW, include_eval=True)

    tel = Telemetry()
    tr = _trip_trainer(tmp_path, mesh4, tel, metrics_ring=WINDOW)
    tr.train_model(0)
    tr.test_model()
    assert dispatchlib.total_runtime_trips(tel.records) == windowed == 3
    assert dispatchlib.count_runtime_trips(tel.records) \
        == {"window_drain": 2, "eval": 1}

    tel = Telemetry()
    tr = _trip_trainer(tmp_path, mesh4, tel, metrics_ring=0)
    tr.train_model(0)
    tr.test_model()
    assert dispatchlib.total_runtime_trips(tel.records) == windowed == 3
    assert dispatchlib.count_runtime_trips(tel.records) \
        == {"window_fetch": 2, "eval": 1}

    tel = Telemetry()
    tr = _trip_trainer(tmp_path, mesh4, tel, profile_phases=True)
    tr.train_model(0)
    tr.test_model()
    per_step = dispatchlib.epoch_round_trip_bound(
        "step", nbatches, include_eval=True)
    assert dispatchlib.total_runtime_trips(tel.records) == per_step == 26
    sites = dispatchlib.count_runtime_trips(tel.records)
    assert sites["step_fetch"] == 25 and sites["eval"] == 1


# ---------------------------------------------------------------------------
# Round 13 tentpole gate: the one tier-1 test CI pins everything on
# ---------------------------------------------------------------------------

def test_repo_static_verification(zoo):
    """Folds --audit-zoo, the repo lints, and the whole-program
    analyzers (lock order, wire schema, memory single-source + fixture
    invariants) into one gate — what ``--verify-static`` runs from the
    CLI, asserted here as a tier-1 test."""
    findings = pylint_rules.lint_paths(
        [os.path.join(REPO, t) for t in pylint_rules.DEFAULT_TARGETS])
    findings += lockgraph.check_locks(REPO)
    findings += wire_schema.check_wire(REPO)
    findings += memlife.check_memory(REPO)
    assert findings == [], _fmt(findings)
    assert zoo.clean, "\n".join(zoo.format_lines())
    cert = dispatchlib.certify_zoo(zoo, window=3, nbatches=25)
    assert cert["clean"], json.dumps(cert["findings"], indent=2)


# ---------------------------------------------------------------------------
# Round 14: fused-ingest edge rule, async-dispatch lint, serving-scan cert
# ---------------------------------------------------------------------------

_U8_RUNG = """\
HloModule rung

ENTRY main {
  img = u8[8,32,32,3] parameter(0)
  w = f32[3072,10] parameter(1)
  f = f32[8,32,32,3] convert(img)
  r = f32[8,3072] reshape(f)
  ROOT d = f32[8,10] dot(r, w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}
"""


def test_rule_ingest_edge_seeded():
    # A fused rung: u8 image at the edge, in-program convert -> clean.
    r = auditlib.audit_program(_U8_RUNG, _contract(u8_edge=True))
    assert r.passed, r.findings
    # Float image-shaped entry parameter: the normalize left the program
    # and the wire pays 4x.
    leaked = _U8_RUNG.replace("img = u8[8,32,32,3] parameter(0)",
                              "img = f32[8,32,32,3] parameter(0)") \
                     .replace("f = f32[8,32,32,3] convert(img)",
                              "f = f32[8,32,32,3] negate(img)")
    r = auditlib.audit_program(leaked, _contract(u8_edge=True))
    assert _rules_of(r) == {"ingest-edge"}
    assert "4x transfer" in r.findings[0].message
    # u8 image parameter but no in-program float convert: the program
    # never normalizes on device.
    raw = _U8_RUNG.replace("f = f32[8,32,32,3] convert(img)",
                           "f = f32[8,32,32,3] iota(), iota_dimension=0")
    r = auditlib.audit_program(raw, _contract(u8_edge=True))
    assert _rules_of(r) == {"ingest-edge"}
    assert "never normalizes" in r.findings[0].message
    # The rule is contract-gated: without u8_edge the same float-edge
    # program is a legitimate training lowering.
    assert auditlib.audit_program(leaked, _contract()).passed


_SRC_ASYNC_UNFENCED = """\
import time

class T:
    def run(self, x):
        t0 = time.time()
        h = self.infer_counts_async(x)
        return time.time() - t0
"""


def test_lint_unfenced_timing_async_dispatch():
    # issue-without-complete inside a timing window: the timer stops
    # before the device ran anything.
    bad = pylint_rules.lint_source(_SRC_ASYNC_UNFENCED, "bad.py")
    assert [f.rule for f in bad] == ["unfenced-timing"]
    # complete() IS the fence for the async path.
    fenced = _SRC_ASYNC_UNFENCED.replace(
        "h = self.infer_counts_async(x)",
        "h = self.infer_counts_async(x)\n        out = self.complete(h)")
    assert pylint_rules.lint_source(fenced, "ok.py") == []


def test_cert_serving_rung_straight_line():
    # The static half of the two-in-flight bound: a serving rung that
    # lowers to a scan would host-sync inside the program.
    cert = dispatchlib.ProgramCert(program="serve/b8/f32", path="serve",
                                   scan_trips=(3,), donated=0)
    rules = [f.rule for f in dispatchlib.check_cert(cert)]
    assert rules == ["dispatch-serving-scan"]
    clean = dispatchlib.ProgramCert(program="serve/b8/f32", path="serve",
                                    scan_trips=(), donated=0)
    assert dispatchlib.check_cert(clean) == []
    # Static bound == scheduler constant == arena depth.
    from cs744_ddp_tpu.serve import PIPELINE_SLOTS
    assert dispatchlib.serving_inflight_bound() == PIPELINE_SLOTS == 2
    # Runtime half: occupancy scan over telemetry gauge records.
    recs = [{"kind": "gauge", "name": "serve_inflight", "value": v}
            for v in (1, 2, 1, 0)]
    recs.append({"kind": "gauge", "name": "other", "value": 9})
    assert dispatchlib.max_serving_inflight(recs) == 2
    assert dispatchlib.max_serving_inflight([]) == 0
