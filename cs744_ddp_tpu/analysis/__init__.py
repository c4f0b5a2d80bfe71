"""Static analysis of compiled programs (HLO graph IR, audits, lint).

Three layers, all import-light (jax only where a rule needs a jaxpr):

- ``hlo_ir``     — tokenizer + parser for HLO text (both the optimized
                   ``%``-sigil print and the bare pre-optimization print)
                   into a module/computation/instruction graph IR.
- ``stats``      — the collective-accounting API (``collective_stats``,
                   ``collective_chain_depth``, ``bytes_of_type``) rebuilt
                   on the IR; ``utils/hlo_stats.py`` is now a thin adapter
                   over this module and its regex implementation survives
                   only as ``legacy_*`` differential-test oracles.
- ``audit``      — a rule engine certifying each shipped program's cost
                   shape (collective contract per strategy, dtype leaks,
                   donation misses, host syncs in loop bodies, oversized
                   baked constants) wired into ``cli.py --audit`` and the
                   telemetry manifest.
- ``pylint_rules`` — AST lint for repo invariants the runtime can't see
                   (un-fenced timing, jnp on producer threads, lock
                   ownership); ``tools/lint_graft.py`` is the CLI.
- ``lockgraph``  — whole-package lock-order deadlock detector: builds the
                   cross-class lock-acquisition graph, certifies it
                   acyclic against the declared ``LOCK_ORDER`` partial
                   order, and verifies every ``*_locked`` call site
                   (round 13).
- ``wire_schema`` — wire-protocol schema conformance: every struct
                   format/TLV tag in the codec sources against the
                   declarative ``serve/wire.py`` table, encoder/decoder
                   symmetry, and total extension parsing (round 13).
- ``dispatch``   — static host-round-trip certifier: closed-form
                   per-epoch round-trip bounds from the lowered
                   programs' scan structure, pinned EXACTLY against the
                   runtime ``host_round_trips`` counter (round 13).

``tools/lint_graft.py`` and ``cli.py --verify-static`` run the three
whole-program analyzers together;
``tests/test_analysis.py::test_repo_static_verification`` is the tier-1
CI gate.
"""

from .stats import bytes_of_type, collective_chain_depth, collective_stats

__all__ = ["bytes_of_type", "collective_chain_depth", "collective_stats"]
