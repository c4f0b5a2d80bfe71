"""Bring-up guards: nothing on the main path hides the device it ran on.

* the compile cache lives where ``JAX_COMPILATION_CACHE_DIR`` says (the
  program sets no directory then), else at the fixed ``<checkout>/.jax_cache``;
* ``dryrun_multichip`` / ``_ensure_devices`` never switch platform unasked;
* ``chip_smoke.py`` fails on the CPU backend, before any phase, with no
  result line; the line it ends a green run with has exactly the keys the
  driver parses.
"""

import json
import os
import subprocess
import sys

import pytest

import jax

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Fresh interpreter per case: jax reads the env var once, at import, and the
# test process's own config was already pointed at <repo>/.jax_cache.
_CACHE_PROBE = """\
import json, sys
sys.path.insert(0, sys.argv[1])
import jax
updated = []
real_update = jax.config.update
def recording_update(name, value):
    updated.append(name)
    return real_update(name, value)
jax.config.update = recording_update
from cs744_ddp_tpu.utils import compcache
before = compcache.cache_stats()
compcache.enable_persistent_compilation_cache()
print(json.dumps({"before": before, "stats": compcache.cache_stats(),
                  "config": jax.config.jax_compilation_cache_dir,
                  "updated": updated}))
"""


def _cache_probe(env_dir):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    proc = subprocess.run([sys.executable, "-c", _CACHE_PROBE, REPO],
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode == 0, proc.stderr[-800:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_compile_cache_placed_from_outside(tmp_path):
    want = str(tmp_path / "placed_by_operator")
    got = _cache_probe(want)
    assert got["config"] == want
    assert got["stats"]["dir"] == want and got["stats"]["enabled"]
    # The operator's choice stands because the code sets no directory.
    assert "jax_compilation_cache_dir" not in got["updated"]
    assert got["before"] == {"dir": None, "enabled": False,
                             "hits": 0, "misses": 0}


def test_compile_cache_default_is_the_checkout():
    got = _cache_probe(None)
    want = os.path.join(REPO, ".jax_cache")
    assert got["config"] == want
    assert got["stats"]["dir"] == want and got["stats"]["enabled"]
    assert "jax_compilation_cache_dir" in got["updated"]


def test_ensure_devices_never_switches_platform_unasked():
    import __graft_entry__ as ge

    before = (jax.default_backend(), len(jax.devices()))
    ge._ensure_devices(8)                  # already there: nothing to do
    ge._ensure_devices(8, "cpu")           # asked for what is there
    with pytest.raises(RuntimeError, match="need 99 cpu devices, have 8"):
        ge._ensure_devices(99)
    with pytest.raises(RuntimeError, match="need 99 cpu devices"):
        ge.dryrun_multichip(99)            # raises before any work
    with pytest.raises(ValueError, match="platform must be None or 'cpu'"):
        ge._ensure_devices(1, "gpu")
    assert (jax.default_backend(), len(jax.devices())) == before


def test_chip_smoke_fails_on_cpu_backend():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode == 4, (proc.returncode, proc.stderr[-800:])
    lines = proc.stdout.strip().splitlines()
    assert len(lines) == 1 and lines[0].startswith("chip_smoke: device ")
    assert '"platform": "cpu"' in lines[0]
    assert "no CPU fallback" in proc.stderr


def test_chip_smoke_verdict_line_has_exactly_the_contract_keys():
    """The driver refuses a last line with any other key (PR 21's first
    submission ended with the whole per-phase record)."""
    import chip_smoke

    got = json.loads(chip_smoke.verdict_line(
        {"platform": "tpu", "kind": "TPU v5 lite", "count": 4}))
    assert got == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 4}}
    assert list(got) == ["ok", "device"]
    assert list(got["device"]) == ["platform", "kind", "count"]
    assert type(got["device"]["count"]) is int
