"""What a start without ``--checkpoint-dir`` imports: never orbax.

``orbax.checkpoint`` pulls in ``google.cloud.logging``, whose import walks
every installed distribution: seconds of every start (PERF.md §6, PR 27).
It is imported where a ``CheckpointManager`` is built
(train/checkpoint.py) and nowhere else, so importing the program's entry
points, the elastic package or the checkpoint module itself, and building a
``Trainer`` with no checkpoint directory, must leave it unloaded.  One
subprocess per case: other tests in the same worker load orbax.  The other
side (a manager being built loads it, and the probe sees that) is in
tests/test_checkpoint.py.
"""

import json
import os
import subprocess
import sys

import pytest

TESTS = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(TESTS)

_PROBE = """
import json, sys
sys.path[:0] = [sys.argv[1], sys.argv[2]]
tmp = sys.argv[3]
exec(sys.argv[4])
print(json.dumps(sorted(
    m for m in sys.modules
    if m.split(".")[0] == "orbax" or m.startswith("google.cloud.logging"))))
"""

_TINY_TRAINER = """
from cs744_ddp_tpu.parallel import make_mesh
from cs744_ddp_tpu.train.loop import Trainer
from tinynet import tiny_cnn
tr = Trainer(model=tiny_cnn(), strategy="ddp", mesh=make_mesh(2),
             global_batch=64, data_dir=tmp, log=lambda s: None)
assert tr.elastic is None and tr.state is not None
"""

_CHECKPOINT_HELPERS = """
from cs744_ddp_tpu.train import checkpoint as c
fp = c.publish_fingerprint({"model": "tiny", "seed": 0, "lr": 0.1})
assert fp == {"model": "tiny", "seed": 0,
              "state_format_version": c.STATE_FORMAT_VERSION}, fp
c._atomic_write_json(tmp + "/epoch_meta.json", {"world": 4})
assert c.read_epoch_meta(tmp) == {"world": 4}
assert c.read_mid_epoch_meta(tmp) is None
"""

CASES = {
    "train.loop": "import cs744_ddp_tpu.train.loop",
    "cli": "import cs744_ddp_tpu.cli",
    "elastic": "import cs744_ddp_tpu.elastic\n"
               "cs744_ddp_tpu.elastic.ElasticCoordinator",
    "publish": "import cs744_ddp_tpu.publish",
    "train.checkpoint": "import cs744_ddp_tpu.train.checkpoint as c\n"
                        "c.CheckpointManager",
    "checkpoint_helpers": _CHECKPOINT_HELPERS,
    "trainer_without_checkpoint_dir": _TINY_TRAINER,
}


def loaded_after(code, tmp_path, timeout=240):
    """Run `code` in a fresh CPU interpreter; the orbax / google.cloud.logging
    modules loaded afterwards."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    proc = subprocess.run(
        [sys.executable, "-c", _PROBE, REPO, TESTS, str(tmp_path), code],
        env=env, capture_output=True, text=True, timeout=timeout)
    assert proc.returncode == 0, proc.stderr[-1500:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("case", sorted(CASES))
def test_start_without_checkpoint_dir_never_imports_orbax(case, tmp_path):
    assert loaded_after(CASES[case], tmp_path) == []
