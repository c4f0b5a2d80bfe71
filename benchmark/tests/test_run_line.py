"""The result line has exactly the contract's keys; a reader that finds
nothing leaves its metric out; the run refuses anything but a known TPU."""

import json
import subprocess
import sys

import pytest

from benchmark import manifest as mf, run as runmod
from benchmark.window import Window

M = mf.load()


def fake_result(chips=1):
    units = [{"images": 50_000 * chips, "steps": 7, "failed": 0,
              "seconds": 0.8, "start": 0.8 * i, "train_s": 0.7,
              "eval_s": 0.1} for i in range(5)]
    return {"correct": True, "attempted": 35, "failed": 0,
            "window": Window(units, 10.0, 14.0), "chips": chips,
            "setup_s": 30.0, "memory_peak_bytes": 7_000_000_000,
            "counters": {"compiles_in_window": 0, "host_round_trips": 15},
            "compared": {"loss1": [1e-6, 1e-4]}}


def fake_trace():
    return {"busy_s": 3.9, "ops_s": 3.9, "matmul_s": 2.0,
            "matmul_train_s": 1.8, "train_module_busy_s": 3.6,
            "eval_module_busy_s": 0.3, "collective_s_dev0": 0.0,
            "collective_exposed_s_dev0": 0.0, "gap_total_s_dev0": 0.1,
            "breakdown": {"device_ops": [["fusion.1", 1.0]],
                          "idle_gaps": [["train_model", 0.01]]}}


@pytest.mark.parametrize("cell", [w["name"] for w in M["workloads"]])
@pytest.mark.parametrize("traced", [False, True])
def test_last_line_keys(cell, traced):
    w = mf.cell(M, cell)
    result = fake_result(w["chips"])
    config = mf.load_config(M, w["config"])
    peak = mf.load_peaks()["TPU v5 lite"]
    trace = fake_trace() if traced else None
    run = runmod.Run(result, config, peak, trace)
    kind = "per_layer" if traced else "end_to_end"
    metrics = runmod.read_metrics(M, cell, kind, run)
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": w["chips"],
              "memory_peak_bytes": 1}
    line = json.loads(json.dumps(
        runmod.result_line(result, metrics, device, trace)))
    want = list(mf.RESULT_KEYS) + (["breakdown"] if traced else []) \
        + ["compared"]
    assert list(line) == want
    declared = {m["name"]: m for m in mf.cell_metrics(M, cell, kind)}
    assert set(line["metrics"]) <= set(declared)
    for name, m in line["metrics"].items():
        assert set(m) == {"value", "unit"}
        assert m["unit"] == declared[name]["unit"]
    if traced:
        # no collective time in this trace: the sync readers stay silent
        assert not any(n.startswith("sync.") for n in line["metrics"])
        assert line["metrics"]["kernel.conv_roofline"]["value"] > 0
        assert line["metrics"]["model.mfu"]["value"] > 0
    else:
        assert set(line["metrics"]) == {"train_img_s_chip", "setup_s"}
        assert line["metrics"]["train_img_s_chip"]["value"] == \
            pytest.approx(250_000 / 4.0)


def test_a_reader_that_finds_nothing_returns_nothing():
    result = fake_result()
    run = runmod.Run(result, mf.load_config(M, "vgg11-cifar-f32"),
                     mf.load_peaks()["TPU v5 lite"], {"busy_s": 1.0})
    got = runmod.read_metrics(M, "vgg11-train-1chip", "per_layer", run)
    assert "kernel.conv_roofline" not in got
    assert "model.nonconv_share" not in got and "model.mfu" in got


def test_the_measurement_refuses_the_cpu():
    """No switch lets a CPU run through: exit 4 and no result line."""
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         M["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=mf.ROOT, capture_output=True, text=True,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
             "HOME": "/tmp"} | {k: v for k, v in __import__("os").environ.items()
                                if k in ("PATH", "HOME", "VIRTUAL_ENV",
                                         "PYTHONPATH")})
    assert p.returncode == runmod.EXIT_NO_DEVICE, p.stderr[-500:]
    assert p.stdout.strip() == ""


class FakeDevice:
    def __init__(self, platform, kind):
        self.platform, self.device_kind = platform, kind


@pytest.mark.parametrize("platform,kind,n,chips,ok", [
    ("tpu", "TPU v5 lite", 1, 1, True),
    ("tpu", "TPU v5 lite", 4, 4, True),
    ("tpu", "TPU v5 lite", 1, 4, False),       # fewer chips than asked for
    ("tpu", "TPU v9 imaginary", 1, 1, False),  # not in the peak table
    ("gpu", "TPU v5 lite", 1, 1, False),
    ("cpu", "cpu", 8, 1, False),
])
def test_device_gate(monkeypatch, platform, kind, n, chips, ok):
    import jax
    monkeypatch.setattr(jax, "devices",
                        lambda *a: [FakeDevice(platform, kind)] * n)
    if ok:
        assert runmod.device_or_exit(chips) == {
            "platform": platform, "kind": kind, "count": n}
    else:
        with pytest.raises(SystemExit) as e:
            runmod.device_or_exit(chips)
        assert e.value.code == runmod.EXIT_NO_DEVICE
