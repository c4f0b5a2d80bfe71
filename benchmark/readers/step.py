"""Compiled step (train/step.py): device time of its modules."""


def device_ms_per_kimg(run):
    busy = run.trace.get("train_module_busy_s")
    if not busy:
        return None
    return 1e3 * busy / (run.window.total("images") / run.chips / 1e3)


def eval_device_ms_per_epoch(run):
    busy = run.trace.get("eval_module_busy_s")
    return 1e3 * busy / len(run.window.units) if busy else None
