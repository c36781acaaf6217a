from benchmarks.layer_util import delta
from benchmarks.rooflines.check_step import pull_bytes
from benchmarks.rooflines.peaks import peaks_for


def read(run):
    """Device trace and program counters: the bytes the window's pulls
    gathered a second of the window (``rooflines/check_step.py``), over the
    device's memory bandwidth times the share of the traced window that XLA
    modules named ``check_step`` were on the device. None without a trace, off
    the TPU, on a program without the counter, or where no slice pulled."""
    if run.trace is None or run.device.get("platform") != "tpu":
        return None
    words = delta(run, "keto_check_pull_words_total")
    slots = sum(run.after.get("keto_snapshot_ell_slots", kind=k) for k in ("edge", "pad"))
    busy = sum(s for name, s in run.trace["modules"].items() if "check_step" in name)
    window_s = run.result.get("window_s")
    if words <= 0 or slots <= 0 or busy <= 0 or not window_s:
        return None
    on_device_s = window_s * busy / run.trace["window_s"]
    peak = peaks_for(run.device["kind"])["hbm_bytes_per_s"]
    return 100.0 * pull_bytes(words, slots) / (on_device_s * peak)
