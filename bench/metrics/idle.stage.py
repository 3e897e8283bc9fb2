"""idle.stage: the share of the traced stages' window in which no operation
ran on the device, in percent (profiler trace; busy is the union of the
device's operation intervals)."""


def read(d):
    t = d.get("trace") or {}
    if "flops_per_stage" not in d or not t.get("window_s") or t.get("busy_s") is None:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
