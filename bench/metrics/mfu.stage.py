"""mfu.stage: the stage program's model FLOPs per second over the chip's
peak, in percent.  Model FLOPs of a stage are the configuration's forward
plus backward operations per example times the examples every client trains
on (clients x samples x local epochs x rounds); the time is the window's wall
time over the stages it completed (host clock, each stage ended by
block_until_ready)."""


def read(d):
    if "flops_per_stage" not in d or not d.get("peak_flops") or not d["stages"]:
        return None
    return 100.0 * d["flops_per_stage"] * d["stages"] / d["window_s"] / d["peak_flops"]
