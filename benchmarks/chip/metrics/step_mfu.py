"""Model FLOPs of a round over the round program's mean device time and the
chip's bf16 peak, per chip: the whole step's share of the peak."""


def read(ctx):
    t = ctx.trace
    if t is None:
        return None
    times = [c["program_ns"] / c["program_runs"] for c in t["chips"] if c["program_runs"]]
    if not times:
        return None
    per_chip = ctx.round_flops / ctx.chips
    return 100.0 * per_chip / (sum(times) / len(times) * 1e-9) / ctx.peaks["bf16_flops_per_s"]
