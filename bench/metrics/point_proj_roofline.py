"""``point_proj``'s share of its roofline, in %: the least time for the
projection's work at the round's shapes (memory-bound at these shapes)
over the kernel's device time per traced round. Both are one chip's: the
shapes hold the streams one chip steps, and the trace's op times are
averaged over the chips used."""
from bench import roofline


def read(ctx):
    names = ctx.get("kernel_ops", {}).get("point_proj", ())
    ops = ctx["trace"]["ops"]
    t = sum(ops[n][1] for n in names if n in ops) / ctx["rounds_traced"]
    if not t:
        return None
    sh = ctx["shapes"]
    least, _ = roofline.min_seconds(
        roofline.point_proj_work(sh["streams"], sh["n_points"]),
        ctx["device_kind"])
    return 100.0 * least / t
