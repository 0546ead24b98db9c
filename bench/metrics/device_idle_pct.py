"""Share of the untraced window in which no operation runs on the device,
in %: 1 - (device busy time per traced round, the union of the trace's
``XLA Ops`` intervals) x (rounds in the untraced window) / (the untraced
window's seconds). The profiler slows the host's input path and so
stretches the traced rounds, but not the device's work in them."""


def read(ctx):
    rounds = sum(len(d.rounds) for d in ctx["drives"])
    traced = ctx["rounds_traced"]
    busy = ctx["trace"].get("busy_s", 0.0)
    if not (rounds and traced and busy and ctx["window_s"] > 0):
        return None
    return 100.0 * (1.0 - busy / traced * rounds / ctx["window_s"])
