"""Device time of the jitted fleet step's program per traced round, in
ms: the trace's ``XLA Modules`` events of the step's module."""


def read(ctx):
    name = ctx.get("step_module")
    mods = ctx["trace"]["modules"]
    total = sum(v[1] for k, v in mods.items()
                if name and (k == name or k.startswith(name + "(")))
    if not total:
        return None
    return 1e3 * total / ctx["rounds_traced"]
