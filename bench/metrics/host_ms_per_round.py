"""Host loop time per round: each round's latency less its
``fleet/fetch`` span (puts, telemetry, dispatch and the fleet's uplink and
cloud bookkeeping), mean over the rounds of the untraced window, in ms."""
import numpy as np


def read(ctx):
    per = [d.rounds - d.fetch_s for d in ctx["drives"]]
    return 1e3 * float(np.mean(np.concatenate(per))) if per else None
