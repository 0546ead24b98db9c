"""Host time blocked in the round's one fetch (``fleet/fetch`` span:
transfer plus the device work still queued), mean over the rounds of the
untraced window, in ms."""
import numpy as np


def read(ctx):
    per = [d.fetch_s for d in ctx["drives"]]
    return 1e3 * float(np.mean(np.concatenate(per))) if per else None
