"""Host time a device batch spent waiting for the device: the readiness
wait (``device.ready``) plus the part of the fetch that blocks until the
device is done (``device.fetch.wait``), over the batches dispatched."""
from . import ratio
from .totals import totals


def read(ctx):
    t = totals(ctx)
    ready, wait = t.get("device.ready"), t.get("device.fetch.wait")
    disp = t.get("device.dispatch")
    if not disp or (not ready and not wait):
        return None
    return ratio((ready or (0, 0.0))[1] + (wait or (0, 0.0))[1], disp[0],
                 1e3)
