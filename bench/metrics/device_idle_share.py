"""device_idle_share (device): 100 x (1 - busy / window) over the traced
job, busy being the union of the device's operations in the profiler
trace, averaged over the chips."""


def read(rec):
    if rec.device is None:
        return None
    return 100.0 * (1.0 - rec.device.busy_s / rec.device.window_s)
