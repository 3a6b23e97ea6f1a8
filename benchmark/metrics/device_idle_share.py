"""Share of the traced window (a sweep pass, or the serve window) in
which no operation ran on the device: 1 - (union of device op
intervals) / (traced window). One reader for `device_idle_share.sweep`
and `device_idle_share.serve`."""


def read(r):
    t = r["trace"]
    if not t.devices:
        return None
    return 100.0 * (1.0 - t.busy_s() / t.window_s)
