"""The least time the chip needs for every reduction request of the window
(``bench/roofline.py``, from unpadded shapes) over the device time of the
jitted reduction programs in the trace."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not tr["kernel_device_s"] or not ctx["kernel_least_s"]:
        return None
    return 100.0 * ctx["kernel_least_s"] / tr["kernel_device_s"]
