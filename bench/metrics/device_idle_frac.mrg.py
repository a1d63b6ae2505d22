"""1 - device-busy time over the traced window, on the most idle chip."""


def read(layer):
    tr = layer.get("trace")
    return None if tr is None else max(tr.idle_frac)
