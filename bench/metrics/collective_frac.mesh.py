"""Device time in collective operations over the traced window, on the
chip with the most."""


def read(layer):
    tr = layer.get("trace")
    if tr is None or tr.window_s <= 0:
        return None
    return max(tr.collective_s) / tr.window_s
