"""The GON pass kernel's share of its roofline, in %: the bytes its
``k - 1`` fused passes must read per job (rounds 1 and 2, counted from the
shapes, ``bench/work.py``), at the chip's HBM peak, over the device time
of the kernel's events (``fused_min_argmax*``) per job. Silent where no
such kernel ran."""
from bench import work

KERNEL = "fused_min_argmax"


def read(layer):
    tr = layer.get("trace")
    if tr is None or not layer.get("jobs"):
        return None
    t = tr.kernel_s(KERNEL) / layer["jobs"]
    if t <= 0:
        return None
    rows, d, k, union = layer["job_shape"]
    passes = (k - 1) * (rows + union)
    floor, _ = work.floor_s(work.Work(bytes=4.0 * d * passes,
                                      flops=3.0 * d * passes),
                            layer["device_kind"])
    return 100.0 * floor / t
