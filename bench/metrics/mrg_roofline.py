"""The job's compulsory work (``bench/work.py``) as a share of the
device-busy time per job in the traced window: the least time the chip
could take, over the time it was busy, in %."""
from bench import work


def read(layer):
    tr = layer.get("trace")
    if tr is None or not layer.get("jobs") or tr.busy_s <= 0:
        return None
    floor, _ = work.floor_s(layer["job_work"], layer["device_kind"])
    return 100.0 * floor / (tr.busy_s / layer["jobs"])
