"""Kernels (``kernels/lp_gain.py``): the kernel's share of its roofline,
in percent: the least time the chip could take for all the traced calls,
each the larger of its operations over the peak op rate and its bytes over
the HBM bandwidth (``bench/kernels.py``, ``bench/peaks.json``), over the
device time of those calls. The peak op rate is the bf16 matrix unit's,
the highest the chip has, so the bound is never too tight; at the
kernel's few operations per byte the bytes bound it."""
from bench import kernels as K


def read(rec: dict):
    tr = rec.get("trace")
    if tr is None:
        return None
    p = None
    least = took = 0.0
    for e in tr.ops():
        if e.start < tr.lo or e.end > tr.hi:
            continue  # a call cut by the window's edge
        cost = K.lp_gain_cost(e.name)
        if cost is None:
            continue
        ops, nbytes = cost
        p = p or K.peaks(rec["device_kind"])
        least += max(ops / p["bf16_flops"], nbytes / p["hbm_bytes_per_s"])
        took += (e.end - e.start) * 1e-9
    if took <= 0:
        return None
    return 100.0 * least / took
