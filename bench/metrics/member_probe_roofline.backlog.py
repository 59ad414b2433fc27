"""Share of the HBM roofline that the membership probes reach: the least
bytes the operation needs (query pairs and table pairs read once, one
boolean per query written; ``kernels.member_probe_bytes``) over the HBM
peak, against the device time of the ``member_probe`` kernel calls in the
trace. No compute bound is taken: the operation needs a few integer
operations per byte, far below the chip's peak."""

import kernels
import devtrace


def read(run):
    if run.trace is None or run.arrivals != "closed":
        return None
    least_bytes, seconds = 0, 0.0
    for text, (secs, calls) in run.trace["ops"].items():
        if devtrace.op_name(text).startswith("member_probe") and "custom-call(" in text:
            least_bytes += kernels.member_probe_bytes(text) * calls
            seconds += secs
    if seconds <= 0:
        return None
    least_s = least_bytes / kernels.peaks(run.device_kind)["hbm_bytes_per_s"]
    return 100.0 * least_s / seconds
