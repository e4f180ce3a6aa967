"""Roofline share of the flash prefill attention kernel (%).

Layer: kernels (``kernels/paged_attention/prefill_kernel.py``).  The least
time the chip could take for the kernel's work, the larger of FLOPs / peak
and bytes / bandwidth per call, counted from the real prompt length of each
traced prefill (``benchlib.flops.flash_prefill_call``, causal; one call per
layer), not the padded bucket, over the summed device time of the kernel's
trace events.  Moves ``ttft_p95_ms``.

The trace names the Pallas kernel after its body function; the match is
``NAME`` below.
"""

from benchlib.flops import KernelWork, flash_prefill_call
from benchlib import xplane

NAME = r"paged_prefill_pallas"


def read(rec):
    t = xplane.op_seconds(rec["trace"], NAME)
    if t <= 0 or not rec["prefill_n"]:
        return None
    m, pk = rec["model"], rec["peak"]
    w = KernelWork()
    for n in rec["prefill_n"]:
        f, b = flash_prefill_call(m, n)
        w.add(f, b, pk.flops_per_s, pk.bytes_per_s, calls=m.n_layers)
    return 100.0 * w.least_s / t
