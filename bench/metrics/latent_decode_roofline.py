"""Roofline share of the paged latent (MLA) decode kernel (%).

Layer: kernels (``kernels/paged_attention/latent_kernel.py``).  The least
time the chip could take for the kernel's work, the larger of FLOPs / peak
and bytes / bandwidth per call, counted from the live kv length of each slot
in each traced decode call (``benchlib.latent_moe_model.latent_decode_call``;
one call per layer), over the summed device time of the kernel's trace
events.  Moves ``tpot_p95_ms``.

The trace names the Pallas kernel by the ``name`` of its ``pallas_call``;
the match is ``NAME`` below.  A program without the kernel reads nothing.
"""

from benchlib import xplane
from benchlib.flops import KernelWork
from benchlib.latent_moe_model import latent_decode_call

NAME = r"paged_latent_attention_pallas"


def read(rec):
    t = xplane.op_seconds(rec["trace"], NAME)
    if t <= 0 or not rec.get("decode_kv"):
        return None
    m, pk = rec["model"], rec["peak"]
    w = KernelWork()
    for kv in rec["decode_kv"]:
        if kv:
            f, b = latent_decode_call(m, kv)
            w.add(f, b, pk.flops_per_s, pk.bytes_per_s, calls=m.n_layers)
    return 100.0 * w.least_s / t
