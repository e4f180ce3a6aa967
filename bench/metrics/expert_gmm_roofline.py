"""Roofline share of the grouped expert kernel in the decode steps (%).

Layer: kernels (``kernels/moe/kernel.py``).  The least time the chip could
take for the kernel's work in each traced decode call, the larger of FLOPs /
peak and bytes / bandwidth per MoE layer, counted from the step's own routing
picks per held expert (``benchlib.latent_moe_model.expert_gmm_call``: the
weights of every held expert that got a pick, once, and the picked rows),
over the device time of the kernel's trace events that ran inside an
execution of the decode program (``jit_serve_decode`` on the chip's ``XLA
Modules`` line; the prefills' expert calls are left out).  Moves
``tpot_p95_ms``.

The trace names the Pallas kernel by the ``name`` of its ``pallas_call``;
the match is ``NAME`` below.  A program without the kernel, or whose steps
return no pick counts, reads nothing.
"""

import bisect
import re

from benchlib.flops import KernelWork
from benchlib.latent_moe_model import expert_gmm_call
from benchlib.megascan import executions

NAME = r"expert_gmm_pallas"


def decode_kernel_seconds(tr, sp, pattern: str) -> float:
    """Device time of the ops matching ``pattern`` that start inside an
    execution of ``jit_serve_decode`` on their chip."""
    rx = re.compile(pattern)
    total = 0
    for chip, ops in tr.devices.items():
        runs = [m for c, m in executions(sp, "jit_serve_decode") if c == chip]
        starts = [m.start for m in runs]
        for op in ops:
            if not rx.search(op.name):
                continue
            i = bisect.bisect_right(starts, op.start) - 1
            if i >= 0 and op.start < runs[i].end:
                total += op.end - op.start
    return total / 1e9


def read(rec):
    picks = rec.get("decode_picks")
    if not picks:
        return None
    t = decode_kernel_seconds(rec["trace"], rec["megascan"], NAME)
    if t <= 0:
        return None
    m, pk = rec["model"], rec["peak"]
    w = KernelWork()
    for call in picks:
        for layer in call:
            f, b = expert_gmm_call(m, [int(p) for p in layer])
            w.add(f, b, pk.flops_per_s, pk.bytes_per_s)
    return 100.0 * w.least_s / t
