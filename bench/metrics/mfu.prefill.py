"""Model FLOP utilization of the prefills (%).

Layer: model step, prefill (``serve/engine.py`` flash prefill step).  Model
FLOPs of the real prompt tokens prefilled (2N per token plus causal
attention, ``benchlib.flops.prefill_flops``) over the summed ``serve.prefill_s``
(the program's host clock around each prefill, ending on
``block_until_ready``) times the chip's peak.  Moves ``ttft_p95_ms``.
"""

from benchlib.flops import prefill_flops


def read(rec):
    s, n = rec["hist"].get("serve.prefill_s", (0.0, 0))
    if not n or s <= 0:
        return None
    work = sum(prefill_flops(rec["model"], r["prompt_len"])
               for r in rec["requests"] if r["t_first_token"] is not None)
    return 100.0 * work / (s * rec["peak"].flops_per_s)
