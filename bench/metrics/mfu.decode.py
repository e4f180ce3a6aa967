"""Model FLOP utilization of the decode steps (%).

Layer: model step, decode (``serve/engine.py`` paged decode step).  Model
FLOPs of the tokens decoded, each 2N plus 4 L H dh per live key
(``benchlib.flops.decode_flops``; a request of P prompt tokens decodes its
tokens 2..n against P + 1 .. P + n - 1 keys), over the summed
``serve.decode_step_s`` times the chip's peak.  Moves ``tpot_p95_ms``.
"""

from benchlib.flops import decode_flops


def read(rec):
    s, n = rec["hist"].get("serve.decode_step_s", (0.0, 0))
    if not n or s <= 0:
        return None
    m = rec["model"]
    work = 0.0
    for r in rec["requests"]:
        for j in range(1, r["generated"]):
            work += decode_flops(m, r["prompt_len"] + j)
    return 100.0 * work / (s * rec["peak"].flops_per_s)
