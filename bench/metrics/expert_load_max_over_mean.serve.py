"""How unevenly the routing loads the held experts in the decode steps.

Layer: model step (``models/layers.py`` ``moe_apply``, the dropless
chip-share expert layer).  The mean, over the window's decode calls, of the
most picks one held expert got in any MoE layer (``serve.moe.
max_expert_picks``), over the mean picks a held expert got
(``serve.moe.held_picks`` per call and layer, over the experts held): 1 is
an even load; the grouped expert kernel's time follows the most loaded
expert more than the mean.  Read from the program's own histograms.  Moves
``tpot_p95_ms``.
"""


def read(rec):
    top, n = rec["hist"].get("serve.moe.max_expert_picks", (0.0, 0))
    held, n_held = rec["hist"].get("serve.moe.held_picks", (0.0, 0))
    if not n or not n_held or held <= 0:
        return None
    mean = held / n_held / rec["model"].experts_held
    return (top / n) / mean
