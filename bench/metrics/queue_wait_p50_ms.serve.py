"""Median time a request waited, from its due time to its admission (ms).

Layer: serve host (``serve/scheduler.py`` admission, ``serve/server.py``
tick).  Read from the program's own request timestamps
(``Request.queue_wait``, the server clock).  Moves ``ttft_p95_ms``.
"""

from statistics import median


def read(rec):
    waits = [r["t_admitted"] - r["arrival"] for r in rec["requests"]
             if r["t_admitted"] is not None]
    return median(waits) * 1e3 if waits else None
