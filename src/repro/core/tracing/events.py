"""Trace event model (MegaScan §3.2 "Workload tracing").

Events carry the metadata the paper attaches via ``tracers.scope``: microbatch
index, communication volume, peer rank / participating-rank list — everything
dependency reconstruction and fault diagnosis need downstream.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any


@dataclass
class TraceEvent:
    name: str
    rank: int
    ts: float          # start, seconds in the *local* (per-rank) clock
    dur: float
    kind: str = "compute"  # compute | coll | p2p | marker | host | counter
    args: dict = field(default_factory=dict)
    # well-known args:
    #   mb: microbatch index        chunk: model-chunk index
    #   bytes: payload bytes        group: tuple of participating ranks
    #   peer: peer rank (p2p)       op: operator name
    #   phase: F | B | G            dir: send | recv

    @property
    def end(self) -> float:
        return self.ts + self.dur

    def to_json(self) -> dict:
        return {
            "name": self.name,
            "rank": self.rank,
            "ts": self.ts,
            "dur": self.dur,
            "kind": self.kind,
            "args": {
                k: (list(v) if isinstance(v, tuple) else v)
                for k, v in self.args.items()
            },
        }

    @classmethod
    def from_json(cls, d: dict) -> "TraceEvent":
        args = dict(d.get("args", {}))
        if "group" in args and isinstance(args["group"], list):
            args["group"] = tuple(args["group"])
        return cls(d["name"], d["rank"], d["ts"], d["dur"], d.get("kind", "compute"), args)
