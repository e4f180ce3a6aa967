#!/usr/bin/env python3
"""Compile the pp2 x tp2 training step of minicpm-2b for a described TPU v5e
2x2 host, without the chip, and print what the compiler says about memory.

    JAX_PLATFORMS=cpu python3 bench/tools/compile_minicpm_pp.py \
        [--layers 40] [--seq 2048] [--batch 8]

The step is the program's own (``Session.parallel_plan``, the train rules,
``make_train_step`` with the MegaDPP 1F1B plan), jitted with the state
donated as ``train()`` does, and lowered on shapes whose shardings name the
described devices: the state laid out by the logical-axis rules (tp over
``model``), the batch replicated.  One JSON line per depth:
``memory_analysis()`` per device and whether it fits the chip's 16 GB.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "src"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

HBM_BYTES = 16e9


def compile_step(layers: int, seq: int, batch: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.app.config import RunConfig
    from repro.app.session import Session
    from repro.configs import get_config
    from repro.parallel.sharding import axis_rules, param_shardings
    from repro.train.optim import OptimizerConfig
    from repro.train.train_step import (init_train_state, make_train_step,
                                        train_state_axes)

    cfg = get_config("minicpm-2b").replace(num_layers=layers)
    rc = RunConfig.for_workload("train", arch="minicpm-2b", seed=0)
    rc.modules = ()
    rc.parallel.pp, rc.parallel.tp, rc.parallel.dp = 2, 2, 1
    rc.train.seq_len, rc.train.global_batch = seq, batch
    session = Session(rc, plugins=[], model_cfg=cfg)
    plan = session.parallel_plan()
    rules = session.sharding_rules("train")
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    mesh = Mesh(np.asarray(topo.devices[:4]).reshape(2, 1, 2),
                ("stage", "data", "model"))
    t0 = time.perf_counter()
    with mesh, axis_rules(mesh, rules):
        step = make_train_step(cfg, OptimizerConfig(), plan=plan)
        shapes = jax.eval_shape(lambda k: init_train_state(cfg, k),
                                jax.random.PRNGKey(0))
        shard = param_shardings(train_state_axes(cfg), shapes, mesh, rules)
        state = jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            shapes, shard)
        rep = NamedSharding(mesh, P())
        tok = jax.ShapeDtypeStruct((batch, seq), jnp.int32, sharding=rep)
        batch_av = {"tokens": tok, "targets": tok,
                    "loss_mask": jax.ShapeDtypeStruct((batch, seq), jnp.float32,
                                                      sharding=rep)}
        compiled = jax.jit(step, donate_argnums=(0,)).lower(state, batch_av).compile()
    mem = compiled.memory_analysis()
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes.master))
    peak = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    return {
        "layers": layers, "seq": seq, "global_batch": batch,
        "plan": {"pp": plan.pp, "tp": plan.tp, "dp": plan.dp,
                 "n_micro": plan.n_micro, "schedule": plan.schedule},
        "params": n_params,
        "compile_s": time.perf_counter() - t0,
        "per_device": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_est_bytes": peak,
        },
        "fits_16GB": peak <= HBM_BYTES,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--layers", default="40",
                    help="comma-separated depths to try, in order")
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--batch", type=int, default=8)
    args = ap.parse_args(argv)
    for layers in (int(x) for x in args.layers.split(",")):
        try:
            row = compile_step(layers, args.seq, args.batch)
        except Exception as e:  # the compiler's refusal is the finding
            row = {"layers": layers, "error": f"{type(e).__name__}: {e}"[:2000]}
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
