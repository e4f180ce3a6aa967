"""Production mesh construction (assignment-mandated shapes).

``make_production_mesh`` is a function, not a module-level constant, so
importing this module never touches jax device state.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType


def auto_mesh(shape, axes, *, devices=None) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis ``Auto``.

    The logical-axis rules (``parallel.sharding``) place activations through
    ``with_sharding_constraint``, which accepts only ``Auto`` axes; since
    jax 0.7 ``make_mesh`` defaults to ``Explicit`` ones.
    """
    return jax.make_mesh(
        tuple(shape), tuple(axes), axis_types=(AxisType.Auto,) * len(axes),
        devices=devices,
    )


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_host_mesh(data: int = 4, model: int = 2) -> jax.sharding.Mesh:
    """Small (data, model) mesh over whatever local devices exist."""
    n = len(jax.devices())
    data = min(data, n)
    model = max(1, min(model, n // data))
    return auto_mesh((data, model), ("data", "model"))


def make_pipeline_mesh(pp: int, dp: int = 1, tp: int = 1) -> jax.sharding.Mesh:
    """(stage, data, model) mesh for (composed) pipeline-parallel training.

    Uses the first ``pp*dp*tp`` local devices, so a pp=2 smoke run works on
    the 8-device forced-host CPU fleet without consuming all of it.  All
    three axes are live inside ``core.dpp.executor.pipeline_apply``'s
    ``shard_map``: ``stage`` carries the ring ppermute, ``data`` shards the
    microbatch axis (one pipeline per dp group; parameter cotangents
    all-reduce over it in backward), and ``model`` slices heads/ffn inside
    each stage's block when the plan's tp > 1.  Outside the pipelined
    section ``data`` / ``model`` keep their usual logical-axis rule
    meanings.
    """
    need = pp * dp * tp
    devs = jax.devices()
    if len(devs) < need:
        raise ValueError(
            f"pipeline mesh stage={pp} x data={dp} x model={tp} needs "
            f"{need} devices, have {len(devs)} (for CPU smoke set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={need})"
        )
    arr = np.asarray(devs[:need]).reshape(pp, dp, tp)
    return jax.sharding.Mesh(arr, ("stage", "data", "model"))
