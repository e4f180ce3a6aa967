"""Dispatch for the grouped expert SwiGLU: the Pallas kernel on a TPU, the
reference elsewhere.  The kernel's backward pass is the reference's, so a
dropless expert layer trains on one chip too."""

from __future__ import annotations

import jax

from repro.kernels.moe.kernel import expert_ffn_pallas
from repro.kernels.moe.ref import expert_ffn_ref


@jax.custom_vjp
def _kernel_with_ref_grad(x, wg, wu, wd, sizes, layer):
    return expert_ffn_pallas(x, wg, wu, wd, sizes, layer=layer)


def _fwd(x, wg, wu, wd, sizes, layer):
    out = expert_ffn_pallas(x, wg, wu, wd, sizes, layer=layer)
    return out, (x, wg, wu, wd, sizes, layer)


def _bwd(res, ct):
    x, wg, wu, wd, sizes, layer = res
    _, vjp = jax.vjp(lambda *a: expert_ffn_ref(*a, sizes, layer),
                     x, wg, wu, wd)
    return (*vjp(ct), None, None)


_kernel_with_ref_grad.defvjp(_fwd, _bwd)


def expert_ffn(x, w_gate, w_up, w_down, group_sizes, *, layer=None):
    """``x`` [m, D] picks sorted by held expert, ``group_sizes`` [G] rows per
    held expert, the weights one layer's ``[G, ...]`` or, with ``layer``,
    stacked ``[n_layers, G, ...]``; returns [m, D] in ``x``'s dtype (rows
    past the held picks: zero on the reference, unwritten by the kernel)."""
    if jax.default_backend() == "tpu":
        return _kernel_with_ref_grad(x, w_gate, w_up, w_down, group_sizes,
                                     layer)
    return expert_ffn_ref(x, w_gate, w_up, w_down, group_sizes, layer)
