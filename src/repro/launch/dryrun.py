import os
import tempfile

# REPRO_DRYRUN_DEVICES: forced host device count (default 512 = enough for
# the 2x16x16 multi-pod mesh; CPU smoke runs with --host-mesh set a small
# count — some container kernels cannot stand up 512 device threads)
_N_DEV = int(os.environ.get("REPRO_DRYRUN_DEVICES", "512"))
_DUMP_DIR = tempfile.mkdtemp(prefix="xla_spmd_dump_")
os.environ["XLA_FLAGS"] = (
    f"--xla_force_host_platform_device_count={_N_DEV} "
    f"--xla_dump_to={_DUMP_DIR} "
    "--xla_dump_hlo_pass_re=spmd-partitioning"
)

"""Multi-pod dry-run: lower + compile every (architecture x input-shape) cell
on the production meshes and record memory / cost / collective analyses.

The two lines above MUST stay first: jax locks the device count on first init.

Usage (via the unified CLI — `python -m repro.launch.dryrun` still works as
a deprecation shim with identical flags):

    PYTHONPATH=src python -m repro dryrun --arch qwen3-14b --shape train_4k
    PYTHONPATH=src python -m repro dryrun --all --multi-pod both \
        --out artifacts/dryrun
"""

import json
import time
import traceback
from pathlib import Path


def _spmd_dump_snapshot() -> set[str]:
    return {f for f in os.listdir(_DUMP_DIR) if "after_spmd-partitioning" in f}


def _read_new_spmd_dump(before: set[str]) -> str | None:
    """Post-partitioning / pre-float-normalization HLO of the last compile.

    The CPU backend's float-normalization pass upconverts bf16 to f32 *after*
    SPMD partitioning, which would inflate collective-byte accounting 2x; this
    dump has the true (bf16) collective dtypes.
    """
    new = sorted(_spmd_dump_snapshot() - before)
    if not new:
        return None
    return (Path(_DUMP_DIR) / new[-1]).read_text()


def _compile_cell(cfg, shape, mesh, profile, grad_accum):
    import jax

    from repro.launch.specs import input_specs

    cell = input_specs(cfg, shape, mesh, profile=profile, grad_accum=grad_accum)
    snap = _spmd_dump_snapshot()
    with mesh:
        jitted = jax.jit(
            cell.step,
            in_shardings=cell.in_shardings,
            donate_argnums=cell.donate_argnums,
        )
        lowered = jitted.lower(*cell.in_specs)
        compiled = lowered.compile()
    return cell, compiled, _read_new_spmd_dump(snap)


def _cost_vector(compiled, spmd_hlo: str | None = None) -> dict:
    from repro.launch.hlo_analysis import collective_stats

    cost = compiled.cost_analysis()
    colls = collective_stats(spmd_hlo if spmd_hlo else compiled.as_text())
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        "collective_bytes": colls.total_bytes,
        "collective_bytes_tpu": colls.tpu_adjusted_bytes,
        "collective_bytes_by_kind": dict(colls.bytes_by_kind),
        "collective_counts": dict(colls.counts),
    }


def _extrapolate(small: dict, large: dict, n_units: int) -> dict:
    out: dict = {}
    for k in ("flops", "bytes_accessed", "collective_bytes", "collective_bytes_tpu"):
        marg = large[k] - small[k]
        out[k] = small[k] + (n_units - 1) * marg
    out["collective_bytes_by_kind"] = {
        k: small["collective_bytes_by_kind"].get(k, 0.0)
        + (n_units - 1)
        * (large["collective_bytes_by_kind"].get(k, 0.0)
           - small["collective_bytes_by_kind"].get(k, 0.0))
        for k in set(small["collective_bytes_by_kind"]) | set(large["collective_bytes_by_kind"])
    }
    return out


def run_cell(
    arch: str,
    shape_name: str,
    multi_pod: bool,
    *,
    profile: str | None = None,
    grad_accum: int = 1,
    save_hlo: str | None = None,
    smoke: bool = False,
    probes: bool = True,
    host_mesh: bool = False,
) -> dict:
    import jax

    from repro.configs import SHAPES, get_config
    from repro.launch.hlo_analysis import collective_stats
    from repro.launch.mesh import make_host_mesh, make_production_mesh
    from repro.launch.specs import input_specs, probe_pair
    from repro.models.model import active_param_count

    cfg = get_config(arch, smoke=smoke)
    shape = SHAPES[shape_name]
    # host_mesh: lower/compile on a small host mesh instead of the 16x16
    # production shape — the CPU-smoke path of `python -m repro dryrun`
    mesh = (make_host_mesh() if host_mesh
            else make_production_mesh(multi_pod=multi_pod))
    t0 = time.time()
    snap = _spmd_dump_snapshot()
    cell = input_specs(cfg, shape, mesh, profile=profile, grad_accum=grad_accum)

    with mesh:
        jitted = jax.jit(
            cell.step,
            in_shardings=cell.in_shardings,
            donate_argnums=cell.donate_argnums,
        )
        lowered = jitted.lower(*cell.in_specs)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    colls = collective_stats(_read_new_spmd_dump(snap) or hlo)

    corrected = None
    if probes and not smoke:
        # HLO cost analysis counts scan bodies once; extrapolate true totals
        # from two shallow unrolled probes (see specs.probe_pair).
        cfg_s, cfg_l, n_units = probe_pair(cfg)
        _, comp_s, dump_s = _compile_cell(cfg_s, shape, mesh, profile, grad_accum)
        _, comp_l, dump_l = _compile_cell(cfg_l, shape, mesh, profile, grad_accum)
        corrected = _extrapolate(
            _cost_vector(comp_s, dump_s), _cost_vector(comp_l, dump_l), n_units
        )

    # keep the dump dir bounded over long sweeps
    for f in os.listdir(_DUMP_DIR):
        try:
            os.unlink(os.path.join(_DUMP_DIR, f))
        except OSError:
            pass

    # analytic "useful" FLOPs: 6*N*D train, 2*N*D forward-only
    n_active = active_param_count(cfg)
    tok = cell.meta["tokens"]
    model_flops = (6 if shape.kind == "train" else 2) * n_active * tok

    result = {
        "arch": arch,
        "shape": shape_name,
        "kind": shape.kind,
        "multi_pod": multi_pod,
        "profile": profile or ("decode" if shape.kind == "decode" else "fsdp_cp"),
        "grad_accum": grad_accum,
        "devices": int(len(mesh.devices.reshape(-1))),
        "tokens": cell.meta["tokens"],
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "flops_per_device": float(cost.get("flops", 0.0)),
        "bytes_accessed_per_device": float(cost.get("bytes accessed", 0.0)),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_est_bytes": mem.argument_size_in_bytes
            + mem.output_size_in_bytes
            + mem.temp_size_in_bytes
            - mem.alias_size_in_bytes,
        },
        "collectives": colls.summary(),
        "hlo_len": len(hlo),
        "n_active_params": n_active,
        "model_flops": float(model_flops),
        "corrected": corrected,
    }
    if save_hlo:
        Path(save_hlo).write_text(hlo)
        result["hlo_path"] = save_hlo
    return result


def all_cells() -> list[tuple[str, str]]:
    from repro.configs import applicable_shapes, get_config, list_archs

    cells = []
    for arch in list_archs():
        cfg = get_config(arch)
        for s in applicable_shapes(cfg):
            cells.append((arch, s.name))
    return cells


def run_cells(
    *,
    arch: str | None = None,
    shape: str | None = None,
    run_all: bool = False,
    multi_pod: str = "off",
    profile: str | None = None,
    grad_accum: int = 1,
    out: str = "artifacts/dryrun",
    save_hlo: bool = False,
    smoke: bool = False,
    host_mesh: bool = False,
) -> dict:
    """Run a sweep of (arch x shape x pod) cells; the `python -m repro
    dryrun` workload body.  Always finishes the sweep and returns
    {tag: result-or-{"error": ...}} — exit policy is the CLI's job."""
    cells = all_cells() if run_all else [(arch, shape)]
    pods = {"off": [False], "on": [True], "both": [False, True]}[multi_pod]
    outdir = Path(out)
    outdir.mkdir(parents=True, exist_ok=True)

    results: dict[str, dict] = {}
    for arch_i, shape_i in cells:
        for mp in pods:
            tag = f"{arch_i}__{shape_i}__{'pod2' if mp else 'pod1'}"
            if profile:
                tag += f"__{profile}"
            if host_mesh:
                tag += "__host"
            dest = outdir / f"{tag}.json"
            try:
                res = run_cell(
                    arch_i, shape_i, mp,
                    profile=profile,
                    grad_accum=grad_accum,
                    save_hlo=str(outdir / f"{tag}.hlo") if save_hlo else None,
                    smoke=smoke,
                    host_mesh=host_mesh,
                )
                results[tag] = res
                dest.write_text(json.dumps(res, indent=1))
                corr = res.get("corrected") or {}
                print(
                    f"OK   {tag}: flops/dev={corr.get('flops', res['flops_per_device']):.3e} "
                    f"peak={res['memory']['peak_est_bytes']/2**30:.2f}GiB "
                    f"coll={corr.get('collective_bytes', res['collectives']['total_bytes'])/2**30:.3f}GiB "
                    f"compile={res['compile_s']:.1f}s",
                    flush=True,
                )
            except Exception as e:  # noqa: BLE001 - record and continue
                results[tag] = {"error": f"{type(e).__name__}: {e}"}
                dest.with_suffix(".err").write_text(traceback.format_exc())
                print(f"FAIL {tag}: {type(e).__name__}: {e}", flush=True)
    return results


def main(argv: list[str] | None = None) -> None:
    """Deprecated launcher: delegates to `python -m repro dryrun` (the flags
    are identical).  Kept so existing invocations keep working."""
    import sys
    import warnings

    warnings.warn(
        "python -m repro.launch.dryrun is deprecated; use "
        "`python -m repro dryrun`",
        DeprecationWarning,
        stacklevel=2,
    )
    from repro.app.cli import main as cli_main

    cli_main(["dryrun"] + (sys.argv[1:] if argv is None else list(argv)))


if __name__ == "__main__":
    main()
