import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS_EXTRA", "")
).strip()

"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production meshes and record memory/cost/collective analyses.

  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-4b --shape train_4k --mesh single
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both

Results are appended to results/dryrun/<arch>__<shape>__<mesh>.json and the
compiled HLO text is gzipped next to it (consumed by launch/roofline.py).
"""

import argparse
import gzip
import json
import time
import traceback
from pathlib import Path

import jax

from repro.configs import registry
from repro.configs.base import SHAPES
from repro.distributed import sharding as shd
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import DECODE_HEADROOM, input_specs
from repro.models import lm
from repro.training.optimizer import AdamWConfig, adamw_init
from repro.training.train_step import make_train_step

RESULTS = Path(__file__).resolve().parents[3] / "results" / "dryrun"


# Named perf-iteration variants (EXPERIMENTS.md §Perf): each maps
# (cfg, profile) -> (cfg, profile) for a hypothesis under test.
def _v_seq_parallel(cfg, profile):
    import dataclasses

    return cfg, dataclasses.replace(profile, seq_parallel=True)


def _v_model_as_dp(cfg, profile):
    import dataclasses

    return cfg, dataclasses.replace(
        profile, tp_axis="", extra_dp_axes=("model",),
        fsdp_axes=("data", "model"),
    )


def _v_fp8_dispatch(cfg, profile):
    import dataclasses

    assert cfg.moe is not None
    moe = dataclasses.replace(
        cfg.moe, a2a_dtype="float8_e4m3fn", capacity_factor=1.0,
        dispatch_chunks=4,
    )
    return dataclasses.replace(cfg, moe=moe), profile


def _v_fp8_dispatch_nochunk(cfg, profile):
    import dataclasses

    assert cfg.moe is not None
    moe = dataclasses.replace(cfg.moe, a2a_dtype="float8_e4m3fn",
                              capacity_factor=1.0)
    return dataclasses.replace(cfg, moe=moe), profile


def _v_granite_ep(cfg, profile):
    import dataclasses

    moe = dataclasses.replace(cfg.moe, mode="ep")
    return dataclasses.replace(cfg, moe=moe), profile


def _v_pad_heads(cfg, profile):
    import dataclasses

    # pad q heads to the next multiple of tp (28 -> 32) so head sharding is
    # clean, and replicate the (cheap) K/V projections instead of splitting
    # them within heads.
    return (
        dataclasses.replace(cfg, num_heads=32),
        dataclasses.replace(profile, shard_kv_proj=False),
    )


def _v_kimi_iter2(cfg, profile):
    import dataclasses

    cfg, profile = _v_fp8_dispatch(cfg, profile)
    return dataclasses.replace(cfg, attn_chunk=1024), profile


def _v_kv_seq(cfg, profile):
    import dataclasses

    return cfg, dataclasses.replace(profile, shard_kv_seq=True)


VARIANTS = {
    "seqpar": _v_seq_parallel,
    "padheads": _v_pad_heads,
    "kimi2": _v_kimi_iter2,
    "kvseq": _v_kv_seq,
    "modeldp": _v_model_as_dp,
    "fp8a2a": _v_fp8_dispatch,
    "fp8a2a_nochunk": _v_fp8_dispatch_nochunk,
    "graniteep": _v_granite_ep,
}


def build_cell(arch: str, shape_name: str, mesh, *, remat_override=None,
               profile_override=None, variant: str = ""):
    """Returns (jit_fn, example_args_sds, in_shardings) for one cell."""
    cfg = registry.get(arch)
    shape_kind = SHAPES[shape_name].kind
    profile = profile_override or registry.get_sharding(arch, shape_kind)
    if remat_override is not None:
        import dataclasses

        profile = dataclasses.replace(profile, remat=remat_override)
    if variant:
        cfg, profile = VARIANTS[variant](cfg, profile)
    shape = SHAPES[shape_name]
    dp = shd.dp_axes_for_mesh(mesh, profile)
    ctx = lm.ParallelCtx(mesh=mesh, dp_axes=dp, tp_axis=profile.tp_axis,
                         ep_axis=profile.ep_axis, remat=profile.remat,
                         seq_parallel=profile.seq_parallel)

    params_sds = lm.abstract_params(cfg)
    param_sh = shd.to_shardings(shd.param_pspecs(params_sds, profile, mesh), mesh)
    batch_sds, cache_sds = input_specs(cfg, shape)
    batch_sh = shd.to_shardings(shd.batch_pspecs(batch_sds, mesh, profile), mesh)

    if shape.kind == "train":
        opt_cfg = AdamWConfig(state_dtype=profile.optimizer_dtype)
        opt_sds = jax.eval_shape(lambda p: adamw_init(p, opt_cfg), params_sds)
        opt_sh = {
            "m": param_sh,
            "v": param_sh,
            "step": shd.to_shardings(jax.sharding.PartitionSpec(), mesh),
        }
        step_fn = make_train_step(cfg, opt_cfg, ctx)
        fn = jax.jit(
            step_fn,
            in_shardings=(param_sh, opt_sh, batch_sh),
            donate_argnums=(0, 1),
        )
        return fn, (params_sds, opt_sds, batch_sds)

    if shape.kind == "prefill":
        def prefill_fn(params, batch):
            logits, cache = lm.prefill(cfg, params, batch, ctx)
            return logits[:, -1], cache

        fn = jax.jit(prefill_fn, in_shardings=(param_sh, batch_sh))
        return fn, (params_sds, batch_sds)

    # decode
    cache_sh = shd.to_shardings(shd.cache_pspecs(cache_sds, cfg, profile, mesh), mesh)

    def decode_fn(params, cache, batch):
        return lm.decode_step(cfg, params, cache, batch["tokens"], ctx)

    fn = jax.jit(
        decode_fn, in_shardings=(param_sh, cache_sh, batch_sh), donate_argnums=(1,)
    )
    return fn, (params_sds, cache_sds, batch_sds)


def run_cell(arch: str, shape_name: str, mesh_kind: str, *, save_hlo: bool = True,
             tag: str = "", remat_override=None, variant: str = "") -> dict:
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "mesh_shape": dict(zip(mesh.axis_names, mesh.devices.shape)),
        "tag": tag, "variant": variant, "ok": False,
    }
    try:
        fn, args = build_cell(arch, shape_name, mesh,
                              remat_override=remat_override, variant=variant)
        with jax.set_mesh(mesh):
            lowered = fn.lower(*args)
            t1 = time.time()
            compiled = lowered.compile()
            t2 = time.time()
        rec["lower_s"] = round(t1 - t0, 2)
        rec["compile_s"] = round(t2 - t1, 2)
        try:
            ma = compiled.memory_analysis()
            rec["memory"] = {
                "argument_bytes": int(getattr(ma, "argument_size_in_bytes", 0)),
                "output_bytes": int(getattr(ma, "output_size_in_bytes", 0)),
                "temp_bytes": int(getattr(ma, "temp_size_in_bytes", 0)),
                "generated_code_bytes": int(getattr(ma, "generated_code_size_in_bytes", 0)),
            }
            print("memory_analysis:", rec["memory"])
        except Exception as e:  # pragma: no cover
            rec["memory_error"] = str(e)
        try:
            ca = compiled.cost_analysis()
            rec["cost_analysis"] = {
                k: float(v)
                for k, v in ca.items()
                if isinstance(v, (int, float)) and k in (
                    "flops", "bytes accessed", "transcendentals",
                    "bytes accessed0{}", "bytes accessedout{}",
                )
            }
            print("cost_analysis:", rec["cost_analysis"])
        except Exception as e:  # pragma: no cover
            rec["cost_error"] = str(e)
        if save_hlo:
            RESULTS.mkdir(parents=True, exist_ok=True)
            hlo_path = RESULTS / f"{arch}__{shape_name}__{mesh_kind}{tag}.hlo.gz"
            with gzip.open(hlo_path, "wt") as f:
                f.write(compiled.as_text())
            rec["hlo"] = str(hlo_path)
        rec["ok"] = True
    except Exception as e:
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    rec["total_s"] = round(time.time() - t0, 2)
    RESULTS.mkdir(parents=True, exist_ok=True)
    out = RESULTS / f"{arch}__{shape_name}__{mesh_kind}{tag}.json"
    out.write_text(json.dumps(rec, indent=2))
    status = "OK" if rec["ok"] else f"FAIL ({rec.get('error')})"
    print(f"[dryrun] {arch} x {shape_name} x {mesh_kind}{tag}: {status} "
          f"in {rec['total_s']}s")
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--tag", default="")
    ap.add_argument("--remat", default=None)
    ap.add_argument("--variant", default="")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    if args.all:
        cells = registry.all_cells()
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    n_ok = n_fail = 0
    for arch, shape in cells:
        for mk in meshes:
            if args.skip_existing:
                f = RESULTS / f"{arch}__{shape}__{mk}{args.tag}.json"
                if f.exists() and json.loads(f.read_text()).get("ok"):
                    print(f"[dryrun] skip existing {arch} x {shape} x {mk}")
                    n_ok += 1
                    continue
            rec = run_cell(arch, shape, mk, tag=args.tag,
                           remat_override=args.remat, variant=args.variant)
            n_ok += rec["ok"]
            n_fail += not rec["ok"]
    print(f"[dryrun] done: {n_ok} ok, {n_fail} failed")
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
