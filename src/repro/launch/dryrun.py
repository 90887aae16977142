import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run driver (deliverable e).

For every (architecture × input shape × mesh): build the step function
through the cf4ocl-style ``core.Program`` wrapper, ``.lower()`` against
ShapeDtypeStruct stand-ins, ``.compile()``, print ``memory_analysis()``
(fit proof) and ``cost_analysis()`` (roofline terms), parse collective
traffic from the partitioned HLO, and persist everything to
``experiments/dryrun/<arch>__<shape>__<mesh>[__tag].json``.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-8b \
        --shape train_4k [--multi-pod] [--all] [--tag baseline]
"""

import argparse
import dataclasses
import json
import pathlib
import sys
import time
import traceback

import jax

from repro.configs import ALIASES, ARCHS, SHAPES, get_config, supports_shape
from repro.core import Context, Device, Program
from repro.dist.sharding import ShardCtx
from repro.launch import roofline as RL
from repro.launch import specs as SP
from repro.launch.mesh import make_production_mesh, mesh_devices
from repro.models import model as M
from repro.optim.adamw import AdamWConfig
from repro.serve.step import make_decode_step, make_prefill_step
from repro.train.step import StepConfig, make_train_step

OUT_DIR = pathlib.Path(__file__).resolve().parents[3] / "experiments" / "dryrun"


def build_cfg(arch: str, shape_name: str, overrides: dict):
    kind = SHAPES[shape_name]["kind"]
    cfg = get_config(arch)
    upd = {}
    if kind == "train":
        upd["remat"] = overrides.pop("remat", "full")
    upd.update(overrides)
    return dataclasses.replace(cfg, **upd), kind


def opt_config(cfg) -> AdamWConfig:
    # moments in bf16 for the 400B MoE so a single 16 GiB/chip pod fits
    mdt = "bfloat16" if M.param_count(cfg)[0] > 100e9 else "float32"
    return AdamWConfig(moments_dtype=mdt)


def probe_block(cfg, ctx, context, gi: int, kind: str, B: int, S: int,
                positions_len: int) -> dict:
    """Lower+compile ONE superblock (the scan body) and return its per-
    device cost dict — the correction unit for XLA's count-once while-loop
    accounting (DESIGN.md §6; verified in EXPERIMENTS.md §Dry-run)."""
    import jax.numpy as jnp
    acfg = dataclasses.replace(cfg, analysis_unroll=True,
                               collect_kv=(kind == "prefill"))
    pattern, count = acfg.groups[gi]
    x, lp, caches, ctxe = SP.block_probe_specs(acfg, ctx, gi, B, S, kind)
    positions = jax.numpy.arange(positions_len)

    out_sh = None
    if kind == "train":
        def block_loss(x, lp, ctxe=None):
            y, _, aux = M.apply_superblock(acfg, pattern, x, lp, None,
                                           positions, ctxe, False)
            return y.astype(jnp.float32).sum() + aux.loss
        inner = M.remat_wrap(acfg, block_loss)

        if ctxe is None:
            fn = lambda x, lp: jax.grad(inner, argnums=(0, 1))(x, lp)  # noqa: E731
            args = (x, lp)
        else:
            fn = lambda x, lp, c: jax.grad(  # noqa: E731
                inner, argnums=(0, 1, 2))(x, lp, c)
            args = (x, lp, ctxe)
        # pin grad outputs to the input shardings — otherwise GSPMD may
        # replicate the backward (or all-gather grads), which the real
        # program (whose grads stay sharded in the scan carry) never does
        out_sh = jax.tree.map(lambda s: s.sharding, args)
    elif kind == "prefill":
        def fn(x, lp, ctxe=None):
            y, ncs, _ = M.apply_superblock(acfg, pattern, x, lp, None,
                                           positions, ctxe, False)
            return y, ncs
        args = (x, lp) if ctxe is None else (x, lp, ctxe)
    else:
        def fn(x, lp, caches, pos, ctxe=None):
            posv = jax.numpy.broadcast_to(pos, (1,))
            y, ncs, _ = M.apply_superblock(acfg, pattern, x, lp, caches,
                                           posv, ctxe, True)
            return y, ncs
        pos = jax.ShapeDtypeStruct((), jnp.int32)
        args = (x, lp, caches, pos) if ctxe is None \
            else (x, lp, caches, pos, ctxe)
        out_sh = (x.sharding, jax.tree.map(lambda s: s.sharding, caches))

    prog = Program(context, fn, name=f"probe:{cfg.name}:g{gi}:{kind}")
    kw = {"out_shardings": out_sh} if out_sh is not None else {}
    prog.build(in_shardings=jax.tree.map(lambda s: s.sharding, args), **kw)
    prog.lower(*args)
    prog.compile()
    a = prog.analyze().to_dict()
    prog.destroy()
    return a


def probe_encoder(cfg, ctx, context, kind: str, B: int) -> dict:
    import jax.numpy as jnp
    acfg = dataclasses.replace(cfg, analysis_unroll=True)
    x, lp = SP.encoder_probe_specs(acfg, ctx, B)
    positions = jax.numpy.arange(acfg.encoder_seq)

    def block_loss(x, lp):
        y, _, _ = M.apply_superblock(acfg, (("bidir", "dense"),), x, (lp,),
                                     None, positions, None, False)
        return y.astype(jnp.float32).sum()

    out_sh = None
    if kind == "train":
        inner = M.remat_wrap(acfg, block_loss)
        fn = lambda x, lp: jax.grad(inner, argnums=(0, 1))(x, lp)  # noqa: E731
        out_sh = jax.tree.map(lambda s: s.sharding, (x, lp))
    else:
        def fn(x, lp):
            y, _, _ = M.apply_superblock(acfg, (("bidir", "dense"),), x,
                                         (lp,), None, positions, None, False)
            return y
    prog = Program(context, fn, name=f"probe:{cfg.name}:enc:{kind}")
    kw = {"out_shardings": out_sh} if out_sh is not None else {}
    prog.build(in_shardings=jax.tree.map(lambda s: s.sharding, (x, lp)), **kw)
    prog.lower(x, lp)
    prog.compile()
    a = prog.analyze().to_dict()
    prog.destroy()
    return a


_CORR_KEYS = ("flops", "bytes_accessed", "collective_bytes")


def apply_corrections(analysis: dict, corrections: list,
                      scale: float = 1.0) -> dict:
    """total = full(counted-once bodies) + scale × Σ (count-1) × body."""
    out = dict(analysis)
    for count, body in corrections:
        extra = max(0, count - 1) * scale
        for k in _CORR_KEYS:
            out[k] = out.get(k, 0.0) + extra * float(body.get(k, 0.0))
        for kk, vv in body.get("collective_bytes_by_kind", {}).items():
            d = out.setdefault("collective_bytes_by_kind", {})
            d[kk] = d.get(kk, 0) + int(extra * vv)
    return out


def probe_grads(cfg, ctx, context, B: int, S: int) -> dict:
    """One whole microbatch body (fwd+bwd, layer scans intact) — the
    correction unit for the gradient-accumulation while loop."""
    def fn(params, batch):
        from repro.dist.sharding import use_ctx
        with use_ctx(ctx):
            return jax.grad(lambda p: M.loss_fn(
                cfg, p, batch["tokens"], batch["labels"],
                ctx_embed=batch.get("ctx_embed")))(params)

    params = SP.param_specs(cfg, ctx)
    batch = SP.batch_specs(cfg, ctx, B, S)
    prog = Program(context, fn, name=f"probe:{cfg.name}:micro")
    prog.build(in_shardings=jax.tree.map(lambda s: s.sharding,
                                         (params, batch)),
               out_shardings=jax.tree.map(lambda s: s.sharding, params))
    prog.lower(params, batch)
    prog.compile()
    a = prog.analyze().to_dict()
    prog.destroy()
    return a


def pick_microbatches(B: int, S: int, data_shards: int,
                      target_tokens: int = 8192) -> int:
    """Gradient-accumulation factor so per-device per-micro activations fit
    (the remat layer-input × num_layers term is the train memory driver)."""
    tokens_per_dev = B * S // data_shards
    k = 1
    while tokens_per_dev // k > target_tokens and B // (2 * k) >= data_shards:
        k *= 2
    return k


def run_cell(arch: str, shape_name: str, multi_pod: bool, tag: str = "baseline",
             overrides: dict = None, verbose: bool = True,
             probes: bool = True) -> dict:
    overrides = dict(overrides or {})
    micro_override = int(overrides.pop("microbatches", 0))
    rules_name = str(overrides.pop("rules", "fsdp"))
    shp = SHAPES[shape_name]
    cfg, kind = build_cfg(arch, shape_name, overrides)
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    ndev = mesh_devices(mesh)
    from repro.dist.sharding import rules_variant
    # zero1 / moe_tp: params follow a lighter rule table, but optimizer
    # moments stay fully (ZeRO-)sharded
    split_moments = rules_name in ("zero1", "moe_tp")
    param_rules = {"zero1": "tp", "moe_tp": "moe_tp"}.get(rules_name,
                                                          rules_name)
    ctx = ShardCtx(mesh, rules_variant(param_rules))
    moments_ctx = ShardCtx(mesh, rules_variant("fsdp")) if split_moments \
        else None
    context = Context([Device.wrap(d) for d in mesh.devices.flat], mesh=mesh)

    B, S = shp["global_batch"], shp["seq_len"]
    t0 = time.perf_counter()

    micro = 1
    if kind == "train":
        data_shards = 32 if multi_pod else 16
        micro = micro_override or pick_microbatches(B, S, data_shards)
        opt = opt_config(cfg)
        compress = "bf16" if M.param_count(cfg)[0] > 100e9 else "none"
        fn = make_train_step(cfg, opt,
                             StepConfig(microbatches=micro,
                                        grad_compress=compress), ctx)
        state = SP.state_specs(cfg, opt, ctx, moments_ctx=moments_ctx)
        batch = SP.batch_specs(cfg, ctx, B, S)
        in_sh = jax.tree.map(lambda s: s.sharding, (state, batch))
        prog = Program(context, fn, name=f"train:{cfg.name}")
        prog.build(in_shardings=in_sh, donate_argnums=(0,))
        prog.lower(state, batch)
    elif kind == "prefill":
        fn = make_prefill_step(cfg, ctx)
        params = SP.param_specs(cfg, ctx)
        batch = SP.batch_specs(cfg, ctx, B, S, with_labels=False)
        args = (params, batch["tokens"])
        if "ctx_embed" in batch:
            args = args + (batch["ctx_embed"],)
        prog = Program(context, fn, name=f"prefill:{cfg.name}")
        prog.build(in_shardings=jax.tree.map(lambda s: s.sharding, args))
        prog.lower(*args)
    else:  # decode
        fn = make_decode_step(cfg, ctx)
        args = SP.decode_input_specs(cfg, ctx, B, S)
        prog = Program(context, fn, name=f"decode:{cfg.name}")
        prog.build(in_shardings=jax.tree.map(lambda s: s.sharding, args),
                   donate_argnums=(1,))
        prog.lower(*args)

    prog.compile()
    analysis = prog.analyze()
    compiled = prog.compiled
    ma = compiled.memory_analysis()
    if verbose:
        print(f"[{arch} × {shape_name} × {mesh_name}] "
              f"lower={analysis.lower_s:.1f}s compile={analysis.compile_s:.1f}s")
        print(f"  memory_analysis: args={ma.argument_size_in_bytes/2**30:.2f}GiB "
              f"out={ma.output_size_in_bytes/2**30:.2f}GiB "
              f"temp={ma.temp_size_in_bytes/2**30:.2f}GiB")
        print("  cost_analysis (uncorrected): flops/dev=%.3e bytes/dev=%.3e" %
              (analysis.flops, analysis.bytes_accessed))
        print("  collectives (uncorrected):\n" + analysis.collectives.summary())

    # XLA counts while-loop bodies once: probe each scan body and add
    # (count-1) × body to flops/bytes/collectives.  With gradient
    # accumulation (micro > 1) the micro scan is itself a while loop:
    #   total = A + (micro-1)·G(B/micro) + micro·Σ_g(count_g-1)·block(B/micro)
    # where G is one whole micro body (its own layer scans counted once and
    # fixed by the block terms).
    adict = analysis.to_dict()
    Bp = B // micro
    corrections = []
    if probes:
        for gi, (pattern, count) in enumerate(cfg.groups):
            if count > 1:
                body = probe_block(cfg, ctx, context, gi, kind, Bp,
                                   S if kind != "decode" else S,
                                   positions_len=S if kind != "decode" else 1)
                corrections.append((count, body))
        if cfg.encoder_layers > 1 and kind != "decode":
            corrections.append((cfg.encoder_layers,
                                probe_encoder(cfg, ctx, context, kind, Bp)))
    if micro > 1 and probes:
        g_body = probe_grads(cfg, ctx, context, Bp, S)
        adict = apply_corrections(adict, [(micro, g_body)])
        adict = apply_corrections(adict, corrections, scale=float(micro))
    else:
        adict = apply_corrections(adict, corrections)
    adict["microbatches"] = micro

    total, active = M.param_count(cfg)
    tokens = B * S if kind != "decode" else B  # decode: 1 token per row
    rl = RL.derive(arch, shape_name, mesh_name, ndev, kind,
                   adict, active, tokens)
    result = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name, "kind": kind,
        "tag": tag, "n_devices": ndev,
        "params_total": total, "params_active": active,
        "overrides": {k: str(v) for k, v in overrides.items()},
        "analysis": analysis.to_dict(),
        "roofline": rl.to_dict(),
        "wall_s": time.perf_counter() - t0,
    }
    if verbose:
        print("  " + RL.format_row(rl))
    OUT_DIR.mkdir(parents=True, exist_ok=True)
    out = OUT_DIR / f"{arch.replace('/', '_')}__{shape_name}__{mesh_name}__{tag}.json"
    out.write_text(json.dumps(result, indent=1))
    return result


def iter_cells(only_arch=None, only_shape=None):
    from repro.configs import get_config as _gc
    for arch in ARCHS:
        if only_arch and arch not in (only_arch, ALIASES.get(only_arch)):
            continue
        cfg = _gc(arch)
        for shape_name in SHAPES:
            if only_shape and shape_name != only_shape:
                continue
            yield arch, shape_name, supports_shape(cfg, shape_name)


def main(argv=None):
    ap = argparse.ArgumentParser(description="multi-pod dry-run")
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--tag", default="baseline")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--override", action="append", default=[],
                    help="cfg overrides key=value (e.g. remat=dots)")
    args = ap.parse_args(argv)

    overrides = {}
    for ov in args.override:
        k, v = ov.split("=", 1)
        for cast in (int, float):
            try:
                v = cast(v)
                break
            except ValueError:
                continue
        overrides[k] = v

    meshes = [args.multi_pod]
    if args.both_meshes:
        meshes = [False, True]

    failures = []
    ran = 0
    for arch, shape_name, ok in iter_cells(args.arch, args.shape):
        if not args.all and args.arch is None:
            break
        if not ok:
            print(f"[skip] {arch} × {shape_name}: needs sub-quadratic "
                  f"attention (DESIGN.md §4)")
            continue
        for mp in meshes:
            mname = "2x16x16" if mp else "16x16"
            fn = OUT_DIR / f"{arch}__{shape_name}__{mname}__{args.tag}.json"
            if args.skip_existing and fn.exists():
                print(f"[cached] {arch} × {shape_name} × {mname}")
                continue
            try:
                run_cell(arch, shape_name, mp, args.tag, overrides)
                ran += 1
            except Exception as e:  # noqa: BLE001
                failures.append((arch, shape_name, mname, repr(e)))
                traceback.print_exc()
    print(f"\ndry-run complete: {ran} cells, {len(failures)} failures")
    for f in failures:
        print("  FAIL:", *f)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
