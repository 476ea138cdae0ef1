import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run: lower + compile every (architecture x input shape)
against the production meshes, and extract the roofline terms.

The lines above MUST stay the first statements in this file — jax
locks the host platform device count on first initialization, and the
dry-run needs 512 placeholder devices for the 2x16x16 multi-pod mesh,
pinned to the CPU even on a host that has an accelerator.
(Do NOT import this module from tests; run it as a subprocess.)

Usage:
  python -m repro.launch.dryrun --arch qwen3-14b --shape train_4k
  python -m repro.launch.dryrun --arch kimi-k2-1t-a32b --shape decode_32k \
      --multi-pod --packed base3
  python -m repro.launch.dryrun --all            # subprocess per cell, resumable
"""
import argparse
import dataclasses
import json
import subprocess
import sys
import time

import jax
import jax.numpy as jnp

DEFAULT_OUT = "experiments/dryrun"


def _mesh_tag(multi_pod: bool) -> str:
    return "2x16x16" if multi_pod else "16x16"


def cell_filename(arch: str, shape: str, multi_pod: bool,
                  packed: str | None) -> str:
    tag = _mesh_tag(multi_pod)
    suffix = f"__{packed}" if packed else ""
    return f"{arch}__{shape}__{tag}{suffix}.json"


def run_cell(arch: str, shape: str, *, multi_pod: bool = False,
             packed: str | None = None, microbatches: int = 0,
             fsdp: bool = True, remat: str = "full",
             opt_name: str = "auto", ep: str = "model", sp: bool = False,
             pure_dp: bool = False, kv_cache: str = "",
             decode_loop: int = 0, continuous: int = 0,
             kv_layout: str = "dense", page_size: int = 16,
             fidelity: str = "exact",
             extra_tags: dict | None = None) -> dict:
    from repro import configs
    from repro.configs.shapes import SHAPES, runnable
    from repro.dist import sharding as shd
    from repro.dist import variants
    from repro.launch.input_specs import (abstract_cache,
                                          abstract_model_params,
                                          decode_token_spec,
                                          paged_pool_specs,
                                          prefill_batch_specs,
                                          slot_pool_specs,
                                          train_batch_specs)
    from repro.launch.mesh import make_production_mesh
    from repro.models import registry
    from repro.roofline import analyze_compiled
    from repro.core.cim_linear import CIMConfig

    if fidelity == "device" and not packed:
        raise ValueError("fidelity 'device' requires packed ternary "
                         "weights (--packed); the device model faults "
                         "packed trits")
    cfg = configs.get(arch)
    cell = SHAPES[shape]
    meta = {"arch": arch, "shape": shape, "mesh": _mesh_tag(multi_pod),
            "packed": packed, "fsdp": fsdp, "remat": remat,
            "microbatches": microbatches, **(extra_tags or {})}
    ok, reason = runnable(cfg, cell)
    if not ok:
        return {**meta, "skipped": reason}
    if kv_cache == "int8":
        cfg = dataclasses.replace(cfg, kv_cache_dtype="int8")
        meta["kv_cache"] = "int8"

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    mode = "train" if cell.kind == "train" else "serve"
    rules = shd.rules_for(cfg, mode, fsdp=fsdp)
    # the named overrides live in dist.variants (the registry the
    # `shard` analysis pass proves contracts over) — applying them
    # through apply_override keeps the dry-run and the prover on the
    # same lattice; see each OVERRIDES entry for the rationale
    if ep == "data":
        rules = variants.apply_override(rules, "ep-data")
        meta["ep"] = ep
    if pure_dp:
        rules = variants.apply_override(rules, "pure-dp")
        meta["pure_dp"] = True
    if sp:
        # activations shard (batch x data, seq x model): the TP matmuls
        # all-gather / reduce-scatter the seq axis around them (same
        # wire bytes as the TP all-reduces they replace) but norms,
        # residuals, rope, and crucially ATTENTION SCORES for archs
        # whose head count does not divide the 16-way model axis
        # (qwen3: 40H, whisper: 20H) stop being replicated 16x
        rules = variants.apply_override(rules, "sp")
        meta["sp"] = True
    shd.set_activation_context(rules, mesh)
    if cell.kind == "train" and remat != "config":
        cfg = dataclasses.replace(cfg, remat=remat)
    model = registry.build(cfg)
    # resolved once against the kernel registry: the dry-run pins the
    # xla backend (Pallas TPU kernels cannot lower on the CPU host
    # platform) and records the resolved routing in the cell metadata.
    # A 'device' fidelity request cannot pin xla (the fault-injected
    # backend is the only device-capable one): it resolves 'auto' under
    # the cell's phase, so decode cells lower the device path and
    # prefill cells route back to an exact backend (route_fidelity).
    cim = None
    if packed:
        if fidelity == "device":
            if cell.kind == "train":
                raise ValueError("--fidelity device is a serving "
                                 "fidelity; train cells have no device "
                                 "path")
            from repro import faults
            faults.set_fault_model(faults.measured_fault_model(
                num_mc=1024))
            phase = "decode" if cell.kind == "decode" else "prefill"
            cim = CIMConfig(mode="ternary", packing=packed,
                            backend="auto",
                            fidelity="device").resolve(phase=phase)
        else:
            cim = CIMConfig(mode="ternary", packing=packed,
                            backend="xla").resolve()
        meta["cim_backend"] = cim.backend
        meta["cim_fidelity"] = cim.fidelity

    t0 = time.monotonic()
    if cell.kind == "train":
        from repro.optim import adafactor, adamw, warmup_cosine
        from repro.train.step import make_abstract_state, make_train_step
        nparams = cfg.param_count()
        use_adafactor = (opt_name == "adafactor" or
                         (opt_name == "auto" and nparams > 3e9))
        lr = warmup_cosine(3e-4, 1000, 100_000)
        opt = adafactor(lr) if use_adafactor else adamw(lr)
        meta["optimizer"] = "adafactor" if use_adafactor else "adamw"
        mb = microbatches or (8 if cell.global_batch >= 64 else 1)
        meta["microbatches"] = mb
        state_abs, _specs = make_abstract_state(model, opt, rules, mesh)
        batch_abs = train_batch_specs(cfg, cell, rules, mesh)
        step_fn = make_train_step(model, opt, cim=cim, microbatches=mb,
                                  rules=rules, mesh=mesh)
        lowered = jax.jit(step_fn, donate_argnums=(0,)).lower(
            state_abs, batch_abs)
        tokens = cell.global_batch * cell.seq_len
    elif cell.kind == "prefill":
        params_abs = abstract_model_params(model, rules, mesh, packed)
        batch_abs = prefill_batch_specs(cfg, cell, rules, mesh)

        def prefill_step(params, batch):
            logits, state = model.prefill(params, batch, cell.seq_len,
                                          cim=cim)
            return jnp.argmax(logits[:, -1], -1).astype(jnp.int32), state

        lowered = jax.jit(prefill_step).lower(params_abs, batch_abs)
        tokens = cell.global_batch * cell.seq_len
    else:                                   # decode
        params_abs = abstract_model_params(model, rules, mesh, packed)
        if kv_layout == "paged" and not continuous:
            raise ValueError("--kv paged requires --continuous SLOTS "
                             "(the paged pool is a continuous-batching "
                             "slot-pool layout)")
        if decode_loop and not continuous:
            raise ValueError("--decode-loop is the chunk budget of a "
                             "--continuous SLOTS round")
        if continuous and kv_layout == "paged":
            # paged-KV slot pool: lower one chunked round of the paged
            # scheduler loop (serve.make_paged_decode_loop) — the page
            # pool on a 'page' logical axis, per-slot page tables +
            # write positions, one host transfer per chunk.
            from repro.serve import make_paged_decode_loop
            chunk = decode_loop if decode_loop >= 1 else 8
            pages_per_slot = -(-cell.seq_len // page_size)
            num_pages = 1 + continuous * pages_per_slot
            (pool_abs, table_abs, pos_abs, tok_abs, live_abs, made_abs,
             fresh_abs, mn_abs, eos_abs) = paged_pool_specs(
                model, cell, rules, mesh, continuous, page_size,
                num_pages)
            loop_fn = make_paged_decode_loop(
                model, chunk, cim,
                spmd_axes=shd.slot_spmd_axes(rules, mesh, continuous))
            lowered = loop_fn.lower(params_abs, tok_abs, pool_abs,
                                    table_abs, pos_abs, live_abs,
                                    made_abs, fresh_abs, mn_abs, eos_abs)
            tokens = continuous * chunk
            meta["continuous_slots"] = continuous
            meta["chunk"] = chunk
            meta["kv_layout"] = "paged"
            meta["page_size"] = page_size
            meta["num_pages"] = num_pages
        elif continuous:
            # continuous-batching slot pool: lower one chunked decode
            # round (serve.make_chunked_decode_loop) — per-slot batch-1
            # states at independent positions, slot axis folded over DP,
            # one host transfer per chunk.  Chunk budget comes from
            # --decode-loop (default 8 steps).
            from repro.serve import make_chunked_decode_loop
            chunk = decode_loop if decode_loop >= 1 else 8
            specs = slot_pool_specs(model, cell, rules, mesh, continuous)
            pool_abs, tok_abs, live_abs, made_abs, fresh_abs, mn_abs, \
                eos_abs = specs
            loop_fn = make_chunked_decode_loop(
                model, chunk, cim,
                spmd_axes=shd.slot_spmd_axes(rules, mesh, continuous))
            lowered = loop_fn.lower(params_abs, tok_abs, pool_abs,
                                    live_abs, made_abs, fresh_abs,
                                    mn_abs, eos_abs)
            # at most `chunk` tokens per slot per scheduling round
            tokens = continuous * chunk
            meta["continuous_slots"] = continuous
            meta["chunk"] = chunk
        else:
            cache_abs = abstract_cache(model, cell, rules, mesh)
            token_abs = decode_token_spec(cell, rules, mesh)

            def serve_step(params, token, state):
                logits, st = model.decode(params, token, state, cim=cim)
                return jnp.argmax(logits[:, -1], -1).astype(jnp.int32), st

            lowered = jax.jit(serve_step, donate_argnums=(2,)).lower(
                params_abs, token_abs, cache_abs)
            tokens = cell.global_batch
    t_lower = time.monotonic() - t0

    t0 = time.monotonic()
    compiled = lowered.compile()
    t_compile = time.monotonic() - t0

    print(compiled.memory_analysis())       # proves it fits
    ca = compiled.cost_analysis()
    print({k: v for k, v in (ca[0] if isinstance(ca, list) else ca).items()
           if k in ("flops", "bytes accessed")})

    report = analyze_compiled(
        compiled, arch=arch, shape=shape, mesh_name=_mesh_tag(multi_pod),
        chips=chips, cfg=cfg, tokens=tokens,
        kind="train" if cell.kind == "train" else "serve")
    out = {**meta, "lower_s": round(t_lower, 2),
           "compile_s": round(t_compile, 2), **report.to_dict()}
    return out


def save_result(result: dict, out_dir: str):
    os.makedirs(out_dir, exist_ok=True)
    fname = cell_filename(result["arch"], result["shape"],
                          result["mesh"] == "2x16x16", result.get("packed"))
    with open(os.path.join(out_dir, fname), "w") as f:
        json.dump(result, f, indent=1, default=str)
    if "skipped" in result:
        print(f"[skip] {fname}: {result['skipped']}")
    else:
        print(f"[ok]   {fname}: bottleneck={result['bottleneck']} "
              f"compute={result['t_compute']*1e3:.2f}ms "
              f"memory={result['t_memory']*1e3:.2f}ms "
              f"collective={result['t_collective']*1e3:.2f}ms "
              f"(compile {result['compile_s']}s)")


def sweep(out_dir: str, multi_pod_too: bool = True, resume: bool = True,
          packed: str | None = None, archs=None, timeout: int = 3600):
    """Subprocess-per-cell sweep (isolates XLA state; resumable)."""
    from repro import configs
    from repro.configs.shapes import SHAPES
    meshes = [False, True] if multi_pod_too else [False]
    failures = []
    for arch in (archs or configs.ARCHS):
        for shape in SHAPES:
            for mp in meshes:
                fname = cell_filename(arch, shape, mp, packed)
                path = os.path.join(out_dir, fname)
                if resume and os.path.exists(path):
                    continue
                cmd = [sys.executable, "-m", "repro.launch.dryrun",
                       "--arch", arch, "--shape", shape,
                       "--out-dir", out_dir]
                if mp:
                    cmd.append("--multi-pod")
                if packed:
                    cmd += ["--packed", packed]
                print(f"--- {fname}", flush=True)
                try:
                    r = subprocess.run(cmd, timeout=timeout,
                                       capture_output=True, text=True)
                    if r.returncode:
                        failures.append(fname)
                        print(r.stdout[-2000:])
                        print(r.stderr[-4000:])
                except subprocess.TimeoutExpired:
                    failures.append(fname + " (timeout)")
    print(f"sweep done; {len(failures)} failures: {failures}")
    return failures


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--arch")
    p.add_argument("--shape")
    p.add_argument("--multi-pod", action="store_true")
    p.add_argument("--all", action="store_true")
    p.add_argument("--single-pod-only", action="store_true")
    p.add_argument("--packed", choices=("base3", "trit2"))
    p.add_argument("--microbatches", type=int, default=0)
    p.add_argument("--no-fsdp", action="store_true")
    p.add_argument("--remat", default="full",
                   choices=("full", "dots", "none", "config"))
    p.add_argument("--opt", default="auto",
                   choices=("auto", "adamw", "adafactor"))
    p.add_argument("--ep", default="model", choices=("model", "data"))
    p.add_argument("--sp", action="store_true",
                   help="sequence parallelism over the model axis")
    p.add_argument("--pure-dp", action="store_true",
                   help="fold the model axis into data parallelism")
    p.add_argument("--kv-cache", default="", choices=("", "int8"),
                   help="KV cache storage dtype (int8 = scaled)")
    p.add_argument("--decode-loop", type=int, default=0,
                   help="chunk budget (decode steps) of the --continuous "
                        "round (default 8)")
    p.add_argument("--continuous", type=int, default=0, metavar="SLOTS",
                   help="decode cells: lower one chunked round of the "
                        "continuous-batching slot pool with this many "
                        "slots (chunk budget = --decode-loop, default 8)")
    p.add_argument("--kv", default="dense", choices=("dense", "paged"),
                   help="slot-pool KV layout for --continuous: dense "
                        "per-slot caches or the paged block pool "
                        "(serve.make_paged_decode_loop)")
    p.add_argument("--page-size", type=int, default=16,
                   help="positions per KV page for --kv paged")
    p.add_argument("--fidelity", default="exact",
                   choices=("exact", "device"),
                   help="execution fidelity for packed cells: 'device' "
                        "lowers decode through the fault-injected "
                        "analog backend (prefill cells route back to "
                        "exact — see repro.faults)")
    p.add_argument("--out-dir", default=DEFAULT_OUT)
    p.add_argument("--tag", default=None,
                   help="suffix for the output file (perf experiments)")
    args = p.parse_args(argv)
    if args.kv == "paged" and not args.continuous:
        p.error("--kv paged requires --continuous SLOTS (the paged "
                "pool is a continuous-batching slot-pool layout)")
    if args.decode_loop and not args.continuous:
        p.error("--decode-loop is the chunk budget of a --continuous "
                "SLOTS round")

    if args.all:
        fails = sweep(args.out_dir, multi_pod_too=not args.single_pod_only,
                      packed=args.packed)
        sys.exit(1 if fails else 0)

    if not args.arch or not args.shape:
        p.error("--arch and --shape required (or --all)")
    # no blanket except here: a failing cell should crash with its real
    # traceback and the interpreter's nonzero exit, not a laundered
    # sys.exit(1) that hides the exception type from callers
    res = run_cell(args.arch, args.shape, multi_pod=args.multi_pod,
                   packed=args.packed, microbatches=args.microbatches,
                   fsdp=not args.no_fsdp, remat=args.remat,
                   opt_name=args.opt, ep=args.ep, sp=args.sp,
                   pure_dp=args.pure_dp, kv_cache=args.kv_cache,
                   decode_loop=args.decode_loop,
                   continuous=args.continuous, kv_layout=args.kv,
                   page_size=args.page_size, fidelity=args.fidelity)
    if args.tag:
        res["tag"] = args.tag
        os.makedirs(args.out_dir, exist_ok=True)
        fname = cell_filename(res["arch"], res["shape"],
                              res["mesh"] == "2x16x16", res.get("packed"))
        fname = fname.replace(".json", f"__{args.tag}.json")
        with open(os.path.join(args.out_dir, fname), "w") as f:
            json.dump(res, f, indent=1, default=str)
        print(f"[ok] {fname}")
    else:
        save_result(res, args.out_dir)


if __name__ == "__main__":
    main()
