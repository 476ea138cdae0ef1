"""Serving launcher — batched requests against a (optionally ternary-
packed) model.  The paper's end-to-end mode: weights stored at 1 byte /
5-trit weight (base3) or 2 bits/trit (trit2) and dequantized on-load.

Three drivers:
  * bucket (default) — ServeEngine pops one prompt-length bucket at a
    time (on-device decode loop per bucket);
  * ``--continuous`` — the continuous-batching Scheduler: a persistent
    pool of ``--slots`` decode slots, chunked on-device decode
    (``--chunk`` steps per host yield) with prefill-into-freed-slot
    admission;
  * ``--frontend`` — the SLO-aware serving front-end
    (``repro.frontend``): a model registry (``--frontend-models``, one
    scheduler pool per architecture) behind one bounded-queue submit
    path (``--queue-limit``), with FIFO or priority/deadline admission
    (``--admission slo``) and the open-loop trace replay as the
    request stream.

Request streams: all-at-once (default), a Poisson arrival stream
(``--arrival-rate`` requests/s), or a recorded JSON trace
(``--trace-file``: list of {arrival_s, prompt_len, max_new, eos_id}
plus optional {priority, deadline_s} SLO fields).  With an arrival
stream all drivers replay the same trace, so their latency percentiles
are comparable.

  PYTHONPATH=src python -m repro.launch.serve --arch internlm2-1.8b \
      --smoke --requests 16 --prompt-len 32 --max-new 16 --packed base3 \
      --continuous --slots 8 --chunk 8 --arrival-rate 50

  PYTHONPATH=src python -m repro.launch.serve --frontend \
      --frontend-models internlm2-1.8b,qwen3-14b --smoke --requests 16 \
      --admission slo --deadline-s 0.5 --arrival-rate 50
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import time

import jax
import jax.numpy as jnp


def main(argv=None) -> dict:
    """Serve one request stream; prints the result as one JSON line and
    returns the same dict."""
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="internlm2-1.8b")
    p.add_argument("--smoke", action="store_true")
    p.add_argument("--requests", type=int, default=8)
    p.add_argument("--prompt-len", type=int, default=32)
    p.add_argument("--max-new", type=int, default=16)
    p.add_argument("--max-batch", type=int, default=8)
    p.add_argument("--capacity", type=int, default=256)
    p.add_argument("--packed", choices=("base3", "trit2"))
    p.add_argument("--domain", default="float", choices=("float", "int8"),
                   help="ternary-mode MXU domain (int8 = decode fast lane)")
    p.add_argument("--backend", default="auto",
                   help="kernel execution backend (any registered name; "
                        "'auto' = capability match, see "
                        "src/repro/kernels/README.md)")
    p.add_argument("--fidelity", default="exact",
                   choices=("exact", "device"),
                   help="execution fidelity: 'device' serves decode "
                        "through the fault-injected analog backend at "
                        "the measured TL restore yield (prefill stays "
                        "exact — see repro.faults); requires --packed")
    p.add_argument("--scrub-every", type=int, default=8,
                   help="decode chunks between restore-scrub repairs "
                        "under --fidelity device (0 disables scrubbing "
                        "— degradation accumulates)")
    p.add_argument("--legacy-loop", action="store_true",
                   help="per-step decode driver (one host sync per token) "
                        "instead of the on-device lax.while_loop")
    p.add_argument("--continuous", action="store_true",
                   help="continuous-batching Scheduler (slot pool + "
                        "chunked decode) instead of the bucket engine")
    p.add_argument("--slots", type=int, default=0,
                   help="decode slots for --continuous (default: "
                        "--max-batch)")
    p.add_argument("--chunk", type=int, default=8,
                   help="decode steps per scheduling round (host yield)")
    p.add_argument("--kv", default=None, choices=("dense", "paged"),
                   help="--continuous/--frontend KV layout: dense "
                        "per-slot caches or the paged, prefix-shared "
                        "block pool (default: dense for --continuous, "
                        "paged for --frontend pools)")
    p.add_argument("--page-size", type=int, default=16,
                   help="positions per KV page for --kv paged")
    p.add_argument("--num-pages", type=int, default=0,
                   help="page-pool size for --kv paged (0 = the "
                        "dense-pool equivalent: slots x capacity / "
                        "page size usable pages, + 1 for the reserved "
                        "null page)")
    p.add_argument("--arrival-rate", type=float, default=0.0,
                   help="Poisson request arrivals per second (0 = all "
                        "requests available at t=0)")
    p.add_argument("--trace-file", default=None,
                   help="JSON arrival trace: list of {arrival_s, "
                        "prompt_len, max_new, eos_id} (overrides "
                        "--requests/--prompt-len/--max-new/--arrival-rate)")
    p.add_argument("--frontend", action="store_true",
                   help="serve through the SLO-aware front-end "
                        "(repro.frontend): model registry + bounded "
                        "queue + admission policy + open-loop replay")
    p.add_argument("--frontend-models", default=None, metavar="A,B",
                   help="comma-separated architecture names to "
                        "register as front-end pools (default: --arch)")
    p.add_argument("--queue-limit", type=int, default=64,
                   help="--frontend pending-queue bound; past it "
                        "submits are rejected with 'queue-full'")
    p.add_argument("--admission", default="fifo",
                   choices=("fifo", "slo"),
                   help="--frontend admission policy: fifo, or slo "
                        "(priority classes + earliest-deadline-first "
                        "+ shedding of unmeetable requests)")
    p.add_argument("--deadline-s", type=float, default=0.0,
                   help="--frontend relative completion budget applied "
                        "to every generated request (0 = no deadline; "
                        "a --trace-file's per-record deadline_s wins)")
    p.add_argument("--service-floor-s", type=float, default=0.0,
                   help="--admission slo minimum-service estimate: "
                        "pending requests whose deadline cannot be met "
                        "within it are shed")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)
    kv = args.kv or ("paged" if args.frontend else "dense")
    if kv == "paged" and not (args.continuous or args.frontend):
        p.error("--kv paged requires --continuous or --frontend (the "
                "paged pool is a slot-pool layout)")
    if args.frontend and args.continuous:
        p.error("--frontend drives its registry's scheduler pools "
                "itself; drop --continuous")
    if args.frontend and (args.packed or args.fidelity == "device"):
        p.error("--frontend pools serve float weights through the "
                "model registry; packed/device-fidelity serving is the "
                "bucket/--continuous path")
    if args.frontend and args.legacy_loop:
        p.error("--frontend has no legacy per-step loop; its pools are "
                "chunked schedulers")
    if args.fidelity == "device" and not args.packed:
        p.error("--fidelity device requires --packed (the device model "
                "faults packed ternary weights; float serving has no "
                "device path)")
    if args.fidelity == "device" and not args.continuous:
        p.error("--fidelity device requires --continuous (drift + "
                "restore-scrub are per-chunk hooks of the Scheduler)")

    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()
    if args.frontend:
        return _run_frontend(args, kv)

    from repro import configs
    from repro.core.cim_linear import CIMConfig, hbm_bytes, ternarize_params
    from repro.models import registry
    from repro.serve import (PagedScheduler, Request, Scheduler,
                             ServeEngine, latency_stats, load_trace,
                             make_trace, poisson_arrivals)

    t_setup = time.monotonic()
    cfg = configs.smoke(args.arch) if args.smoke else configs.get(args.arch)
    model = registry.build(cfg)
    params = model.init(jax.random.key(args.seed))
    raw_bytes = hbm_bytes(params)

    cim = cim_decode = None
    if args.packed:
        if args.fidelity == "device":
            # pin the measured-yield fault campaign BEFORE resolution so
            # the device backend serves the paper's TL restore yield
            from repro import faults
            faults.set_fault_model(faults.measured_fault_model(
                seed=args.seed, drift_rate=0.001))
        # fail fast for BOTH phases the engines will resolve (a device
        # request splits decode->device / prefill->exact; pinning the
        # decode resolution into the request would poison the prefill
        # one, so the engines get the unresolved request)
        cim = CIMConfig(mode="ternary", packing=args.packed,
                        domain=args.domain, backend=args.backend,
                        fidelity=args.fidelity)
        cim_decode = cim.resolve()
        cim.resolve(phase="prefill")
        params = ternarize_params(params, cim)
    print(f"arch={cfg.name} params={cfg.param_count()/1e6:.1f}M "
          f"weights {raw_bytes/1e6:.1f}MB -> {hbm_bytes(params)/1e6:.1f}MB "
          f"({args.packed or 'float'}"
          + (f", backend={cim_decode.backend}, domain={cim_decode.domain}, "
             f"fidelity={cim_decode.fidelity}" if cim else "") + ")")

    extra = {}
    if cfg.family == "audio":
        extra["frames"] = lambda b: jnp.zeros(
            (b, cfg.encoder_seq, cfg.d_model), cfg.dtype)
    if cfg.family == "vlm":
        extra["patches"] = lambda b: jnp.zeros(
            (b, cfg.encoder_seq, cfg.d_model), cfg.dtype)

    if args.trace_file:
        trace = load_trace(args.trace_file)
    else:
        arrivals = poisson_arrivals(args.requests, args.arrival_rate,
                                    seed=args.seed)
        trace = make_trace(arrivals, [args.prompt_len], [args.max_new])

    if args.continuous and kv == "paged":
        eng = PagedScheduler(model, params, capacity=args.capacity,
                             slots=args.slots or args.max_batch,
                             chunk=args.chunk, page_size=args.page_size,
                             num_pages=args.num_pages or None,
                             cim=cim, extra_inputs=extra,
                             scrub_every=args.scrub_every)
    elif args.continuous:
        eng = Scheduler(model, params, capacity=args.capacity,
                        slots=args.slots or args.max_batch,
                        chunk=args.chunk, cim=cim, extra_inputs=extra,
                        scrub_every=args.scrub_every)
    else:
        eng = ServeEngine(model, params, capacity=args.capacity,
                          max_batch=args.max_batch, cim=cim,
                          extra_inputs=extra,
                          on_device_loop=not args.legacy_loop)

    key = jax.random.key(args.seed + 1)
    for i, rec in enumerate(trace):
        k = jax.random.fold_in(key, i)
        prompt = jax.random.randint(k, (rec["prompt_len"],), 0,
                                    cfg.vocab_size)
        eng.submit(Request(uid=i, prompt=prompt, max_new=rec["max_new"],
                           eos_id=rec["eos_id"],
                           arrival_s=rec["arrival_s"]))

    t0 = time.monotonic()
    setup_s = t0 - t_setup        # weights built and packed, engine ready
    if args.continuous:
        done = eng.run()                      # natively arrival-aware
    else:
        # run_trace even when every arrival is 0.0 (no sleeps happen):
        # it stamps latency_s = completion - arrival, the same
        # definition the Scheduler uses, so the printed p50/p99 are
        # comparable across drivers
        done = eng.run_trace()
    dt = time.monotonic() - t0

    out = {
        "requests": len(done),
        "generated_tokens": eng.generated_tokens,
        "steps": eng.steps_run,
        "host_transfers": eng.host_transfers,
        "setup_s": round(setup_s, 2),
        "wall_s": round(dt, 2),
        "tok_per_s": round(eng.generated_tokens / max(dt, 1e-9), 1),
        **latency_stats(done),
    }
    # digest of every request's tokens, by uid: two runs of the same
    # requests served the same tokens iff their digests are equal
    out["tokens_digest"] = hashlib.sha256(json.dumps(
        sorted((r.uid, r.out_tokens) for r in done)).encode()).hexdigest()
    if cim_decode is not None:
        out["fidelity"] = cim_decode.fidelity
        # the decode plan request as the engine resolved it
        out["plan"] = eng.cim.plan_request()
    if args.continuous:
        out.update(decode_loop="continuous", slots=eng.slots,
                   chunk=eng.chunk, chunks=eng.chunks_run,
                   slot_occupancy=round(eng.slot_occupancy, 3))
        if cim_decode is not None and cim_decode.fidelity == "device":
            out.update(scrubs=eng.scrubs_run,
                       adc_clip_lo=eng.adc_clip_lo,
                       adc_clip_hi=eng.adc_clip_hi)
        if kv == "paged":
            out.update(kv="paged", page_size=eng.page_size,
                       num_pages=eng.num_pages,
                       pages_in_use_peak=eng.allocator.peak_in_use,
                       kv_bytes_pool=eng.kv_bytes(),
                       kv_bytes_resident_peak=eng.kv_bytes_resident_peak,
                       prefix_hit_rate=round(eng.prefix_hit_rate, 3),
                       attn_cells_computed=eng.attn_cells_computed,
                       attn_cells_grid=eng.attn_cells_grid,
                       attn_plan=(eng.attn_plan.describe()
                                  if eng.attn_plan else None))
    else:
        out["decode_loop"] = "legacy" if args.legacy_loop else "device"
    stats = jax.local_devices()[0].memory_stats()
    out["peak_bytes_in_use"] = (stats or {}).get("peak_bytes_in_use")
    print(json.dumps(out))
    return out


def _run_frontend(args, kv: str) -> dict:
    """The --frontend mode: registry + bounded-queue server + open-loop
    replay, reporting the load-harness stats (goodput, TTFT, latency
    split) plus the registry capacity report."""
    from repro.frontend import (FIFOAdmission, FrontendServer,
                                ModelRegistry, ModelSpec, SLOAdmission,
                                replay, trace_requests)
    from repro.serve import load_trace, make_trace, poisson_arrivals

    names = [m.strip()
             for m in (args.frontend_models or args.arch).split(",")
             if m.strip()]
    reg = ModelRegistry()
    for name in names:
        reg.register(ModelSpec(
            name=name, arch=name, smoke=args.smoke, kind=kv,
            capacity=args.capacity, slots=args.slots or args.max_batch,
            chunk=args.chunk, page_size=args.page_size,
            num_pages=args.num_pages or None, seed=args.seed))

    if args.trace_file:
        trace = load_trace(args.trace_file)
    else:
        arrivals = poisson_arrivals(args.requests, args.arrival_rate,
                                    seed=args.seed)
        trace = make_trace(arrivals, [args.prompt_len], [args.max_new],
                           deadlines=[args.deadline_s or None])
    records = trace_requests(trace, reg, names, seed=args.seed)

    policy = (SLOAdmission(service_floor_s=args.service_floor_s)
              if args.admission == "slo" else FIFOAdmission())
    server = FrontendServer(reg, policy, queue_limit=args.queue_limit)
    report = replay(server, records)
    out = {"decode_loop": "frontend", "models": names,
           "admission": policy.name, "queue_limit": args.queue_limit,
           "kv": kv, **report,
           "capacity_report": reg.capacity_report()}
    print(json.dumps(out))
    return out


def pin_bf16_rounding() -> None:
    """Make XLA round bf16 values where the model says, in every program.

    By default XLA may keep bf16 intermediates in f32 inside a fusion
    ("excess precision"), so two programs that fuse differently (pallas
    against xla kernels, a sharded pool against one device) round
    differently, and their greedy tokens drift apart on the same
    weights.  Takes effect only before JAX starts its backend."""
    flag = "--xla_allow_excess_precision=false"
    flags = os.environ.get("XLA_FLAGS", "")
    if flag not in flags.split():
        os.environ["XLA_FLAGS"] = f"{flags} {flag}".strip()


if __name__ == "__main__":
    pin_bf16_rounding()
    main()
