"""Chip smoke test: drive packed-ternary serving of internlm2-1.8b on a TPU.

  python chip_smoke.py            # one chip: kernels, then paged serving
  python chip_smoke.py --chips 4  # four chips: the sharded slot pool only

One process does everything (a chip belongs to one process).  Each phase
prints one line of its own; the last line of standard output is one JSON
object, ``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count":
N}}``, printed only when every check passed on a TPU.  On any other
platform, or after a failed check, the script exits nonzero without it.
The phase functions default to the chip sizes; tests/test_chip_smoke.py
runs them on the CPU at tiny sizes (Pallas in interpret mode).

Phases (one chip):
  kernels  ternary_matmul (f32 and bf16 activations) / ternary_matmul_int8
           x base3 / trit2 at the internlm2 decode (M=8) and prefill
           (M=512) widths against the kernels/ref.py oracles, and the
           fused paged-attention read at the serving pool geometry
           against its gather oracle.  Float limits are derived from f32
           round-off; each is also applied to a control that rounds the
           operand a one-pass MXU dot would round to bf16, which must
           fail it;
  serve    ``repro.launch.serve.main`` at the full internlm2-1.8b config,
           int8 domain, continuous batching over the paged KV pool, once
           per packing on the pallas backend and once on the xla backend;
           the two must serve identical tokens.
Phase (``--chips 4``):
  sharded  the continuous Scheduler with its slot pool sharded over a
           ('data', 'model') mesh of the four chips, against the same
           requests served on one chip.  It serves the model's bf16
           weights: Mosaic refuses to partition a Pallas kernel that is
           not wrapped in a shard_map, so packed weights on a mesh fail
           to compile (a refusal, not a fallback).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH = "internlm2-1.8b"
# internlm2-1.8b widths: d_model 2048 (K) x d_ff 8192 (N); decode and
# prefill row counts
KERNEL_K, KERNEL_N, KERNEL_MS = 2048, 8192, (8, 512)
# serving pool geometry: slots, pages per slot, page size, KV heads,
# query heads per KV head, head dim
ATTN_GEOMETRY = (8, 16, 16, 8, 2, 128)
SERVE_ARGV = ["--arch", ARCH, "--domain", "int8", "--kv", "paged",
              "--requests", "8", "--prompt-len", "128",
              "--max-new", "32", "--slots", "8", "--chunk", "8",
              "--capacity", "256", "--page-size", "16"]
F32_U = 2.0 ** -24      # unit round-off of f32
SIGMAS = 4.0            # rounding-error standard deviations allowed
TINY = 1e-30            # keeps err / limit finite where both are 0


def _f32_sum_bound(n, mag):
    """Limit on |kernel - oracle| for two f32 sums (or dot products) of
    ``n`` terms whose absolute values sum to ``mag``.  Rounding errors of
    a sum add like a random walk, so each result lies within about
    sqrt(n)*u*mag of the exact one; allow SIGMAS of those on each side.
    An f32 operand rounded to bf16 (u = 2^-9) errs far more, which the
    controls show."""
    return 2 * SIGMAS * F32_U * mag * n ** 0.5


def _ratio(err, limit) -> float:
    """Largest err / limit: at most 1 where every element is in bounds."""
    import jax.numpy as jnp
    return float(jnp.max(err / (limit + TINY)))


def _verdict(ok: bool) -> str:
    return "ok" if ok else "FAIL"


def _matmul_checks(x, pw, wdec, mode: str, interpret: bool) -> dict:
    """err/limit of the float kernel on ``x`` (and, for f32 ``x``, of the
    one-bf16-pass control) against the f32 oracle."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ref
    from repro.kernels import ternary_matmul as tm

    y = tm.ternary_matmul(x, pw.data, pw.scale, mode=mode,
                          interpret=interpret)
    with jax.default_matmul_precision("highest"):
        y_ref = ref.ternary_matmul_ref(x, pw.data, pw.scale, mode)
        mag = (jnp.abs(x.astype(jnp.float32)) @ jnp.abs(wdec)
               ) * jnp.abs(pw.scale)
        # the final scale product rounds once on each side
        limit = _f32_sum_bound(x.shape[1], mag) + 4 * F32_U * jnp.abs(y_ref)
        out = {"kernel": _ratio(jnp.abs(y - y_ref), limit)}
        if x.dtype == jnp.float32:
            y_ctl = ref.ternary_matmul_ref(x.astype(jnp.bfloat16), pw.data,
                                           pw.scale, mode)
            out["control"] = _ratio(jnp.abs(y_ctl - y_ref), limit)
    return out


def _attn_out_one_pass(q, kv):
    """Attention output with the softmax weights rounded to bf16 before
    the p@v dot, as one MXU pass of an f32 dot rounds them: the control
    the attention limit must reject."""
    import jax.numpy as jnp
    from repro.kernels import paged_attention as pa

    s, w_pages = kv.page_table.shape
    _, ps, kvh, hd = kv.k_pages.shape
    kg = kv.k_pages[kv.page_table].reshape(s, w_pages * ps, kvh, hd)
    vg = kv.v_pages[kv.page_table].reshape(s, w_pages * ps, kvh, hd)
    sc = jnp.einsum("skrd,stkd->skrt", q.astype(jnp.float32),
                    kg.astype(jnp.float32))
    valid = jnp.arange(w_pages * ps)[None, :] < kv.pos[:, None]
    sc = jnp.where(valid[:, None, None, :], sc, pa.NEG_INF)
    p = jnp.exp(sc - sc.max(axis=-1, keepdims=True))
    acc = jnp.einsum("skrt,stkd->skrd",
                     p.astype(jnp.bfloat16).astype(jnp.float32),
                     vg.astype(jnp.float32))
    return acc / p.sum(axis=-1)[..., None]


def _attention_checks(q, kv, interpret: bool) -> dict:
    """err/limit of the fused paged read's m, l and output against the
    gather oracle, and of the one-bf16-pass control's output."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import paged_attention as pa

    acc, m, l = pa.paged_attention(q, kv, interpret=interpret)
    out = acc / l[..., None]
    hd = q.shape[-1]
    with jax.default_matmul_precision("highest"):
        acc_r, m_r, l_r = pa.paged_attention_ref(q, kv)
        out_r = acc_r / l_r[..., None]
        # largest sum of |q||k| over the live positions, and sum of p|v|
        _, mag_s, _ = pa.paged_attention_ref(
            jnp.abs(q), kv._replace(k_pages=jnp.abs(kv.k_pages)))
        acc_abs, _, _ = pa.paged_attention_ref(
            q, kv._replace(v_pages=jnp.abs(kv.v_pages)))
        out_ctl = _attn_out_one_pass(q, kv)
    # a score (and so m) errs by at most e_s; each softmax weight
    # exp(s - m) then by 2*e_s relative plus its own rounding, and the
    # sums over `pos` live positions add their random-walk round-off
    e_s = _f32_sum_bound(hd, mag_s)
    npos = kv.pos.astype(jnp.float32)[:, None, None]
    rel = 2 * e_s + _f32_sum_bound(npos, 1.0) + 4 * F32_U
    # acc and l each err by rel relative: out by twice that of sum p|v| / l
    out_limit = 2 * rel[..., None] * acc_abs / l_r[..., None]
    return {"m": _ratio(jnp.abs(m - m_r), e_s),
            "l": _ratio(jnp.abs(l - l_r) / l_r, rel),
            "out": _ratio(jnp.abs(out - out_r), out_limit),
            "out_control": _ratio(jnp.abs(out_ctl - out_r), out_limit)}


def kernel_phase(k: int = KERNEL_K, n: int = KERNEL_N, ms=KERNEL_MS,
                 attn=ATTN_GEOMETRY) -> list:
    """Run each kernel against its oracle; returns failures."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.packing import unpack_base3, unpack_trits2
    from repro.kernels import ops, ref
    from repro.kernels import paged_attention as pa
    from repro.kernels import ternary_matmul as tm
    from repro.kernels.plan import default_interpret

    interpret = default_interpret()
    failures = []
    key = jax.random.key(0)
    w = 0.02 * jax.random.normal(jax.random.fold_in(key, 0), (k, n))
    for mode in ("base3", "trit2"):
        pw = ops.pack_weights(w, mode)
        wdec = (unpack_base3(pw.data) if mode == "base3"
                else unpack_trits2(pw.data)).astype(jnp.float32)
        for m in ms:
            x = jax.random.normal(jax.random.fold_in(key, m), (m, k))
            f32 = _matmul_checks(x, pw, wdec, mode, interpret)
            bf16 = _matmul_checks(x.astype(jnp.bfloat16), pw, wdec, mode,
                                  interpret)
            xi, xs = ops.quantize_acts_int8(x)
            yi = np.asarray(tm.ternary_matmul_int8(
                xi, xs, pw.data, pw.scale, mode=mode, interpret=interpret))
            yi_ref = np.asarray(ref.ternary_matmul_int8_ref(
                xi, xs, pw.data, pw.scale, mode))
            checks = {"f32": f32["kernel"] <= 1,
                      "f32_control_rejected": f32["control"] > 1,
                      "bf16": bf16["kernel"] <= 1,
                      "int8": bool(np.array_equal(yi, yi_ref))}
            print(f"kernels: ternary_matmul {mode} M={m} K={k} N={n}: "
                  f"err/limit f32 x {f32['kernel']:.3e} "
                  f"(one-bf16-pass control {f32['control']:.3e}), "
                  f"bf16 x {bf16['kernel']:.3e}; int8 bitwise "
                  f"{_verdict(checks['int8'])} "
                  + _verdict(all(checks.values())), flush=True)
            failures += [f"ternary_matmul {mode} M={m}: {c}"
                         for c, good in checks.items() if not good]

    s, w_pages, ps, kvh, rep, hd = attn
    q = (jax.random.normal(jax.random.fold_in(key, 1), (s, kvh, rep, hd))
         / np.sqrt(hd)).astype(jnp.bfloat16)
    pool = (1 + s * w_pages, ps, kvh, hd)
    kv = pa.PagedAttentionKV(
        jax.random.normal(jax.random.fold_in(key, 2), pool, jnp.bfloat16),
        jax.random.normal(jax.random.fold_in(key, 3), pool, jnp.bfloat16),
        jnp.arange(1, 1 + s * w_pages, dtype=jnp.int32).reshape(s, w_pages),
        # live lengths: full, partly filled, page-unaligned, empty-ish
        jnp.asarray([(i * 37) % (w_pages * ps) + 1 for i in range(s)],
                    jnp.int32))
    ratios = _attention_checks(q, kv, interpret)
    checks = {"m": ratios["m"] <= 1, "l": ratios["l"] <= 1,
              "out": ratios["out"] <= 1,
              "out_control_rejected": ratios["out_control"] > 1}
    print(f"kernels: paged_attention slots={s} pages={w_pages}x{ps} "
          f"kv={kvh} rep={rep} hd={hd}: err/limit "
          + " ".join(f"{k_}={v:.3e}" for k_, v in ratios.items())
          + " " + _verdict(all(checks.values())), flush=True)
    failures += [f"paged_attention: {c}"
                 for c, good in checks.items() if not good]
    return failures


def serve_phase(base_argv=SERVE_ARGV) -> list:
    """Serve the same requests with each packing on the pallas and the
    xla backend; returns failures."""
    from repro.launch import serve

    def flag(name):
        return int(base_argv[base_argv.index(name) + 1])

    failures = []
    want_tokens = flag("--requests") * flag("--max-new")
    for packing in ("base3", "trit2"):
        out = serve.main(base_argv + ["--packed", packing])
        ref = serve.main(base_argv + ["--packed", packing,
                                      "--backend", "xla"])
        plan, attn = out["plan"], out["attn_plan"]
        checks = {
            "pallas": plan["backend"] == "pallas" and not plan["interpret"],
            "fused_read": (attn is not None
                           and attn["backend"] == "paged_attn"
                           and not attn["interpret"]),
            "tokens": out["generated_tokens"] == want_tokens,
            "transfers": out["host_transfers"] == out["chunks"],
            "xla_backend": ref["plan"]["backend"] == "xla",
            "tokens_eq_xla": out["tokens_digest"] == ref["tokens_digest"],
        }
        bad = [c for c, good in checks.items() if not good]
        print(f"serve: {ARCH} {packing} int8 plan={json.dumps(plan)} "
              f"attn_plan={json.dumps(attn)} "
              f"generated_tokens={out['generated_tokens']}/{want_tokens} "
              f"host_transfers={out['host_transfers']} "
              f"chunks={out['chunks']} "
              f"tokens_digest={out['tokens_digest'][:16]} "
              f"xla_digest={ref['tokens_digest'][:16]} "
              f"peak_bytes_in_use={out['peak_bytes_in_use']} "
              f"setup_s={out['setup_s']} wall_s={out['wall_s']} "
              f"xla_setup_s={ref['setup_s']} xla_wall_s={ref['wall_s']} "
              + ("ok" if not bad else "FAIL " + ",".join(bad)), flush=True)
        failures += [f"serve {packing}: {c}" for c in bad]
    return failures


def sharded_phase(chips: int, arch_cfg=None, slots: int = 8,
                  prompt_len: int = 128, max_new: int = 32) -> list:
    """The continuous Scheduler with its slot pool sharded over a
    ('data', 'model') = (chips, 1) mesh, against the same requests on
    one chip, both with the model's own (bf16) weights; returns
    failures."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro import configs
    from repro.dist import mesh as mesh_lib, sharding as shd
    from repro.models import registry
    from repro.serve import Request, Scheduler

    cfg = arch_cfg or configs.get(ARCH)
    model = registry.build(cfg)
    params = model.init(jax.random.key(0))
    key = jax.random.key(1)

    def serve(params, spmd_axes, rules=None, mesh=None):
        shd.set_activation_context(rules, mesh)
        try:
            sch = Scheduler(model, params, capacity=prompt_len + max_new,
                            slots=slots, chunk=8, spmd_axes=spmd_axes)
            for i in range(slots):
                sch.submit(Request(uid=i, max_new=max_new,
                                   prompt=jax.random.randint(
                                       jax.random.fold_in(key, i),
                                       (prompt_len,), 0, cfg.vocab_size)))
            t0 = time.monotonic()
            done = sch.run()
            dt = time.monotonic() - t0
        finally:
            shd.set_activation_context(None, None)
        return {r.uid: r.out_tokens for r in done}, sch, dt

    one_chip, _, dt_one = serve(params, None)
    mesh = mesh_lib.make_mesh(mesh_lib.MeshSpec((chips, 1),
                                                ("data", "model")))
    rules = shd.rules_for(cfg, "serve")
    axes = shd.slot_spmd_axes(rules, mesh, slots)
    params_mesh = jax.device_put(params, NamedSharding(mesh, P()))
    got, sch, dt_mesh = serve(params_mesh, axes, rules, mesh)
    # every pool leaf must hold slots/chips slots on each of the chips
    spread = all(
        len(leaf.sharding.device_set) == chips
        and all(s.data.shape[0] == slots // chips
                for s in leaf.addressable_shards)
        for leaf in jax.tree.leaves(sch.pool))
    used = [d.memory_stats()["bytes_in_use"] if d.memory_stats() else None
            for d in mesh.devices.flat]
    checks = {"spmd_axes": axes == "data",
              "pool_spread": spread,
              "tokens_eq_one_chip": got == one_chip,
              "tokens": sum(map(len, got.values())) == slots * max_new,
              "transfers": sch.host_transfers == sch.chunks_run}
    bad = [c for c, good in checks.items() if not good]
    print(f"sharded: {cfg.name} bf16 weights mesh=(data={chips}, model=1) "
          f"spmd_axes={axes!r} pool_spread={spread} "
          f"bytes_in_use_per_chip={used} "
          f"generated_tokens={sum(map(len, got.values()))} "
          f"host_transfers={sch.host_transfers} chunks={sch.chunks_run} "
          f"tokens_eq_one_chip={got == one_chip} "
          f"run_s_one_chip={dt_one:.1f} run_s_mesh={dt_mesh:.1f} "
          + ("ok" if not bad else "FAIL " + ",".join(bad)), flush=True)
    return [f"sharded: {c}" for c in bad]


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--chips", type=int, default=1, choices=(1, 4),
                   help="4: run only the sharded slot-pool phase")
    args = p.parse_args(argv)

    # the serving launcher's numerics, set before JAX starts its backend
    from repro.launch.serve import pin_bf16_rounding
    pin_bf16_rounding()
    from repro.launch.compile_cache import configure_compile_cache
    cache_dir = configure_compile_cache()
    import jax
    from jax import monitoring

    cache_events = {"cache_hits": 0, "cache_misses": 0}

    def count(event, **_):
        name = event.rsplit("/", 1)[-1]
        if event.startswith("/jax/compilation_cache/") \
                and name in cache_events:
            cache_events[name] += 1
    monitoring.register_event_listener(count)

    devices = jax.devices()
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)} compile_cache={cache_dir}", flush=True)
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform!r})",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1

    phases = ([("sharded", lambda: sharded_phase(args.chips))]
              if args.chips > 1 else
              [("kernels", kernel_phase), ("serve", serve_phase)])
    failures = []
    for name, run in phases:
        t0 = time.monotonic()
        try:
            failures += run()
        except Exception:          # report the phase, run the next one
            traceback.print_exc()
            failures.append(f"{name}: raised")
        print(f"phase {name}: {time.monotonic() - t0:.1f}s", flush=True)
    print(f"compile_cache: dir={cache_dir} hits={cache_events['cache_hits']}"
          f" misses={cache_events['cache_misses']}", flush=True)
    if failures:
        print("chip_smoke: FAILED " + "; ".join(failures), file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
