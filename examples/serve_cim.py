"""End-to-end driver — serve a model with batched requests from
packed-ternary weights (the paper is an inference accelerator: weight
storage density + ternary MACs; this is its system-level image).

    PYTHONPATH=src python examples/serve_cim.py [--arch internlm2-1.8b]

Flow: init model -> quantize every matmul weight to the paper's 5-trit
base3 format (2x denser than bf16; trit2 is 8x) -> submit a batch of
requests -> continuous greedy decoding -> report density + throughput.
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro import configs
from repro.core.cim_linear import CIMConfig, hbm_bytes, ternarize_params
from repro.models import registry
from repro.serve import Request, ServeEngine


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="internlm2-1.8b")
    p.add_argument("--packing", default="base3", choices=("base3", "trit2"))
    p.add_argument("--requests", type=int, default=8)
    p.add_argument("--max-new", type=int, default=12)
    args = p.parse_args()

    cfg = configs.smoke(args.arch)      # reduced config: CPU-runnable
    model = registry.build(cfg)
    params = model.init(jax.random.key(0))
    float_bytes = hbm_bytes(params)

    cim = CIMConfig(mode="ternary", packing=args.packing)
    packed = ternarize_params(params, cim)
    print(f"{cfg.name}: weights {float_bytes/1e6:.2f} MB float -> "
          f"{hbm_bytes(packed)/1e6:.2f} MB {args.packing} "
          f"(matmul weights at "
          f"{'1 byte / 5-trit weight' if args.packing == 'base3' else '2 bits/trit'})")

    extra = {}
    if cfg.family == "audio":
        extra["frames"] = lambda b: jnp.zeros((b, cfg.encoder_seq,
                                               cfg.d_model), cfg.dtype)
    if cfg.family == "vlm":
        extra["patches"] = lambda b: jnp.zeros((b, cfg.encoder_seq,
                                                cfg.d_model), cfg.dtype)
    eng = ServeEngine(model, packed, capacity=128, max_batch=4, cim=cim,
                      extra_inputs=extra)
    key = jax.random.key(7)
    for i in range(args.requests):
        prompt = jax.random.randint(jax.random.fold_in(key, i), (24,), 0,
                                    cfg.vocab_size)
        eng.submit(Request(uid=i, prompt=prompt, max_new=args.max_new))

    t0 = time.monotonic()
    done = eng.run()
    dt = time.monotonic() - t0
    print(f"served {len(done)} requests, {eng.generated_tokens} tokens in "
          f"{dt:.1f}s ({eng.generated_tokens/dt:.1f} tok/s on "
          f"{jax.devices()[0].device_kind}, backend {eng.cim.backend}, "
          f"interpret={eng.cim.interpret})")
    print("sample output tokens:", done[0].out_tokens)


if __name__ == "__main__":
    main()
