"""The plain serving reference the scheduler tests compare against.

One request at a time, batch 1: ``make_prefill_step`` then
``make_decode_step`` until the request's budget or its EOS token, with
each family's extra inputs given as the launcher gives them.  A
scheduler's tokens must equal these bitwise: a slot's computation is
exactly the batch-1 decode of its request.
"""
import jax.numpy as jnp

from repro.launch.serve import extra_inputs
from repro.serve import make_decode_step, make_prefill_step


def reference_tokens(model, params, reqs, capacity: int, cim=None) -> dict:
    """``{uid: tokens}`` of each request in ``reqs`` (read, not mutated),
    prefilled into a ``capacity``-position cache.  ``cim`` is resolved
    per phase as the schedulers resolve it."""
    cim_decode = cim_prefill = None
    if cim is not None:
        cim_decode, cim_prefill = cim.resolve(), cim.resolve(phase="prefill")
    prefill = make_prefill_step(model, capacity, cim_prefill)
    decode = make_decode_step(model, cim_decode)
    extra = extra_inputs(model.cfg)
    out = {}
    for r in reqs:
        batch = {"tokens": jnp.asarray(r.prompt, jnp.int32)[None],
                 **{k: fn(1) for k, fn in extra.items()}}
        tok, state = prefill(params, batch)
        toks = [int(tok[0])]
        while len(toks) < r.max_new and toks[-1] != r.eos_id:
            tok, state = decode(params, tok, state)
            toks.append(int(tok[0]))
        out[r.uid] = toks
    return out
