"""End-to-end system test: train a smoke model on the synthetic chain task
through the Trainer (checkpointing on), then serve it packed-ternary —
the full paper pipeline (train -> quantize-to-trits -> CIM-serve)."""
import jax
import jax.numpy as jnp

from repro import configs
from repro.core.cim_linear import CIMConfig, ternarize_params
from repro.data import DataConfig, lm_batch
from repro.models import registry
from repro.optim import adamw
from repro.serve import PagedScheduler, Request
from repro.train import Trainer, TrainerConfig


def test_train_then_cim_serve(tmp_path):
    cfg = configs.smoke("internlm2-1.8b")
    model = registry.build(cfg)
    data = DataConfig(vocab_size=cfg.vocab_size, seq_len=64, global_batch=8,
                      seed=11)
    tc = TrainerConfig(total_steps=30, ckpt_dir=str(tmp_path),
                       ckpt_interval=10, seed=11)
    tr = Trainer(model, adamw(3e-3), data, tc)
    state = tr.run()

    losses = [h["loss"] for h in tr.history]
    assert losses[-1] < losses[0] - 0.3, losses[::6]

    # quantize the trained weights to the paper's 5-trit format and serve
    cim = CIMConfig(mode="ternary", packing="base3")
    packed = ternarize_params(state.params, cim)
    eng = PagedScheduler(model, packed, capacity=96, slots=4, cim=cim)
    prompts = lm_batch(data, jnp.asarray(999))["tokens"][:4, :32]
    for i in range(4):
        eng.submit(Request(uid=i, prompt=prompts[i], max_new=4))
    done = eng.run()
    assert len(done) == 4
    assert all(len(r.out_tokens) == 4 for r in done)
