"""Serving engine: batched generation, bucketing, packed-ternary serving,
engine output == manual prefill/decode loop."""
import dataclasses

import jax
import jax.numpy as jnp

from repro import configs
from repro.core.cim_linear import CIMConfig, hbm_bytes, ternarize_params
from repro.models import registry
from repro.serve import Request, ServeEngine, make_decode_step, \
    make_prefill_step


def _setup(arch="internlm2-1.8b", dtype=jnp.float32):
    cfg = dataclasses.replace(configs.smoke(arch), dtype=dtype)
    model = registry.build(cfg)
    params = model.init(jax.random.key(0))
    return cfg, model, params


import pytest


@pytest.mark.parametrize("on_device_loop", [True, False],
                         ids=["device-loop", "legacy-step-loop"])
def test_engine_generates_batch(on_device_loop):
    cfg, model, params = _setup()
    eng = ServeEngine(model, params, capacity=64, max_batch=4,
                      on_device_loop=on_device_loop)
    key = jax.random.key(1)
    for i in range(6):
        prompt = jax.random.randint(jax.random.fold_in(key, i), (8,), 0,
                                    cfg.vocab_size)
        eng.submit(Request(uid=i, prompt=prompt, max_new=5))
    done = eng.run()
    assert len(done) == 6
    assert all(len(r.out_tokens) == 5 for r in done)
    assert all(0 <= t < cfg.padded_vocab for r in done for t in r.out_tokens)


@pytest.mark.parametrize("on_device_loop", [True, False],
                         ids=["device-loop", "legacy-step-loop"])
def test_engine_matches_manual_loop(on_device_loop):
    cfg, model, params = _setup()
    prompt = jax.random.randint(jax.random.key(2), (8,), 0, cfg.vocab_size)

    eng = ServeEngine(model, params, capacity=64, max_batch=1,
                      on_device_loop=on_device_loop)
    eng.submit(Request(uid=0, prompt=prompt, max_new=4))
    got = eng.run()[0].out_tokens

    pre = make_prefill_step(model, 64)
    dec = make_decode_step(model)
    tok, state = pre(params, {"tokens": prompt[None]})
    want = [int(tok[0])]
    for _ in range(3):
        tok, state = dec(params, tok, state)
        want.append(int(tok[0]))
    assert got == want


def test_bucketing_by_prompt_length():
    cfg, model, params = _setup()
    eng = ServeEngine(model, params, capacity=64, max_batch=8)
    for i, ln in enumerate([8, 8, 16, 8, 16]):
        eng.submit(Request(uid=i, prompt=jnp.zeros((ln,), jnp.int32),
                           max_new=2))
    done = eng.run()
    assert len(done) == 5


def test_eos_stops_row():
    cfg, model, params = _setup()
    prompt = jnp.zeros((4,), jnp.int32)
    eng = ServeEngine(model, params, capacity=32, max_batch=1)
    # eos = whatever greedy produces first -> generation stops at 1 token
    pre = make_prefill_step(model, 32)
    tok, _ = pre(params, {"tokens": prompt[None]})
    eng.submit(Request(uid=0, prompt=prompt, max_new=8, eos_id=int(tok[0])))
    done = eng.run()
    assert len(done[0].out_tokens) == 1


def test_packed_ternary_serving_runs_and_shrinks_weights():
    cfg, model, params = _setup()
    raw = hbm_bytes(params)
    cim = CIMConfig(mode="ternary", packing="base3")
    packed = ternarize_params(params, cim)
    assert hbm_bytes(packed) < raw
    eng = ServeEngine(model, packed, capacity=32, max_batch=2, cim=cim)
    for i in range(2):
        eng.submit(Request(uid=i, prompt=jnp.arange(6, dtype=jnp.int32),
                           max_new=3))
    done = eng.run()
    assert len(done) == 2
    assert all(len(r.out_tokens) == 3 for r in done)


def test_packed_xla_backend_matches_pallas_interpret():
    cfg, model, params = _setup()
    cim_p = CIMConfig(mode="ternary", packing="base3")
    cim_x = CIMConfig(mode="ternary", packing="base3", backend="xla")
    packed = ternarize_params(params, cim_p)
    batch = {"tokens": jnp.arange(8, dtype=jnp.int32)[None]}
    lp, _ = model.prefill(packed, batch, 16, cim=cim_p)
    lx, _ = model.prefill(packed, batch, 16, cim=cim_x)
    assert jnp.allclose(lp.astype(jnp.float32), lx.astype(jnp.float32),
                        atol=1e-3, rtol=1e-3)


# ------------------------------------------------- latency percentiles

def test_latency_stats_interpolates_percentiles():
    """Linear interpolation between order statistics (ISSUE 5
    satellite): the old nearest-rank ``int(q*(n-1)+0.5)`` made every
    small-sample p99 degenerate to the max.  Pin exact values for known
    inputs."""
    from repro.serve import latency_stats, percentile

    def stats(vals):
        rs = [Request(uid=i, prompt=None) for i in range(len(vals))]
        for r, v in zip(rs, vals):
            r.latency_s = v
        return latency_stats(rs)

    s = stats([1.0, 2.0, 3.0, 4.0, 5.0])
    assert s["p50_s"] == 3.0
    assert s["p99_s"] == 4.96            # 4 + 0.96*(5-4), not the max
    assert s["mean_s"] == 3.0

    s = stats([0.0, 10.0])
    assert s["p50_s"] == 5.0             # interpolated midpoint
    assert s["p99_s"] == 9.9

    one = stats([7.0])
    assert (one["p50_s"], one["p99_s"], one["p999_s"], one["mean_s"]) \
        == (7.0, 7.0, 7.0, 7.0)
    assert one["queue_wait_mean_s"] == 0.0 and one["service_mean_s"] == 7.0
    empty = stats([])
    assert set(empty) == set(one) and set(empty.values()) == {0.0}

    lat = sorted([3.0, 1.0, 4.0, 1.5, 9.0, 2.6, 5.3, 5.8])
    assert percentile(lat, 0.0) == lat[0]
    assert percentile(lat, 1.0) == lat[-1]
    # monotone in q
    qs = [i / 20 for i in range(21)]
    vals = [percentile(lat, q) for q in qs]
    assert vals == sorted(vals)


# ------------------------------------------------- launcher entry point

@pytest.fixture
def cache_config(monkeypatch, tmp_path):
    """Point the launchers' default compile cache at a scratch dir and
    restore this process's cache settings afterwards."""
    from jax.experimental.compilation_cache import compilation_cache
    from repro.launch import compile_cache
    saved = (jax.config.jax_compilation_cache_dir,
             jax.config.jax_persistent_cache_min_compile_time_secs)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(compile_cache, "REPO_CACHE_DIR",
                        str(tmp_path / "jax_cache"))
    compilation_cache.reset_cache()
    yield tmp_path / "jax_cache"
    jax.config.update("jax_compilation_cache_dir", saved[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      saved[1])
    compilation_cache.reset_cache()


def test_serve_main_returns_printed_result(capsys, cache_config):
    """``launch.serve.main`` returns the dict it prints; its int8-domain
    tokens are identical on the pallas and xla backends, one transfer
    per chunk; the compile cache lands in the launcher's fixed dir."""
    import json
    from repro.launch import serve
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    argv = ["--arch", "internlm2-1.8b", "--smoke", "--packed", "trit2",
            "--domain", "int8", "--continuous", "--kv", "paged",
            "--requests", "3", "--prompt-len", "8", "--max-new", "5",
            "--slots", "2", "--chunk", "2", "--capacity", "16",
            "--page-size", "4"]
    out = serve.main(argv)
    assert json.loads(capsys.readouterr().out.splitlines()[-1]) == out
    ref = serve.main(argv + ["--backend", "xla"])
    assert out["plan"]["backend"] == "pallas"
    assert ref["plan"]["backend"] == "xla"
    assert out["tokens_digest"] == ref["tokens_digest"]
    assert out["generated_tokens"] == 3 * 5
    assert out["host_transfers"] == out["chunks"]
    assert out["attn_plan"] is None      # interpret-only platform: gather
    assert any(cache_config.iterdir())


def test_compile_cache_env_dir_wins(monkeypatch, tmp_path):
    """Where JAX_COMPILATION_CACHE_DIR is set, JAX reads it itself and
    the helper sets nothing."""
    from repro.launch import compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.configure_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
