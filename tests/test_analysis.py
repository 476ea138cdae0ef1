"""The analysis gate gates (ISSUE 6 acceptance):

  * every pass runs clean on the repo as merged — no baseline file of
    pre-existing violations;
  * a seeded violation in each category (capability drift, block/
    index-map violation, extra transfer / retrace, lint rule) is
    caught, and the CLI exits nonzero on it;
  * ``analysis.sanitize()`` enforces the serve transfer/retrace
    contract around ``Scheduler``/``PagedScheduler``: exactly one
    device->host transfer per chunk, zero retraces after warmup;
  * lint rules RA000-RA005 fire (and suppress) on the exact shapes
    they document (RA005 only inside ``src/repro/frontend/``);
  * the ``frontend`` pass catches each seeded violation (extra
    transfer, dropped accounting, perturbed admission order);
  * kernel-registry mutation edges: ``override=True`` replacement,
    unknown unregister, and plan-cache invalidation (stale plans must
    not resolve to — or execute on — an unregistered backend).
"""
import dataclasses
import json
import textwrap

import jax
import jax.numpy as jnp
import pytest

from repro import configs
from repro.analysis import (Finding, SanitizeError, sanitize, abscache,
                            blockmap, capability, jaxpr_audit, lint,
                            sanitizer, shardspec)
from repro.analysis.__main__ import main as cli_main
from repro.kernels import plan as plan_mod
from repro.kernels.plan import (execute, get_backend, plan_matmul,
                                register_backend, resolve_backend,
                                unregister_backend)
from repro.models import registry
from repro.serve import PagedScheduler, Request, Scheduler

jax.config.update("jax_platform_name", "cpu")


# ------------------------------------------------- clean gate

def test_capability_pass_clean():
    assert capability.run() == []


def test_blockmap_pass_clean():
    assert blockmap.run() == []


def test_lint_pass_clean():
    """src/ + benchmarks/ as merged carry zero lint findings — the
    gate landed with its findings fixed, not baselined."""
    assert lint.run() == []


# ------------------------------------------------- capability drift

def test_capability_matrix_round_trips():
    reg = capability._registry()
    parsed = capability.parse_capability_matrix(
        capability.render_capability_matrix())
    assert set(parsed) == set(reg)


def test_capability_readme_drift_is_flagged(tmp_path):
    text = capability.render_capability_matrix()
    doctored = text.replace("cpu, gpu, tpu", "cpu", 1)
    assert doctored != text
    readme = tmp_path / "README.md"
    readme.write_text("# doctored\n\n" + doctored)
    findings = capability._check_readme_matrix(capability._registry(),
                                               str(readme))
    assert findings and all(f.rule == "CAP006" for f in findings)


def test_capability_readme_missing_backend_is_flagged(tmp_path):
    text = capability.render_capability_matrix()
    kept = [ln for ln in text.splitlines() if "`ref`" not in ln]
    readme = tmp_path / "README.md"
    readme.write_text("# doctored\n\n" + "\n".join(kept) + "\n")
    findings = capability._check_readme_matrix(capability._registry(),
                                               str(readme))
    assert any("ref" in f.message for f in findings)


def test_cli_capability_drift_exits_nonzero(tmp_path):
    readme = tmp_path / "README.md"
    readme.write_text("# no matrix here\n")
    assert cli_main(["--passes", "capability",
                     "--readme", str(readme)]) != 0


# ------------------------------------------------- blockmap violations

def test_blockmap_pinned_misaligned_blocks_flagged():
    findings = blockmap.run(pin_blocks=(100, 100, 100))
    rules = {f.rule for f in findings}
    assert "BM001" in rules            # 100 breaks sublane/lane multiples


def test_cli_blockmap_pinned_exits_nonzero():
    assert cli_main(["--passes", "blockmap",
                     "--pin-blocks", "100,100,100"]) != 0


def test_blockmap_live_selector_cells_clean():
    assert blockmap.check_ternary_cell(333, 77, 129, "trit2", "float") == []
    assert blockmap.check_cim_cell(16, 256, 256) == []


# ------------------------------------------------- sanitize: unit

def test_sanitize_counts_transfers_and_restores():
    orig = jax.device_get
    with sanitize() as rep:
        jax.device_get(jnp.ones((3,)))
        jax.device_get((jnp.ones((2,)), jnp.zeros((2,))))
    assert rep.transfers == 2
    assert jax.device_get is orig      # wrapper uninstalled on exit


def test_sanitize_transfer_budget_enforced():
    with pytest.raises(SanitizeError, match="budget is 0"):
        with sanitize(max_transfers=0):
            jax.device_get(jnp.ones((3,)))


def test_sanitize_counts_compiles():
    with sanitize() as rep:
        jax.jit(lambda x: x * 3 + 1)(jnp.ones((4,)))   # fresh jit: compiles
    assert rep.compiles >= 1
    with pytest.raises(SanitizeError, match="retrace"):
        with sanitize(max_compiles=0):
            jax.jit(lambda x: x * 5 - 2)(jnp.ones((4,)))


def test_sanitize_clean_region_counts_nothing():
    f = jax.jit(lambda x: x + 2)
    f(jnp.ones((4,)))                  # warmup outside the region
    with sanitize(max_transfers=0, max_compiles=0) as rep:
        f(jnp.ones((4,)))              # cached: no compile, no transfer
    assert rep.transfers == 0 and rep.compiles == 0


# ------------------------------------------------- sanitize: serve

def _smoke_scheduler(kind):
    cfg = dataclasses.replace(configs.smoke("internlm2-1.8b"),
                              dtype=jnp.float32)
    model = registry.build(cfg)
    params = model.init(jax.random.key(0))
    if kind == "paged":
        sched = PagedScheduler(model, params, capacity=64, slots=2,
                               chunk=4, page_size=16)
    else:
        sched = Scheduler(model, params, capacity=64, slots=2, chunk=4)
    return cfg, sched


def _reqs(cfg, uids):
    key = jax.random.key(0)
    return [Request(uid=u,
                    prompt=jax.random.randint(jax.random.fold_in(key, u),
                                              (8,), 0, cfg.vocab_size),
                    max_new=6) for u in uids]


@pytest.mark.parametrize("kind", ["dense", "paged"])
def test_scheduler_one_transfer_per_chunk_zero_retrace(kind):
    """PR 3/5's accounting claims as enforced invariants: the measured
    region performs exactly chunks_run device->host transfers (the
    engine's own counter agrees) and compiles nothing after warmup."""
    cfg, sched = _smoke_scheduler(kind)
    for r in _reqs(cfg, range(3)):     # warmup at the same shapes
        sched.submit(r)
    sched.run()
    chunks0, transfers0 = sched.chunks_run, sched.host_transfers
    with sanitize() as rep:
        for r in _reqs(cfg, range(10, 13)):
            sched.submit(r)
        sched.run()
    chunks = sched.chunks_run - chunks0
    assert chunks > 0
    assert rep.transfers == chunks
    assert sched.host_transfers - transfers0 == chunks
    assert rep.compiles == 0


def test_sanitize_pass_catches_injected_violations():
    findings = sanitizer._check_scheduler(
        lambda model, params: Scheduler(model, params, capacity=64,
                                        slots=2, chunk=4),
        "dense", inject=("transfer", "retrace"))
    rules = {f.rule for f in findings}
    assert "SAN001" in rules           # the extra device_get
    assert "SAN002" in rules           # the mid-region fresh jit


def test_cli_sanitize_injection_exits_nonzero():
    assert cli_main(["--passes", "sanitize",
                     "--inject-sanitize", "retrace"]) != 0


# ------------------------------------------------- lint rules

def _lint(tmp_path, source, rel_path="src/x.py"):
    p = tmp_path / "x.py"
    p.write_text(textwrap.dedent(source))
    return lint.check_file(str(p), rel_path=rel_path)


def test_ra001_bare_except(tmp_path):
    fs = _lint(tmp_path, """\
        try:
            pass
        except:
            pass
        """)
    assert [f.rule for f in fs] == ["RA001"]


def test_ra001_blind_except_exception(tmp_path):
    fs = _lint(tmp_path, """\
        try:
            pass
        except Exception:
            pass
        """)
    assert [f.rule for f in fs] == ["RA001"]


def test_ra001_bound_but_unused(tmp_path):
    fs = _lint(tmp_path, """\
        try:
            pass
        except Exception as e:
            pass
        """)
    assert [f.rule for f in fs] == ["RA001"]
    assert "never uses it" in fs[0].message


def test_ra001_clean_variants(tmp_path):
    fs = _lint(tmp_path, """\
        try:
            pass
        except ValueError:
            pass
        try:
            pass
        except Exception:
            raise
        try:
            pass
        except Exception as e:
            print(e)
        """)
    assert fs == []


def test_ra002_device_get_outside_chokepoint(tmp_path):
    fs = _lint(tmp_path, """\
        import jax
        def f(x):
            return jax.device_get(x)
        """)
    assert [f.rule for f in fs] == ["RA002"]


def test_ra002_chokepoint_and_suppression_clean(tmp_path):
    fs = _lint(tmp_path, """\
        import jax
        def _device_get(x):
            return jax.device_get(x)
        def g(x):
            return jax.device_get(x)   # lint: allow RA002 (test fixture)
        """)
    assert fs == []


def test_ra002_from_import(tmp_path):
    fs = _lint(tmp_path, "from jax import device_get\n")
    assert [f.rule for f in fs] == ["RA002"]


def test_ra003_routing_kwargs(tmp_path):
    src = """\
        from repro.kernels import ops
        def f(x, w):
            return ops.ternary_matmul(x, w, backend="xla", bm=128)
        """
    fs = _lint(tmp_path, src, rel_path="src/repro/serve/x.py")
    assert [f.rule for f in fs] == ["RA003"]
    # the kernels package itself is the one layer allowed kwargs
    assert _lint(tmp_path, src, rel_path="src/repro/kernels/x.py") == []


def test_ra004_unseeded_rng_benchmarks_only(tmp_path):
    src = """\
        import numpy as np
        import random
        def f():
            a = np.random.randn(3)
            b = random.random()
            rng = np.random.default_rng()
            ok = np.random.default_rng(0)
            return a, b, rng, ok
        """
    fs = _lint(tmp_path, src, rel_path="benchmarks/x.py")
    assert [f.rule for f in fs] == ["RA004"] * 3
    assert _lint(tmp_path, src, rel_path="src/x.py") == []


def test_ra004_jax_key_seed_derivation(tmp_path):
    src = """\
        import jax
        from benchmarks.common import stable_seed
        def f(n):
            bad = jax.random.key(100 + n)
            bad2 = jax.random.PRNGKey(hash("x"))
            ok = jax.random.key(42)
            ok2 = jax.random.key(stable_seed("sweep", n))
            return bad, bad2, ok, ok2
        """
    fs = _lint(tmp_path, src, rel_path="benchmarks/x.py")
    assert [f.rule for f in fs] == ["RA004"] * 2
    assert all("stable_seed" in f.message for f in fs)
    # seed hygiene is a benchmarks-only contract
    assert _lint(tmp_path, src, rel_path="src/x.py") == []


def test_ra000_malformed_suppression(tmp_path):
    fs = _lint(tmp_path, "x = 1   # lint: allow everything\n")
    assert [f.rule for f in fs] == ["RA000"]


def test_suppression_in_string_literal_is_not_parsed(tmp_path):
    fs = _lint(tmp_path, "doc = 'use # lint: allow RAxxx (reason)'\n")
    assert fs == []


def test_cli_lint_violation_exits_nonzero(tmp_path):
    (tmp_path / "bad.py").write_text(
        "try:\n    pass\nexcept Exception:\n    pass\n")
    assert cli_main(["--passes", "lint",
                     "--lint-paths", str(tmp_path)]) != 0


# ------------------------------------------------- RA005: frontend purity

FRONTEND_REL = "src/repro/frontend/x.py"


def test_ra005_device_get_banned_even_in_chokepoint(tmp_path):
    src = """\
        import jax
        def _device_get(x):
            return jax.device_get(x)
        """
    fs = _lint(tmp_path, src, rel_path=FRONTEND_REL)
    assert [f.rule for f in fs] == ["RA005"]
    assert "per-chunk payload" in fs[0].message
    # outside the frontend the audited chokepoint idiom stays legal
    assert _lint(tmp_path, src, rel_path="src/repro/serve/x.py") == []


def test_ra005_from_import_device_get(tmp_path):
    fs = _lint(tmp_path, "from jax import device_get\n",
               rel_path=FRONTEND_REL)
    assert [f.rule for f in fs] == ["RA005"]


def test_ra005_wallclock_calls_vs_injectable_default(tmp_path):
    src = """\
        import time
        def bad():
            return time.monotonic()
        def worse():
            return time.perf_counter_ns()
        def ok(clock=time.monotonic):
            return clock()
        """
    fs = _lint(tmp_path, src, rel_path=FRONTEND_REL)
    assert [f.rule for f in fs] == ["RA005"] * 2
    assert all("inject" in f.message for f in fs)
    # wall-clock hygiene is a frontend-only contract
    assert _lint(tmp_path, src, rel_path="src/repro/serve/x.py") == []


def test_ra005_rng_and_unbounded_deque(tmp_path):
    src = """\
        import random
        from collections import deque
        import numpy as np
        def f():
            a = np.random.randn(3)
            b = random.random()
            c = np.random.default_rng()
            q = deque()
            ok = np.random.default_rng(0)
            ok2 = deque(maxlen=8)
            ok3 = deque([1, 2], 2)
            return a, b, c, q, ok, ok2, ok3
        """
    fs = _lint(tmp_path, src, rel_path=FRONTEND_REL)
    assert [f.rule for f in fs] == ["RA005"] * 4
    assert _lint(tmp_path, src, rel_path="src/repro/serve/x.py") == []


# ------------------------------------------------- frontend pass

def test_frontend_pass_clean():
    from repro.analysis import frontend
    assert frontend.run() == []


def test_frontend_pass_catches_injected_transfer():
    from repro.analysis import frontend
    fs = frontend._check_streaming(inject=("transfer",))
    assert fs and all(f.rule == "FE001" for f in fs)


def test_frontend_pass_catches_dropped_accounting():
    from repro.analysis import frontend
    fs = frontend._check_backpressure(inject=("drop",))
    assert fs and all(f.rule == "FE002" for f in fs)
    assert any("silently dropped" in f.message for f in fs)


def test_frontend_pass_catches_perturbed_admission_order():
    from repro.analysis import frontend
    fs = frontend._check_determinism(inject=("order",))
    assert any(f.rule == "FE003" and "diverge" in f.message for f in fs)


def test_cli_frontend_injection_exits_nonzero():
    assert cli_main(["--passes", "frontend",
                     "--inject-frontend", "drop"]) != 0


# ------------------------------------------------- lint config hygiene

def test_repo_rules_toml_is_valid_and_wildcard_free():
    findings = []
    cfg = lint.load_config(lint.CONFIG_PATH, findings)
    assert findings == []              # every entry has rule + reason
    assert all(lint._RULE_ID_RE.match(rule)
               for rule, _, _ in cfg["suppress"])


def test_config_rejects_wildcards_and_empty_reasons(tmp_path):
    bad = tmp_path / "rules.toml"
    bad.write_text(textwrap.dedent("""\
        [[suppress]]
        rule = "*"
        path = "src"
        reason = "everything"

        [[suppress]]
        rule = "RA001"
        path = "src"
        reason = ""
        """))
    findings = []
    cfg = lint.load_config(str(bad), findings)
    assert cfg["suppress"] == []       # neither suppression applies
    assert len(findings) == 2
    assert all(f.rule == "RA000" for f in findings)


def test_config_suppression_applies_by_path(tmp_path):
    (tmp_path / "bad.py").write_text(
        "import jax\nx = jax.device_get(1)\n")
    cfg = tmp_path / "rules.toml"
    cfg.write_text(textwrap.dedent(f"""\
        [[suppress]]
        rule = "RA002"
        path = "{lint.rel(str(tmp_path))}"
        reason = "test fixture tree"
        """))
    assert lint.run(paths=[str(tmp_path)], config=str(cfg)) == []
    # same tree without the suppression: the finding is live
    assert [f.rule for f in lint.run(paths=[str(tmp_path)],
                                     config=str(tmp_path / "none.toml"))
            ] == ["RA002"]


# ------------------------------------------------- registry mutation

_SHAPE = (8, 64, 32)


def _spec_clone(name, priority, base="xla"):
    return dataclasses.replace(get_backend(base), name=name,
                               priority=priority)


def test_register_existing_requires_override():
    with pytest.raises(ValueError, match="override=True"):
        register_backend(_spec_clone("xla", 1))


def test_register_override_replaces_builtin():
    original = get_backend("xla")
    try:
        register_backend(dataclasses.replace(original, priority=1),
                         override=True)
        assert get_backend("xla").priority == 1
        # the builtin keeps resolving by name with its new priority
        assert resolve_backend(backend="xla").priority == 1
    finally:
        register_backend(original, override=True)
    assert get_backend("xla").priority == original.priority


def test_unregister_unknown_is_noop():
    before = set(plan_mod.backend_names())
    unregister_backend("no-such-backend")
    assert set(plan_mod.backend_names()) == before


def test_plan_cache_invalidation_on_registry_mutation():
    """Stale cached plans must not resolve to an unregistered backend:
    registering a higher-priority backend re-routes auto plans, and
    unregistering it both re-routes new plans AND makes any plan still
    holding the dead name fail loudly in execute."""
    baseline = plan_matmul(_SHAPE).backend
    turbo = _spec_clone("turbo", 10_000)
    try:
        register_backend(turbo)
        stale = plan_matmul(_SHAPE)
        assert stale.backend == "turbo"    # cache was invalidated
    finally:
        unregister_backend("turbo")
    assert plan_matmul(_SHAPE).backend == baseline
    with pytest.raises(ValueError, match="unknown backend"):
        execute(stale, jnp.ones((8, 64)), jnp.ones((64, 32)))


# ------------------------------------------------- shard pass (SD001-SD006)

_SMOKE = (abscache.SMOKE_ARCH,)


def test_shard_pass_clean():
    """Every (variant x mesh x arch) cell of the live lattice resolves
    and re-verifies — on the repo as merged, with zero devices."""
    assert shardspec.run() == []


def test_sd001_unresolvable_axes_flagged():
    fs = shardspec.run(inject="resolve", archs=_SMOKE)
    assert fs and all(f.rule == "SD001" for f in fs)


def test_sd002_invalid_spec_flagged():
    fs = shardspec.run(inject="spec", archs=_SMOKE)
    assert fs and all(f.rule == "SD002" for f in fs)


def test_sd003_large_replication_flagged():
    fs = shardspec.run(inject="replicate", archs=_SMOKE)
    assert fs and all(f.rule == "SD003" for f in fs)


def test_sd004_mirror_divergence_flagged():
    fs = shardspec.run(inject="mirror", archs=_SMOKE)
    assert fs and all(f.rule == "SD004" for f in fs)


def test_sd005_unknown_axis_flagged():
    fs = shardspec.run(inject="axis", archs=_SMOKE)
    assert [f.rule for f in fs] == ["SD005"]
    assert "embeddd" in fs[0].message


def test_sd006_readme_drift_flagged():
    fs = shardspec.run(inject="drift", archs=_SMOKE)
    assert fs and all(f.rule == "SD006" for f in fs)


def test_typod_axis_in_model_file_caught_without_devices(tmp_path):
    """ISSUE 10 acceptance: a typo'd logical axis in a model file is a
    finding from the static pass alone — no mesh, no device code."""
    (tmp_path / "model.py").write_text(textwrap.dedent("""\
        from repro.models.registry import ParamDef
        wq = ParamDef((512, 512), ("embed", "headz"))
        """))
    fs = shardspec.run(scan_paths=(str(tmp_path),), archs=_SMOKE)
    assert [f.rule for f in fs] == ["SD005"]
    assert "headz" in fs[0].message and "model.py" in fs[0].where


def test_cli_shard_injection_exits_nonzero():
    assert cli_main(["--passes", "shard", "--inject-shard", "axis"]) != 0


def test_axis_table_round_trips():
    parsed = shardspec.parse_axis_table(shardspec.render_axis_table())
    assert parsed  # and it matches the live rules
    assert shardspec._check_readme_axes(shardspec.DIST_README) == []


# ------------------------------------------------- jaxpr pass (JX001-JX004)

def _jaxpr_injected(inject):
    entry = jaxpr_audit._injected_entry(inject)
    return jaxpr_audit._check_entry(entry, abscache.smoke_model(),
                                    inject)


def test_jaxpr_pass_clean_and_shares_abscache():
    """Every audited serve/train/frontend entry traces clean; the
    shard pass run just before it hits the shared model cache."""
    abscache.clear()
    assert shardspec.run(archs=_SMOKE) == []
    assert jaxpr_audit.run() == []
    st = abscache.stats()
    assert st["smoke_model"]["misses"] == 1     # built once...
    assert st["config"]["hits"] >= 1            # ...reused across passes


def test_jx001_unaliased_donation_flagged():
    fs = _jaxpr_injected("donation")
    assert fs and all(f.rule == "JX001" for f in fs)


def test_jx002_widening_flagged():
    fs = _jaxpr_injected("widen")
    assert fs and all(f.rule == "JX002" for f in fs)
    assert any("float64" in f.message for f in fs)


def test_jx003_callback_flagged():
    fs = _jaxpr_injected("callback")
    rules = {f.rule for f in fs}
    # the debug print is both a banned primitive (JX003) and a debug
    # effect — an extra channel out of the graph (JX004); both correct
    assert "JX003" in rules


def test_jx004_arity_drift_flagged():
    fs = _jaxpr_injected("transfer")
    assert [f.rule for f in fs] == ["JX004"]
    assert "arity" in fs[0].message


def test_cli_jaxpr_injection_exits_nonzero():
    assert cli_main(["--passes", "jaxpr",
                     "--inject-jaxpr", "transfer"]) != 0


def test_manifest_entries_declare_unique_names():
    names = [e.name for e in jaxpr_audit.manifest_entries()]
    assert len(names) == len(set(names))
    # every jitted entry the serving, training and front-end paths
    # dispatch is audited
    assert set(names) >= {
        "serve.prefill_step", "serve.decode_step",
        "serve.chunked_decode_loop", "serve.admit",
        "serve.paged_decode_loop", "serve.paged_admit",
        "train.train_step", "frontend.pump"}


# ------------------------------------------------- dead suppressions

def test_dead_inline_suppression_is_ra000(tmp_path):
    fs = _lint(tmp_path, "x = 1   # lint: allow RA002 (stale)\n")
    assert [f.rule for f in fs] == ["RA000"]
    assert "dead suppression" in fs[0].message


def test_matched_inline_suppression_is_not_dead(tmp_path):
    fs = _lint(tmp_path, """\
        import jax
        x = jax.device_get(1)   # lint: allow RA002 (fixture)
        """)
    assert fs == []


def test_dead_config_suppression_is_ra000(tmp_path):
    (tmp_path / "ok.py").write_text("x = 1\n")
    cfg = tmp_path / "rules.toml"
    cfg.write_text(textwrap.dedent(f"""\
        [[suppress]]
        rule = "RA002"
        path = "{lint.rel(str(tmp_path))}"
        reason = "stale fixture"
        """))
    fs = lint.run(paths=[str(tmp_path)], config=str(cfg))
    assert [f.rule for f in fs] == ["RA000"]
    assert "dead suppression" in fs[0].message


def test_config_suppression_outside_scan_is_not_audited(tmp_path):
    """A --lint-paths subset run must not declare repo-wide
    suppressions dead: only entries under the scanned trees are
    audited."""
    (tmp_path / "ok.py").write_text("x = 1\n")
    cfg = tmp_path / "rules.toml"
    cfg.write_text(textwrap.dedent("""\
        [[suppress]]
        rule = "RA002"
        path = "src/somewhere/else.py"
        reason = "lives outside this scan"
        """))
    assert lint.run(paths=[str(tmp_path)], config=str(cfg)) == []


# ------------------------------------------------- findings artifact

def test_cli_json_out_writes_findings_document(tmp_path):
    out = tmp_path / "findings.json"
    rc = cli_main(["--passes", "lint", "--format", "json",
                   "--out", str(out)])
    doc = json.loads(out.read_text())
    assert rc == 0 and doc["ok"] is True
    assert doc["passes"][0]["name"] == "lint"
    assert "seconds" in doc["passes"][0]
    assert "abscache" in doc
