"""repro.analysis: contract checker, bloat linter, convention lint, and the
autotune pruning hook.

The negative fixtures each seed ONE violation class the checker exists to
catch — the failure modes this repo actually hit (the seed's out-of-bounds
halo indexing, a missing widened accumulator, a racing revisit dim, the
im2col HBM bloat) — and assert exactly one violation of the expected kind
fires. The positive tests prove the real registered families are clean.
"""
import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.analysis import bloat, contracts, lint  # noqa: E402
from repro.analysis.contracts import Block, KernelInstance, Violation  # noqa: E402
from repro import health  # noqa: E402


# ---------------------------------------------------------------------------
# seeded-violation fixtures (negative): each fires exactly one typed violation
# ---------------------------------------------------------------------------

def _clean_conv_like(**overrides) -> KernelInstance:
    """A small, fully-in-bounds conv1d-shaped instance the fixtures
    perturb one property of. Grid (B=2, tiles=4, cout=1, red=2); array
    padded to the halo need; f32 scratch; revisit dim trailing."""
    tile_l, K, cb, ob = 64, 5, 8, 8
    halo = tile_l - 1 + K  # stride 1
    need = 4 * tile_l - 1 + K
    fields = dict(
        family="fixture", key="fixture|conv_like",
        grid=(2, 4, 1, 2),
        inputs=[
            Block("x", (1, halo, cb), "float32",
                  lambda b, i, co, r: (b, i * tile_l, r * cb),
                  (2, need, 2 * cb), element=True),
            Block("w", (K, cb, ob), "float32",
                  lambda b, i, co, r: (0, r, co), (K, 2 * cb, ob)),
        ],
        outputs=[Block("out", (1, tile_l, ob), "float32",
                       lambda b, i, co, r: (b, i, co), (2, 4 * tile_l, ob))],
        scratch=[Block("acc", (tile_l, ob), "float32")],
        compute_dtypes=("float32", "float32"),
        acc_dtype="float32",
    )
    fields.update(overrides)
    return KernelInstance(**fields)


def _kinds(violations):
    return [v.kind for v in violations]


def test_clean_fixture_passes():
    assert contracts.check_instance(_clean_conv_like()) == []


def test_fixture_halo_oob():
    """The seed bug: an element-offset halo index map over an UNPADDED array —
    the final tile reads past the end."""
    tile_l, K, cb = 64, 5, 8
    halo = tile_l - 1 + K
    bad_x = Block(
        "x", (1, halo, cb), "float32",
        lambda b, i, co, r: (b, i * tile_l, r * cb),
        (2, 4 * tile_l, 2 * cb),  # length 256: tile 3 reads [192, 260)
        element=True,
    )
    inst = _clean_conv_like()
    inst.inputs[0] = bad_x
    vio = contracts.check_instance(inst)
    assert _kinds(vio) == ["halo_oob"]
    assert "x" in vio[0].detail and "axis 1" in vio[0].detail


def test_fixture_bf16_accumulator():
    """bf16 inputs accumulating into a bf16 scratch (no f32 widening)."""
    inst = _clean_conv_like(
        compute_dtypes=("bfloat16", "bfloat16"),
        acc_dtype="bfloat16",
        scratch=[Block("acc", (64, 8), "bfloat16")],
    )
    vio = contracts.check_instance(inst)
    assert _kinds(vio) == ["acc_dtype"]
    assert "float32" in vio[0].detail


def test_fixture_int8_accumulator_rule():
    """int8 x int8 requires int32, not float32."""
    inst = _clean_conv_like(
        compute_dtypes=("int8", "int8"), acc_dtype="float32",
        scratch=[Block("acc", (64, 8), "float32")],
    )
    assert _kinds(contracts.check_instance(inst)) == ["acc_dtype"]


def test_fixture_parallel_revisit_dim():
    """The reduction dim marked parallel: accumulation would race."""
    inst = _clean_conv_like(
        dim_roles=("arbitrary", "arbitrary", "arbitrary", "parallel"),
    )
    vio = contracts.check_instance(inst)
    assert _kinds(vio) == ["revisit_race"]
    assert "parallel" in vio[0].detail


def test_fixture_leading_revisit_dim():
    """A revisit dim AHEAD of varying dims: other blocks' visits
    interleave between two visits of the same accumulator."""
    tile_l, K, cb, ob = 64, 5, 8, 8
    halo = tile_l - 1 + K
    need = 4 * tile_l - 1 + K
    inst = _clean_conv_like(
        grid=(2, 2, 4, 1),  # reduction (size 2) now leads tiles (size 4)
        inputs=[
            Block("x", (1, halo, cb), "float32",
                  lambda b, r, i, co: (b, i * tile_l, r * cb),
                  (2, need, 2 * cb), element=True),
            Block("w", (K, cb, ob), "float32",
                  lambda b, r, i, co: (0, r, co), (K, 2 * cb, ob)),
        ],
        outputs=[Block("out", (1, tile_l, ob), "float32",
                       lambda b, r, i, co: (b, i, co),
                       (2, 4 * tile_l, ob))],
    )
    vio = contracts.check_instance(inst)
    assert _kinds(vio) == ["revisit_race"]
    assert "precedes varying" in vio[0].detail


def test_fixture_store_every_visit():
    inst = _clean_conv_like(out_on_last_visit=False)
    vio = contracts.check_instance(inst)
    assert _kinds(vio) == ["revisit_race"]
    assert "every visit" in vio[0].detail


def test_fixture_vmem_budget():
    vio = contracts.check_instance(_clean_conv_like(), budget=10_000)
    assert _kinds(vio) == ["vmem_budget"]


def test_fixture_im2col_bloat():
    """The paper's im2col baseline materializes the K×-bloated column
    matrix — exactly one bloat violation from the HLO walk."""
    fn, args = bloat.KNOWN_BLOATED["conv1d.im2col_gemm"]()
    v = bloat.check_fn(fn, args, family="bloat", key="conv1d.im2col_gemm")
    assert v is not None and v.kind == "bloat"
    # K=31 columns: the offender is ~29x the natural size, well past alpha
    assert "x the rung's natural size" in v.detail


def test_sliding_rung_clean():
    fn, args = bloat.GATE_RUNGS["conv1d.sliding"]()
    assert bloat.check_fn(
        fn, args, family="bloat", key="conv1d.sliding"
    ) is None


# ---------------------------------------------------------------------------
# positive: every registered family over the (sampled) key space
# ---------------------------------------------------------------------------

def test_check_all_families_clean():
    vio, stats = contracts.check_all(quick=True)
    assert vio == [], [v.line() for v in vio]
    assert stats["instances"] > 50
    # every registered builder family must appear in the swept space
    for fam in ("conv1d.fp", "conv1d.w8a8", "conv2d.w8a16",
                "conv1d_depthwise.fp", "pool1d", "attention_decode.int8",
                "conv1d_bwd_dw", "conv2d_bwd_dw", "ssm_scan"):
        assert fam in stats["families"], stats["families"]


def test_builders_cover_registry():
    _, stats = contracts.check_all(quick=True)
    swept = {f.split(".")[0] for f in stats["families"]}
    assert swept == set(contracts.FAMILIES)


def test_dequant_chains_clean():
    vio, stats = bloat.check_chains()
    assert vio == [], [v.line() for v in vio]
    assert "edge/c1 -> edge/c2 -> edge/c3" in stats["chains"]


def test_chain_cycle_detected():
    paths, errors = bloat._chain_paths({"a": "b", "b": "a"})
    assert errors and "cycle" in errors[0] or "no chain heads" in errors[0]
    assert paths == []


# ---------------------------------------------------------------------------
# autotune consumes contract verdicts
# ---------------------------------------------------------------------------

def test_autotune_prunes_over_budget_candidates(monkeypatch, capsys, tmp_path):
    """With a lowered VMEM budget, large-tile candidates are pruned from
    the conv1d search BEFORE being timed (logged per candidate), the
    winner is a surviving tile, and the tuned kernel's output still
    matches the reference."""
    from repro.core import conv as C
    from repro.kernels import autotune, ops

    monkeypatch.setenv("REPRO_VMEM_BUDGET", "50000")
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((1, 512, 16)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((5, 16, 16)), jnp.float32)
    res = autotune.autotune_conv1d(x, w)
    err = capsys.readouterr().err
    assert res.pruned >= 1
    assert "[autotune] pruned" in err and "vmem_budget" in err
    # the surviving winner must itself satisfy the budget
    v = contracts.check_autotune_candidate(
        "conv1d", dict(B=1, L=512, Cin=16, Cout=16, K=5),
        {k: res.best[k] for k in ("tile_l", "cin_block", "cout_block",
                                  "regime")},
        budget=50_000,
    )
    assert v is None
    y = ops.conv1d(x, w, backend="sliding", tile_l=res.best["tile_l"],
                   cin_block=res.best["cin_block"],
                   cout_block=res.best["cout_block"],
                   regime=res.best["regime"])
    ref = C.conv1d(x, w, backend="sliding")
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_autotune_default_budget_prunes_nothing():
    """At the default 16 MiB budget no BENCH-space candidate is pruned —
    tuned configs are bit-identical to the pre-checker searches."""
    n = 0
    for family, shape, cand in contracts.default_space(quick=True):
        assert contracts.check_autotune_candidate(family, shape, cand) is None
        n += 1
    assert n > 50


def test_autotune_never_prunes_default(monkeypatch, capsys):
    """An absurdly small budget prunes EVERY candidate, but the default
    still gets timed and recorded — dispatch always has a config."""
    from repro.kernels import autotune

    monkeypatch.setenv("REPRO_VMEM_BUDGET", "1")
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((1, 256, 8)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((3, 8, 8)), jnp.float32)
    res = autotune.autotune_conv1d(x, w)
    assert res.best["tile_l"] >= 1 and res.best["us"] > 0


# ---------------------------------------------------------------------------
# health vocabulary + dispatch log (satellites)
# ---------------------------------------------------------------------------

def test_health_rejects_unknown_reason():
    h = health.Health()
    with pytest.raises(ValueError, match="unknown health reason"):
        h.record("conv1d", "not_a_reason", "demote:pallas->jax")
    h.record("conv1d", "pallas_compile", "demote:pallas->jax")
    assert h.events[0].reason == "pallas_compile"


def test_canon_reason():
    class Fault(RuntimeError):
        kind = "pallas_runtime"

    assert health.canon_reason(Fault()) == "pallas_runtime"
    assert health.canon_reason(FloatingPointError()) == "nan_logits"
    assert health.canon_reason(RuntimeError(), default="jax_error") == "jax_error"
    assert health.canon_reason(RuntimeError(), default="bogus") == "runtime_error"
    assert health.canon_reason(RuntimeError()) == "runtime_error"


def test_dispatch_log_counts():
    log = health.DispatchLog()
    assert "k" not in log and log.count("k") == 0
    log["k"] = "pallas"
    log["k"] = "pallas"
    log["k"] = "jax"  # demotion mid-run: value updates, count keeps growing
    assert log["k"] == "jax"
    assert log.count("k") == 3
    assert log.items() == [("k", "jax")]
    assert log.counts() == {"k": 3}
    assert len(log) == 1 and list(log) == ["k"]
    log.clear()
    assert len(log) == 0


# ---------------------------------------------------------------------------
# convention lint
# ---------------------------------------------------------------------------

def test_lint_src_clean():
    vio, stats = lint.check_all()
    assert vio == [], [v.line() for v in vio]
    assert stats["files"] > 40


def test_lint_flags_unknown_reason_literal(tmp_path):
    f = tmp_path / "bad_reason.py"
    f.write_text(
        "HEALTH.record('conv1d', 'totally_new_reason', 'demote')\n"
    )
    vio = lint.lint_file(f)
    assert _kinds(vio) == ["lint_reason"]


def test_lint_flags_fstring_reason(tmp_path):
    f = tmp_path / "fstring_reason.py"
    f.write_text(
        "HEALTH.record('conv1d', f'{name}_error', 'demote')\n"
    )
    vio = lint.lint_file(f)
    assert _kinds(vio) == ["lint_reason"]
    assert "canon_reason" in vio[0].detail


def test_lint_flags_unregistered_site(tmp_path):
    f = tmp_path / "bad_site.py"
    f.write_text(
        "conv1d_bias_act(x, w, b, site='whisper/conv3')\n"
        "HEALTH.record('serve/generate', 'straggler', 'flag')\n"
    )
    vio = lint.lint_file(f)
    assert _kinds(vio) == ["lint_site"]
    assert "whisper/conv3" in vio[0].detail


def test_lint_accepts_conv_site_pattern(tmp_path):
    f = tmp_path / "shape_site.py"
    f.write_text("observe(x, site='conv2d|Cin32|Cout64|K3x3')\n")
    assert lint.lint_file(f) == []


def test_lint_flags_raw_pallas_indexing(tmp_path):
    d = tmp_path / "kernels"
    d.mkdir()
    f = d / "raw.py"
    f.write_text(
        "def k(x_ref, o_ref):\n"
        "    v = pl.load(x_ref, (0, 0))\n"
        "    pl.store(o_ref, (0, 0), v)\n"
    )
    vio = lint.lint_file(f)
    assert _kinds(vio) == ["lint_raw_indexing", "lint_raw_indexing"]
    # same file OUTSIDE a kernels/ dir is not subject to the rule
    g = tmp_path / "raw.py"
    g.write_text(f.read_text())
    assert lint.lint_file(g) == []


# ---------------------------------------------------------------------------
# CLI gate
# ---------------------------------------------------------------------------

def test_cli_quick_run_writes_report(tmp_path, monkeypatch):
    from repro.analysis.__main__ import main

    out = tmp_path / "ANALYSIS.json"
    rc = main(["--contracts", "--lint", "--quick", "--json", str(out)])
    assert rc == 0
    import json

    report = json.loads(out.read_text())
    assert report["ok"] is True
    assert report["stats"]["contracts"]["instances"] > 50
    assert "autotune_prune" in report["stats"]["contracts"]


def test_cli_fails_on_violation(tmp_path):
    from repro.analysis.__main__ import main

    bad = tmp_path / "tree" / "bad.py"
    bad.parent.mkdir()
    bad.write_text("HEALTH.record('conv1d', 'oops_reason', 'x')\n")
    out = tmp_path / "ANALYSIS.json"
    rc = main(["--lint", "--lint-root", str(bad.parent), "--json", str(out)])
    assert rc == 1
    import json

    report = json.loads(out.read_text())
    assert report["ok"] is False
    assert report["violations"][0]["kind"] == "lint_reason"


# ---------------------------------------------------------------------------
# lint_walltime: the time.time() ban (PR 8's perf_counter fix, enforced)
# ---------------------------------------------------------------------------

def test_lint_flags_walltime_call(tmp_path):
    f = tmp_path / "timed.py"
    f.write_text(
        "import time\n"
        "t0 = time.time()\n"
        "elapsed = time.time() - t0\n"
    )
    vio = lint.lint_file(f)
    assert _kinds(vio) == ["lint_walltime", "lint_walltime"]
    assert "perf_counter" in vio[0].detail


def test_lint_flags_from_time_import_time(tmp_path):
    f = tmp_path / "hidden.py"
    f.write_text("from time import time\nt = time()\n")
    vio = lint.lint_file(f)
    assert _kinds(vio) == ["lint_walltime"]
    # importing anything else from time is fine
    g = tmp_path / "ok.py"
    g.write_text("from time import perf_counter\nt = perf_counter()\n")
    assert lint.lint_file(g) == []


def test_lint_walltime_allowlist_exempts_registered_files(tmp_path):
    d = tmp_path / "repro" / "distributed"
    d.mkdir(parents=True)
    f = d / "ft.py"
    f.write_text("import time\nstamp = time.time()\n")
    rel = "repro/distributed/ft.py"
    assert rel in lint.WALLCLOCK_ALLOWED  # registry entry carries a reason
    assert lint.WALLCLOCK_ALLOWED[rel]
    assert lint.lint_file(f, rel=rel) == []
    # the same code under an unregistered path is flagged
    assert _kinds(lint.lint_file(f, rel="repro/kernels/ft.py")) \
        == ["lint_walltime"]


def test_lint_walltime_ignores_perf_counter(tmp_path):
    f = tmp_path / "mono.py"
    f.write_text(
        "import time\n"
        "t0 = time.perf_counter()\n"
        "dt = time.perf_counter() - t0\n"
    )
    assert lint.lint_file(f) == []


# ---------------------------------------------------------------------------
# CLI: the two new passes + the schema-2 report contract
# ---------------------------------------------------------------------------

def test_cli_costmodel_and_ranges_pass(tmp_path):
    import json

    from repro.analysis.__main__ import SCHEMA, main

    out = tmp_path / "ANALYSIS.json"
    rc = main(["--costmodel", "--ranges", "--quick", "--json", str(out)])
    assert rc == 0
    report = json.loads(out.read_text())
    assert report["schema"] == SCHEMA == 2
    assert report["ok"] is True
    cm = report["stats"]["costmodel"]
    assert cm["instances"] > 50
    assert {"gflops", "hbm_gbps", "vmem_gbps", "source"} \
        <= set(cm["peaks"])
    fams = cm["validate"]["families"]
    for d in fams.values():  # the MAPE/Spearman table CI uploads
        assert {"n", "mape", "spearman", "gated"} <= set(d)
    rg = report["stats"]["ranges"]
    assert rg["chains"]
    assert all(c["status"] == "safe" for c in rg["chains"].values())


def test_load_report_reads_legacy_schema1(tmp_path):
    import json

    from repro.analysis.__main__ import load_report

    legacy = {  # the PR 7/8 shape: no "schema", three stats sections
        "ok": True,
        "violations": [],
        "stats": {"contracts": {"instances": 7}, "bloat": {}, "lint": {}},
        "elapsed_s": 1.0,
    }
    p = tmp_path / "legacy.json"
    p.write_text(json.dumps(legacy))
    rep = load_report(str(p))
    assert rep["schema"] == 1
    assert rep["stats"]["contracts"]["instances"] == 7
    # the sections that postdate the report read as empty, not KeyError
    assert rep["stats"]["costmodel"] == {}
    assert rep["stats"]["ranges"] == {}


def test_load_report_passthrough_schema2(tmp_path):
    import json

    from repro.analysis.__main__ import load_report, main

    out = tmp_path / "ANALYSIS.json"
    assert main(["--ranges", "--json", str(out)]) == 0
    rep = load_report(str(out))
    assert rep["schema"] == 2
    assert rep["stats"]["ranges"]["chains"]
