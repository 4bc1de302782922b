"""Fast-tier docs drift guard (ISSUE 9 satellite): every PERCEIVER_IO_TPU_*
env var the package reads must appear in the docs kill-switch tables
(docs/*.md or README.md) — scripts/check_killswitch_docs.py is the
executable contract, this smoke wires it into tier 1 so an undocumented
switch fails CI, not an operator mid-incident."""

import importlib.util
import os


def _load():
    spec = importlib.util.spec_from_file_location(
        "check_killswitch_docs_under_test",
        os.path.join(os.path.dirname(__file__), "..", "scripts",
                     "check_killswitch_docs.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_every_package_env_var_is_documented():
    mod = _load()
    result = mod.check()
    assert result["ok"], (
        f"undocumented PERCEIVER_IO_TPU_* env var(s): "
        f"{result['missing_from_docs']} — add them to the docs kill-switch "
        f"tables (docs/serving.md / docs/training-pipeline.md / "
        f"docs/reliability.md / docs/observability.md)"
    )
    # the guard is not vacuous: the known switches are actually found
    for var in ("PERCEIVER_IO_TPU_DISABLE_DECODE_KERNEL",
                "PERCEIVER_IO_TPU_DISABLE_PREEMPTION",
                "PERCEIVER_IO_TPU_TELEMETRY"):
        assert var in result["package_vars"]
        assert var in result["documented_vars"]


def test_checker_detects_missing_var(tmp_path):
    """The guard actually fires: a fake repo with a code-only env var fails,
    and documenting it passes."""
    mod = _load()
    pkg = tmp_path / "perceiver_io_tpu"
    pkg.mkdir()
    (pkg / "thing.py").write_text(
        'FLAG = os.environ.get("PERCEIVER_IO_TPU_DISABLE_THING", "0")\n'
    )
    (tmp_path / "README.md").write_text("# nothing documented yet\n")
    result = mod.check(repo=str(tmp_path))
    assert not result["ok"]
    assert result["missing_from_docs"] == ["PERCEIVER_IO_TPU_DISABLE_THING"]
    docs = tmp_path / "docs"
    docs.mkdir()
    (docs / "x.md").write_text("| `PERCEIVER_IO_TPU_DISABLE_THING=1` | off |\n")
    assert mod.check(repo=str(tmp_path))["ok"]
    # a bare prose glob ("PERCEIVER_IO_TPU_*") never counts as documentation
    assert "PERCEIVER_IO_TPU_" not in mod.documented_env_vars(str(tmp_path))


def test_schema_versions_tracked():
    """ISSUE 10 satellite: the guard also pins versioned artifact schemas —
    the newest serving-metrics version the package stamps must be the one
    docs/serving.md documents (the v4→v5→v6 doc races)."""
    mod = _load()
    result = mod.check()
    fam = result["schemas"]["serving-metrics"]
    assert fam["ok"], fam
    # not vacuous: the package really references a versioned schema and the
    # doc really mentions that exact version
    assert fam["newest_package_version"] is not None
    assert fam["newest_package_version"] in fam["documented_versions"]


def test_schema_guard_detects_doc_lag(tmp_path):
    """A fake repo whose package bumps the schema without the doc fails; the
    doc catching up passes (older versions lingering in both is fine)."""
    mod = _load()
    pkg = tmp_path / "perceiver_io_tpu"
    pkg.mkdir()
    (pkg / "metrics.py").write_text('SCHEMA = "serving-metrics/v10"\n'
                                    'OLD = "serving-metrics/v8"\n')
    docs = tmp_path / "docs"
    docs.mkdir()
    (docs / "serving.md").write_text("## Metrics schema (`serving-metrics/v8`)\n")
    (tmp_path / "README.md").write_text("# nothing\n")
    result = mod.check(repo=str(tmp_path))
    assert not result["ok"]
    fam = result["schemas"]["serving-metrics"]
    assert not fam["ok"] and fam["newest_package_version"] == 10
    # doc catches up -> green, even with v8 still mentioned in the package
    (docs / "serving.md").write_text(
        "## Metrics schema (`serving-metrics/v10`)\nv8 added things.\n"
        "serving-metrics/v8 remains readable.\n")
    assert mod.check(repo=str(tmp_path))["ok"]
