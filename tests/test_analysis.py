"""repro.analysis: every checker must FIRE on the corpus and stay SILENT
on the repo (modulo the committed baseline).

The corpus under ``tests/analysis_corpus/`` holds one minimal known-bad
snippet per rule; a checker that cannot flag its own corpus file is a
gate that cannot fail, which is no gate at all (the check_bench
``--selftest`` lesson). The clean-side tests then pin the repo itself:
annotations in ``ps/runtime.py`` / ``serving/forest_server.py`` hold, the
kernels' BlockSpecs are SMEM-correct, and the full CLI run agrees with
``analysis_baseline.json`` bit for bit.
"""
import json
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import determinism, findings, lints, locks, vmem
from repro.launch.mesh import make_mesh

ROOT = pathlib.Path(__file__).resolve().parents[1]
CORPUS = ROOT / "tests" / "analysis_corpus"


def _codes(fs):
    return {f.code for f in fs}


# ------------------------------------------------------------------- locks
def test_locks_flags_corpus():
    fs = locks.check_file(CORPUS / "bad_lock.py", "bad_lock.py")
    assert "unguarded-write" in _codes(fs)  # worker: thread target
    assert "unguarded-read" in _codes(fs)  # reporter: # concurrent opt-in
    idents = {f.ident for f in fs}
    assert "worker:shared" in idents and "reporter:shared" in idents
    # `fine` locks correctly and `main` only touches the Thread object.
    assert not any(f.ident.startswith(("fine:", "main:")) for f in fs)


def test_locks_repo_is_clean():
    assert locks.check_repo(ROOT) == []


def test_locks_catch_delocked_runtime_access():
    """De-indent one locked read in the REAL runtime and the checker must
    notice — proof the annotations there are live, not decorative."""
    src = (ROOT / "src/repro/ps/runtime.py").read_text()
    needle = '                        pulled_version = shared["version"]'
    assert needle in src
    # hoist the read out of `with lock:` (an if-block at the with's own
    # indent keeps the rest of the body parseable)
    broken = src.replace(needle, "                    if True:\n" + needle)
    p = CORPUS / "_runtime_delocked.py"
    try:
        p.write_text(broken)
        fs = locks.check_file(p, "runtime_delocked.py")
        assert "unguarded-read" in _codes(fs)
    finally:
        p.unlink(missing_ok=True)


# ------------------------------------------------------------ determinism
def _import_corpus(name):
    sys.path.insert(0, str(CORPUS))
    try:
        import importlib

        return importlib.import_module(name)
    finally:
        sys.path.pop(0)


@pytest.fixture(scope="module")
def seam_mod():
    return _import_corpus("bad_seam")


def test_seam_unpinned_flagged(seam_mod):
    f = jnp.zeros(8)
    jaxpr = jax.make_jaxpr(seam_mod.unpinned_round)(f, f)
    assert _codes(determinism.audit_seam(jaxpr, "corpus")) == {"seam-unpinned"}


def test_seam_crossing_flagged(seam_mod):
    f = jnp.zeros(8)
    jaxpr = jax.make_jaxpr(seam_mod.leaky_round)(f, f)
    fs = determinism.audit_seam(jaxpr, "corpus")
    assert _codes(fs) == {"seam-crossing"}
    # the leak is the FMA-contractible mul->add pair, named as such
    assert any("FMA-contractible" in f.message for f in fs)


def test_seam_pinned_is_clean(seam_mod):
    f = jnp.zeros(8)
    jaxpr = jax.make_jaxpr(seam_mod.pinned_round)(f, f)
    assert determinism.audit_seam(jaxpr, "corpus") == []


@pytest.mark.filterwarnings("ignore:Explicitly requested dtype")
def test_f64_intermediate_flagged():
    mod = _import_corpus("bad_f64")
    jax.config.update("jax_enable_x64", True)
    try:
        jaxpr = jax.make_jaxpr(mod.double_round)(jnp.zeros(8, jnp.float32))
    finally:
        jax.config.update("jax_enable_x64", False)
    assert "f64-intermediate" in _codes(determinism.audit_f64(jaxpr, "corpus"))
    # the same function traced WITHOUT x64 stays f32 end-to-end: clean
    jaxpr32 = jax.make_jaxpr(mod.double_round)(jnp.zeros(8, jnp.float32))
    assert determinism.audit_f64(jaxpr32, "corpus") == []


def test_staleness_twin_matches():
    assert determinism.audit_staleness_twin() == []


def test_psum_order_flags_premerge_subtract():
    mod = _import_corpus("bad_psum")
    mesh = make_mesh((1,), ("data",))
    bins = jnp.zeros((8,), jnp.int32)
    g = jnp.zeros((8,), jnp.float32)
    bad = jax.make_jaxpr(mod.make_bad_builder(mesh))(bins, g)
    fs = determinism.audit_psum_order(bad, "corpus")
    assert _codes(fs) == {"premerge-combine"}
    good = jax.make_jaxpr(mod.make_good_builder(mesh))(bins, g)
    assert determinism.audit_psum_order(good, "corpus") == []


def test_psum_order_flags_premerge_argmax():
    """The 2D-mesh inversion: pmax of gains over UNMERGED partial
    histograms must fire; row-psum-then-pmax (the merged-argmax split
    search, DESIGN.md §16) must stay clean."""
    mod = _import_corpus("bad_psum")
    mesh = make_mesh((1,), ("data",))
    bins = jnp.zeros((8,), jnp.int32)
    g = jnp.zeros((8,), jnp.float32)
    bad = jax.make_jaxpr(mod.make_bad_argmax_builder(mesh))(bins, g)
    fs = determinism.audit_psum_order(bad, "corpus")
    assert _codes(fs) == {"premerge-combine"}
    assert any("pmax" in f.message for f in fs)
    good = jax.make_jaxpr(mod.make_good_argmax_builder(mesh))(bins, g)
    assert determinism.audit_psum_order(good, "corpus") == []


def test_determinism_repo_round_path_is_clean():
    """The real engine honors all three invariants (seam pinned, no f64,
    twin bitwise-equal, subtract after psum)."""
    assert determinism.check_repo(ROOT) == []


# -------------------------------------------------------------------- vmem
def test_vmem_flags_corpus_blockspecs():
    fs = vmem.check_blockspecs(CORPUS / "bad_spec.py", "bad_spec.py")
    assert _codes(fs) == {"blockspec-scalar", "blockspec-any"}
    lines = {f.line for f in fs}
    assert len(lines) == 2  # the SMEM-placed good spec is not flagged


def test_vmem_kernels_are_clean():
    for rel in vmem.KERNEL_FILES:
        assert vmem.check_blockspecs(ROOT / rel, rel) == [], rel


# ------------------------------------------------------------------- lints
def test_lints_flag_fake_repo():
    fs = lints.check_repo(CORPUS / "fake_repo")
    by_code = {f.code: f for f in fs}
    assert by_code["hardcoded-interpret"].file == "benchmarks/bad_interpret.py"
    assert by_code["prngkey-outside-ticket"].file == "src/repro/core/bad_rng.py"
    assert by_code["unknown-trace-field"].ident == "staleness"
    # rows["schedule"] IS in the fake schema: exactly one trace finding
    assert sum(f.code == "unknown-trace-field" for f in fs) == 1


def test_lints_repo_is_clean():
    """Clean modulo inline pragmas (the determinism tracer's own keys
    carry `# analysis: ignore[prngkey-outside-ticket]`)."""
    fs = lints.check_repo(ROOT)
    sources = {f.file: (ROOT / f.file).read_text().splitlines() for f in fs}
    assert findings.apply_suppressions(fs, sources) == []


# ------------------------------------------- findings / baseline machinery
def test_fingerprint_survives_line_moves():
    a = findings.Finding("locks", "unguarded-read", "error", "x.py", 10, "m", "f:v")
    b = findings.Finding("locks", "unguarded-read", "error", "x.py", 99, "m", "f:v")
    assert a.fingerprint == b.fingerprint


def test_suppression_pragma():
    f = findings.Finding("lints", "hardcoded-interpret", "error", "a.py", 2, "m")
    pragma = "run(interpret=True)  # analysis: ignore[hardcoded-interpret]"
    sources = {"a.py": ["x = 1", pragma]}
    assert findings.apply_suppressions([f], sources) == []
    assert findings.apply_suppressions([f], {"a.py": ["x", "run(interpret=True)"]}) == [f]


def test_baseline_requires_justification(tmp_path):
    p = tmp_path / "base.json"
    p.write_text(json.dumps({"findings": [{"fingerprint": "a:b:c:d"}]}))
    with pytest.raises(ValueError, match="justification"):
        findings.load_baseline(p)


def test_split_by_baseline(tmp_path):
    f1 = findings.Finding("locks", "c", "error", "x.py", 1, "m", "i1")
    f2 = findings.Finding("locks", "c", "error", "x.py", 2, "m", "i2")
    base = {f1.fingerprint: "known", "locks:c:gone.py:i9": "fixed long ago"}
    new, old, stale = findings.split_by_baseline([f1, f2], base)
    assert new == [f2] and old == [f1]
    assert stale == ["locks:c:gone.py:i9"]


# --------------------------------------------------------------------- CLI
def _cli(*args, cwd=ROOT):
    import os

    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"))
    return subprocess.run(
        [sys.executable, "-m", "repro.analysis", *args],
        capture_output=True, text=True, cwd=cwd, env=env, timeout=600,
    )


def test_cli_selftest_passes():
    r = _cli("--selftest")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "selftest ok" in r.stdout


def test_cli_fails_on_new_and_respects_baseline(tmp_path):
    # a fake repo with one lint violation and no baseline -> exit 1
    root = tmp_path / "repo"
    (root / "benchmarks").mkdir(parents=True)
    (root / "benchmarks" / "b.py").write_text("def r(k):\n    k(interpret=True)\n")
    r = _cli("--only", "lints", "--root", str(root))
    assert r.returncode == 1
    assert "hardcoded-interpret" in r.stdout
    # --no-fail-on-new reports but exits 0
    r = _cli("--only", "lints", "--root", str(root), "--no-fail-on-new")
    assert r.returncode == 0
    # accept into a baseline -> clean run, finding shown as baselined
    base = tmp_path / "base.json"
    r = _cli("--only", "lints", "--root", str(root), "--baseline", str(base),
             "--write-baseline")
    assert r.returncode == 0
    r = _cli("--only", "lints", "--root", str(root), "--baseline", str(base))
    assert r.returncode == 0
    assert "1 baselined" in r.stdout
    # fix the violation -> the baseline entry is reported stale
    (root / "benchmarks" / "b.py").write_text("def r(k):\n    k()\n")
    r = _cli("--only", "lints", "--root", str(root), "--baseline", str(base))
    assert r.returncode == 0
    assert "stale" in r.stdout


def test_cli_stdlib_checkers_match_committed_baseline(tmp_path):
    """The committed repo + committed baseline = green gate (the exact
    invocation the CI analysis job runs, minus the jax-tracing checker
    which test_determinism_repo_round_path_is_clean covers in-process)."""
    report = tmp_path / "report.json"
    r = _cli("--only", "locks", "--only", "vmem", "--only", "lints",
             "--json", str(report))
    assert r.returncode == 0, r.stdout + r.stderr
    payload = json.loads(report.read_text())
    assert payload["new"] == []
    assert payload["stale_baseline_entries"] == []
    # no finding needs a justification: the committed table holds no rows
    # until an on-chip sweep records lane-legal blocks
    assert payload["baselined"] == []
