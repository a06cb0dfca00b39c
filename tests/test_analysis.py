"""Static-analysis suite tests (src/repro/analysis/).

Three layers of assurance:

- **fixture corpus**: every rule fires on its seeded-bad fixture and
  stays silent on the clean twin (tests/_analysis_fixtures/);
- **self-run**: the checkers report zero findings on the real tree —
  src/ and tests/ obey the invariants they enforce;
- **suppression discipline**: a bare ``# repro: allow[...]`` (no
  reason=) is itself a gating finding and can never be suppressed.

The decode-freeze test at the bottom exercises the runtime behaviour the
``alias-writeable`` rule guards: every wire decode view is read-only
even when the transport hands us a writable bytearray.
"""
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.analysis import ALL_RULES, main, run_analysis
from repro.analysis.core import ADVISORY_RULES, META_RULES

TESTS_DIR = Path(__file__).resolve().parent
REPO = TESTS_DIR.parent
SRC = REPO / "src"
FIX = TESTS_DIR / "_analysis_fixtures"
CODEC_REGISTRY = FIX / "codec" / "fl" / "flat.py"


def _rules(paths):
    return {f.rule for f in run_analysis([str(p) for p in paths])}


# ---------------------------------------------------------------------------
# fixture corpus: each bad fixture fires exactly its rule(s); the clean
# twin next to it fires nothing
# ---------------------------------------------------------------------------

BAD_CASES = [
    (["locks/bad_lock_order.py"], {"lock-order"}),
    (["locks/bad_self_deadlock.py"], {"lock-order"}),
    (["locks/bad_guarded.py"], {"guarded-by"}),
    (["locks/bad_guard_annot.py"], {"guarded-by"}),
    (["locks/bad_guard_call.py"], {"guarded-by"}),
    (["locks/bad_credit_ledger.py"], {"guarded-by"}),
    (["determinism/fl/bad_set_iter.py"], {"det-set-iter"}),
    (["determinism/fl/bad_entropy.py"], {"det-entropy"}),
    (["determinism/kernels/bad_float_accum.py"], {"det-float-accum"}),
    (["determinism/kernels/bad_split.py"], {"det-fma-split"}),
    (["aliasing/bad_frombuffer.py"], {"alias-writeable"}),
    (["aliasing/bad_mutation.py"], {"alias-mutation"}),
    (["codec/fl/flat.py", "codec/bad_literal.py"], {"codec-literal"}),
    (["codec/fl/flat.py", "codec/bad_dispatch.py"], {"codec-dispatch"}),
    (["clocks/repro/bad_wallclock.py"], {"monotonic-clock"}),
    (["clocks/repro/bad_transport_ttl.py"], {"monotonic-clock"}),
    (["deadname/repro/bad_unused.py"], {"dead-name"}),
    (["allows/bad_bare.py"], {"bare-allow", "unknown-rule"}),
    (["parse/bad_syntax.py"], {"parse-error"}),
]

GOOD_CASES = [
    ["locks/good_lock_order.py"],
    ["locks/good_guarded.py"],
    ["locks/good_credit_ledger.py"],
    ["determinism/fl/good_set_iter.py"],
    ["determinism/fl/good_entropy.py"],
    ["determinism/kernels/good_float_accum.py"],
    ["determinism/kernels/good_split.py"],
    ["aliasing/good_frombuffer.py"],
    ["aliasing/good_mutation.py"],
    ["codec/fl/flat.py", "codec/good_literal.py"],
    ["codec/fl/flat.py", "codec/good_dispatch.py"],
    ["clocks/repro/good_wallclock.py"],
    ["clocks/repro/good_transport_ttl.py"],
    ["deadname/repro/good_unused.py"],
    ["allows/good_allow.py"],
]


@pytest.mark.parametrize("paths,expected", BAD_CASES,
                         ids=[c[0][-1] for c in BAD_CASES])
def test_bad_fixture_fires(paths, expected):
    assert _rules(FIX / p for p in paths) == expected


@pytest.mark.parametrize("paths", GOOD_CASES,
                         ids=[c[-1] for c in GOOD_CASES])
def test_good_fixture_clean(paths):
    assert _rules(FIX / p for p in paths) == set()


def test_every_rule_covered_by_corpus():
    fired = set().union(*(exp for _, exp in BAD_CASES))
    assert fired == set(ALL_RULES), \
        "corpus must exercise every registered rule"


# ---------------------------------------------------------------------------
# self-run: the real tree is clean (this is the CI gate, in-process)
# ---------------------------------------------------------------------------

def test_self_run_zero_findings():
    findings = run_analysis([str(SRC), str(TESTS_DIR)])
    assert findings == [], "\n".join(f.render() for f in findings)


def test_fixture_corpus_skipped_by_walker():
    # the seeded violations must never leak into a directory-level run
    findings = run_analysis([str(TESTS_DIR)])
    assert not any("_analysis_fixtures" in f.path for f in findings)


# ---------------------------------------------------------------------------
# suppression discipline
# ---------------------------------------------------------------------------

def test_bare_allow_is_rejected(tmp_path):
    f = tmp_path / "mod.py"
    f.write_text(
        "import numpy as np\n"
        "def decode(buf):\n"
        "    arr = np.frombuffer(buf)  # repro: allow[alias-writeable]\n"
        "    return arr\n")
    rules = {x.rule for x in run_analysis([str(f)])}
    # the bare pragma suppresses the underlying finding but is itself a
    # gating finding, so the net effect is still a red build
    assert rules == {"bare-allow"}


def test_reasoned_allow_suppresses(tmp_path):
    f = tmp_path / "mod.py"
    f.write_text(
        "import numpy as np\n"
        "def decode(buf):\n"
        "    # repro: allow[alias-writeable] reason=caller owns buf\n"
        "    arr = np.frombuffer(buf)\n"
        "    return arr\n")
    assert run_analysis([str(f)]) == []


def test_meta_rules_never_suppressible(tmp_path):
    f = tmp_path / "mod.py"
    f.write_text("x = 1  # repro: allow[bare-allow, unknown-rule]\n")
    rules = {x.rule for x in run_analysis([str(f)])}
    assert "bare-allow" in rules


# ---------------------------------------------------------------------------
# CLI contract (exit codes, --only, --strict)
# ---------------------------------------------------------------------------

def test_cli_exit_codes(capsys):
    assert main([str(FIX / "locks")]) == 1
    capsys.readouterr()
    assert main([str(FIX / "locks" / "good_guarded.py")]) == 0
    capsys.readouterr()
    assert main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    assert set(out.split()) == set(ALL_RULES)
    assert main(["--only", "no-such-rule", "."]) == 2
    assert main([str(FIX / "does-not-exist")]) == 2


def test_cli_advisory_vs_strict(capsys):
    bad = str(FIX / "deadname" / "repro" / "bad_unused.py")
    assert main([bad]) == 0          # dead-name is advisory by default
    capsys.readouterr()
    assert main(["--strict", bad]) == 1
    capsys.readouterr()
    assert ADVISORY_RULES == {"dead-name"}
    assert META_RULES == {"bare-allow", "unknown-rule", "parse-error"}


def test_cli_only_filter():
    bad = str(FIX / "clocks" / "repro" / "bad_wallclock.py")
    rules = {f.rule for f in run_analysis([bad], only=["monotonic-clock"])}
    assert rules == {"monotonic-clock"}
    assert run_analysis([bad], only=["det-set-iter"]) == []


def test_module_entrypoint_runs():
    # `python -m repro.analysis` is what CI invokes; smoke it end to end
    proc = subprocess.run(
        [sys.executable, "-m", "repro.analysis",
         str(FIX / "codec" / "fl" / "flat.py"),
         str(FIX / "codec" / "bad_dispatch.py"), "--format", "json"],
        capture_output=True, text=True,
        env={"PYTHONPATH": str(SRC), "PATH": "/usr/bin:/bin"})
    assert proc.returncode == 1
    assert '"codec-dispatch"' in proc.stdout


# ---------------------------------------------------------------------------
# the invariants themselves, exercised at runtime
# ---------------------------------------------------------------------------

def test_registry_is_single_source_of_truth():
    from repro.fl.flat import (PAYLOAD_CODEC_MAGICS, WIRE_MAGIC_HI,
                               WIRE_MAGIC_LO, WIRE_MAGICS)
    from repro.fl.messages import (BF16_MAGIC, FLAT_MAGIC, PARTIAL_MAGIC,
                                   Q8_MAGIC, SPARSE_MAGIC)
    assert FLAT_MAGIC == WIRE_MAGICS["flat"]
    assert BF16_MAGIC == WIRE_MAGICS["bf16"]
    assert Q8_MAGIC == WIRE_MAGICS["q8"]
    assert PARTIAL_MAGIC == WIRE_MAGICS["partial"]
    assert SPARSE_MAGIC == WIRE_MAGICS["sparse"]
    assert set(PAYLOAD_CODEC_MAGICS) <= set(WIRE_MAGICS)
    vals = list(WIRE_MAGICS.values())
    assert len(vals) == len(set(vals)), "duplicate wire byte claimed"
    assert all(WIRE_MAGIC_LO <= v <= WIRE_MAGIC_HI for v in vals)


@pytest.mark.parametrize("codec", ["flat", "bf16", "q8"])
def test_decode_views_frozen_even_from_bytearray(codec):
    # bytes-backed frombuffer views are born read-only; bytearray-backed
    # ones (real receive buffers) are writable unless explicitly frozen —
    # this is the hazard alias-writeable exists to catch
    from repro.fl import messages as M
    arrs = [np.arange(12, dtype=np.float32).reshape(3, 4),
            np.linspace(-1, 1, 7, dtype=np.float32)]
    wire = bytearray(M.arrays_to_bytes(arrs, codec=codec))
    p = M.peek_params(wire)
    views = [p.buf] if hasattr(p, "buf") else \
        [v for v in (p.data, getattr(p, "scales", None)) if v is not None]
    assert views
    for v in views:
        assert v.flags.writeable is False
        with pytest.raises((ValueError, RuntimeError)):
            v[0] = 0
