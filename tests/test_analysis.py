"""shisha-lint self-tests: the rule suite, the pragma machinery, and the
tree-clean gate.

Three layers of guarantee:

  * every registered rule demonstrably fires on its minimal bad fixture
    and stays silent on the paired clean fixture;
  * the suppression machinery is live in both directions — a pragma
    suppresses exactly its finding, and a pragma that suppresses nothing
    is itself an error — so the pragma inventory cannot go stale;
  * the shipped tree is clean: ``python -m repro.analysis src/`` exits 0,
    and deleting any single pragma in ``src/`` re-surfaces a real finding
    (proving the gate would catch the regression).
"""

import json
import re
from pathlib import Path

import pytest

from repro.analysis import RULES, lint_source, run
from repro.analysis.cli import main
from repro.analysis.framework import USELESS_SUPPRESSION, BAD_PRAGMA

FIXTURES = Path(__file__).resolve().parent / "fixtures" / "lint"
REPO = Path(__file__).resolve().parents[1]
SRC = REPO / "src"

PRAGMA_RE = re.compile(r"\s*#\s*shisha:\s*allow\(([^)]*)\)")

RULE_FIXTURES = [
    ("wall-clock", "wall_clock"),
    ("unseeded-random", "unseeded_random"),
    ("set-iteration", "set_iteration"),
    ("unkeyed-sort", "unkeyed_sort"),
    ("telemetry-guard", "telemetry_guard"),
    ("id-ordering", "id_ordering"),
    ("float-accum", "float_accum"),
    ("event-past", "event_past"),
    ("wall-clock", "thermal_accum"),
    ("float-accum", "thermal_accum"),
    ("unseeded-random", "mtbf_sampler"),
]


# -- registry shape ----------------------------------------------------------


def test_registry_covers_the_contracts():
    names = set(RULES)
    assert len(names) >= 8
    expected = {r for r, _ in RULE_FIXTURES} | {"import-layering"}
    assert expected <= names


# -- per-rule fixtures -------------------------------------------------------


@pytest.mark.parametrize("rule,stem", RULE_FIXTURES)
def test_rule_fires_on_bad_fixture(rule, stem):
    report = run([FIXTURES / f"{stem}_bad.py"])
    fired = [f for f in report.findings if f.rule == rule]
    assert fired, f"{rule} did not fire on its bad fixture"
    assert all(f.line > 0 and f.path.endswith(f"{stem}_bad.py") for f in fired)


@pytest.mark.parametrize("rule,stem", RULE_FIXTURES)
def test_clean_fixture_is_fully_clean(rule, stem):
    report = run([FIXTURES / f"{stem}_clean.py"])
    assert report.findings == [], [f.format() for f in report.findings]


# -- layering ----------------------------------------------------------------


def test_layering_contract_violations():
    report = run([FIXTURES / "layering_bad"])
    msgs = [f.message for f in report.findings if f.rule == "import-layering"]
    assert len(msgs) == 2
    assert any("repro.telemetry may not import repro.serve" in m for m in msgs)
    assert any(
        "repro.core may not import repro.interconnect" in m and "lazily" in m
        for m in msgs
    )


def test_layering_clean_tree():
    report = run([FIXTURES / "layering_clean"])
    assert report.findings == []


def test_import_cycle_detected():
    report = run([FIXTURES / "cycle"])
    cyc = [f for f in report.findings if f.rule == "import-layering"]
    assert len(cyc) == 1
    assert "mod_a -> mod_b -> mod_a" in cyc[0].message


def test_lazy_import_is_not_a_cycle():
    a = "def get():\n    import mod_b\n    return mod_b\n"
    # a one-file program can't cycle; check the lazy classifier directly
    from repro.analysis.framework import source_context
    from repro.analysis.layering import collect_edges

    edges = collect_edges(source_context(a, module="mod_a"))
    assert [e.lazy for e in edges] == [True]


# -- suppression pragmas -----------------------------------------------------


def test_pragma_suppresses_and_is_load_bearing():
    src = (FIXTURES / "suppression_ok.py").read_text()
    report = lint_source(src, display="suppression_ok.py")
    assert report.findings == []
    assert [f.rule for f in report.suppressed] == ["wall-clock"]
    stripped = PRAGMA_RE.sub("", src)
    report = lint_source(stripped, display="suppression_ok.py")
    assert [f.rule for f in report.findings] == ["wall-clock"]


def test_useless_pragma_is_an_error():
    report = run([FIXTURES / "suppression_useless.py"])
    assert [f.rule for f in report.findings] == [USELESS_SUPPRESSION]


def test_unknown_rule_in_pragma_is_an_error():
    report = lint_source("x = 1  # shisha: allow(no-such-rule)\n")
    assert [f.rule for f in report.findings] == [BAD_PRAGMA]


def test_pragma_mentions_in_docstrings_are_inert():
    report = lint_source('"""docs say # shisha: allow(wall-clock)."""\nx = 1\n')
    assert report.findings == []
    assert report.suppressed == []


# -- the tree-clean gate -----------------------------------------------------


def test_src_tree_is_clean():
    report = run([SRC])
    assert report.findings == [], [f.format() for f in report.findings]
    assert report.suppressed, "expected load-bearing pragmas in src/"


def test_cli_gate_exits_zero_on_src(capsys):
    assert main([str(SRC)]) == 0
    assert "0 error(s)" in capsys.readouterr().out


def test_cli_json_report_and_exit_codes(tmp_path, capsys):
    out = tmp_path / "lint.json"
    rc = main(
        [str(FIXTURES / "wall_clock_bad.py"), "--format=json", "--output", str(out)]
    )
    capsys.readouterr()
    assert rc == 1
    payload = json.loads(out.read_text())
    assert payload["tool"] == "shisha-lint"
    assert payload["summary"]["errors"] >= 1
    assert all(f["rule"] == "wall-clock" for f in payload["findings"])
    rc = main([str(FIXTURES / "wall_clock_bad.py"), "--report-only"])
    capsys.readouterr()
    assert rc == 0


def _module_for(py: Path) -> str:
    return ".".join(py.relative_to(SRC).with_suffix("").parts).removesuffix(
        ".__init__"
    )


def test_every_pragma_in_src_is_load_bearing():
    """Deleting any single suppression pragma must fail the gate."""
    from repro.analysis.framework import parse_pragmas

    checked = 0
    for py in sorted(SRC.rglob("*.py")):
        src = py.read_text()
        lines = src.splitlines(keepends=True)
        for pragma in parse_pragmas(src):
            i = pragma.line - 1
            mutated = "".join(
                PRAGMA_RE.sub("", l) if j == i else l for j, l in enumerate(lines)
            )
            report = lint_source(mutated, display=str(py), module=_module_for(py))
            resurfaced = [f for f in report.findings if f.rule in pragma.rules]
            assert resurfaced, (
                f"{py}:{pragma.line}: pragma allow({', '.join(pragma.rules)}) "
                "suppresses nothing — the gate would not notice its deletion"
            )
            checked += 1
    assert checked >= 1, "expected at least the known pragma in src/"


def test_report_is_deterministic():
    a = run([FIXTURES])
    b = run([FIXTURES])
    assert [f.to_json() for f in a.findings] == [f.to_json() for f in b.findings]
    assert [f.to_json() for f in a.suppressed] == [f.to_json() for f in b.suppressed]
