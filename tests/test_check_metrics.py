"""tools/check_metrics.py — metric-name drift gate (ISSUE 11
satellite): the repo's emitted registry metrics and the
docs/OBSERVABILITY.md Metric inventory must stay in sync, enforced as
a tier-1 test."""

import os

import pytest

import tools.check_metrics as cm

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_repo_metric_inventory_in_sync():
    """THE gate: every emitted metric documented, every documented
    metric emitted. A failure message names the drift."""
    problems, emitted, documented = cm.check(REPO)
    assert problems == [], "\n".join(problems)
    assert len(emitted) >= 50               # the scanner actually scans
    assert emitted.keys() == documented


def test_cli_exit_code():
    assert cm.main(["--root", REPO]) == 0


def _fake_repo(tmp_path, source: str, doc_names):
    (tmp_path / "paddle_tpu").mkdir()
    (tmp_path / "paddle_tpu" / "mod.py").write_text(source)
    (tmp_path / "docs").mkdir()
    rows = "\n".join(f"| `{n}` | counter | | x |" for n in doc_names)
    (tmp_path / "docs" / "OBSERVABILITY.md").write_text(
        "# t\n\n## Metric inventory\n\n| Metric | Type | Labels | "
        f"Meaning |\n|---|---|---|---|\n{rows}\n\n## Next\n`not_me_x`\n")
    return str(tmp_path)


SRC = '''
reg.counter("requests_total", "help text").inc()
reg.histogram("lat_seconds" if warm
              else "cold_lat_seconds",
              "dispatch latency").observe(dt)
reg.gauge(
    "queue_depth",
    "waiting requests").set(3)
for k in names:
    # emits-metrics: dyn_a_total, dyn_b_total
    reg.counter(k).inc()
'''


def test_scanner_literal_conditional_and_annotated(tmp_path):
    root = _fake_repo(tmp_path, SRC, [])
    emitted = cm.emitted_metrics(root)
    assert set(emitted) == {"requests_total", "lat_seconds",
                            "cold_lat_seconds", "queue_depth",
                            "dyn_a_total", "dyn_b_total"}
    # help strings (contain spaces) never leak in as names
    assert "help" not in emitted


def test_undocumented_metric_fails(tmp_path):
    root = _fake_repo(tmp_path, SRC,
                      ["requests_total", "lat_seconds",
                       "cold_lat_seconds", "dyn_a_total",
                       "dyn_b_total"])        # queue_depth missing
    problems, _, _ = cm.check(root)
    assert len(problems) == 1
    assert "UNDOCUMENTED" in problems[0]
    assert "queue_depth" in problems[0]
    assert "mod.py" in problems[0]


def test_documented_but_gone_fails(tmp_path):
    root = _fake_repo(tmp_path, SRC,
                      ["requests_total", "lat_seconds",
                       "cold_lat_seconds", "queue_depth",
                       "dyn_a_total", "dyn_b_total",
                       "ghost_metric_total"])
    problems, _, _ = cm.check(root)
    assert len(problems) == 1
    assert "DOCUMENTED-BUT-GONE" in problems[0]
    assert "ghost_metric_total" in problems[0]
    # names outside the inventory section don't count as documented
    _, _, documented = cm.check(root)
    assert "not_me_x" not in documented


def test_missing_section_is_loud(tmp_path):
    root = _fake_repo(tmp_path, SRC, [])
    (tmp_path / "docs" / "OBSERVABILITY.md").write_text("# nothing\n")
    with pytest.raises(ValueError, match="Metric inventory"):
        cm.check(root)
