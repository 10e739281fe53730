"""bench.py harness contract: the child-process plumbing is pinned here
with stub children — JSON extraction from noisy stdout, failure labeling,
timeout kills — and the result line's fields."""

import json
import os
import sys
import textwrap

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench


@pytest.fixture()
def stub_child(tmp_path, monkeypatch):
    """Point bench's child spawn at a stub script; returns its setter."""

    def set_body(body: str) -> str:
        path = tmp_path / "stub_bench.py"
        path.write_text(
            "import sys, json, time, os\n" + textwrap.dedent(body)
        )
        monkeypatch.setattr(bench, "__file__", str(path))
        return str(path)

    return set_body


def test_run_child_parses_last_json_line_from_noisy_stdout(stub_child):
    stub_child("""
        print("WARNING: some platform noise")
        print(json.dumps({"value": 1}))
        print("trailing log line")
        print(json.dumps({"metric": "m", "value": 42.5, "unit": "H/s"}))
    """)
    out, why = bench._run_child(timeout=30)
    assert why == ""
    assert out == {"metric": "m", "value": 42.5, "unit": "H/s"}


def test_run_child_labels_crash_with_stderr_tail(stub_child):
    stub_child("""
        print("partial")
        print("RuntimeError: UNAVAILABLE: TPU backend setup", file=sys.stderr)
        sys.exit(1)
    """)
    out, why = bench._run_child(timeout=30)
    assert out is None
    assert why.startswith("rc=1")
    assert "UNAVAILABLE" in why


def test_run_child_kills_on_timeout(stub_child):
    stub_child("""
        time.sleep(60)
    """)
    out, why = bench._run_child(timeout=1)
    assert out is None
    assert why.startswith("timeout>")
    assert not bench._children  # the timed-out child was reaped


def test_run_child_flags_missing_json(stub_child):
    stub_child("""
        print("no json here at all")
    """)
    out, why = bench._run_child(timeout=30)
    assert out is None
    assert "no JSON result line" in why


def test_output_contract_fields():
    """The driver parses ONE JSON line with these exact fields; keep the
    measure() dict shape stable."""
    import inspect

    src = inspect.getsource(bench.measure)
    for field in ('"metric"', '"value"', '"unit"', '"vs_baseline"', '"platform"'):
        assert field in src, f"measure() no longer emits {field}"
