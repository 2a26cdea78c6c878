"""``python -m benchmarks.run``: a bench that raises fails the run."""
import json
import sys

import pytest

from benchmarks import pim_figs, run
from repro.core import compile_cache


def test_raising_bench_prints_error_row_and_fails_run(monkeypatch, capsys):
    def boom(scale):
        raise RuntimeError("bench exploded")

    monkeypatch.setattr(pim_figs, "fig11_simt", boom)
    monkeypatch.setattr(compile_cache, "use_persistent_cache", lambda: "")
    monkeypatch.setattr(sys, "argv", ["run", "--only", "fig11_simt"])
    with pytest.raises(SystemExit) as exc:
        run.main()
    assert exc.value.code not in (0, None)
    assert "fig11_simt" in str(exc.value.code)
    name, _, rows = capsys.readouterr().out.strip().split(",", 2)
    assert name == "fig11_simt"
    assert json.loads(rows) == [{"error": "RuntimeError: bench exploded"}]
