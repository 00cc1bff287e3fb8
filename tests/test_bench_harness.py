"""The bench harness's wedge-survival pieces are themselves tested:
the salvage parser (what the parent keeps from a killed child) and a
real timed-out subprocess exercising the full _spawn_stage path.
"""

import json
import subprocess
import sys

import bench


class TestLastJsonLine:
    def test_none_and_empty(self):
        assert bench._last_json_line(None) is None
        assert bench._last_json_line("") is None
        assert bench._last_json_line(b"") is None

    def test_picks_last_json(self):
        out = "\n".join(
            [json.dumps({"a": 1}), "[bench] progress noise", json.dumps({"b": 2})]
        )
        assert bench._last_json_line(out) == {"b": 2}

    def test_bytes_and_partial_garbage_tail(self):
        # The kill can truncate the last line mid-write; the previous
        # complete record must still be recovered.
        out = (json.dumps({"ok": 1}) + "\n" + '{"trunca').encode()
        assert bench._last_json_line(out) == {"ok": 1}

    def test_error_records_are_not_salvaged(self):
        assert bench._last_json_line(json.dumps({"error": "boom"})) is None
        # ...but an earlier good record still wins.
        out = json.dumps({"ok": 1}) + "\n" + json.dumps({"error": "x"})
        assert bench._last_json_line(out) == {"ok": 1}

    def test_non_dict_json_ignored(self):
        assert bench._last_json_line("[1, 2, 3]") is None


def test_spawn_timeout_salvages_partial(monkeypatch):
    """End to end through _spawn_stage: a child that prints one JSON
    line and then hangs is killed at the timeout, and its printed
    record comes back instead of None."""
    real_run = subprocess.run

    def fake_run(cmd, **kw):
        # Replace the bench child with a hang-after-print stub, keeping
        # the real subprocess+timeout machinery (incl. the kill path).
        stub = [
            sys.executable,
            "-c",
            "import json,sys,time;"
            "print(json.dumps({'engine_ops_per_sec': 42.0}), flush=True);"
            "time.sleep(60)",
        ]
        return real_run(stub, **kw)

    monkeypatch.setattr(subprocess, "run", fake_run)
    out = bench._spawn_stage(16, 16, 1, "cpu", timeout_s=3.0)
    assert out == {"engine_ops_per_sec": 42.0}
