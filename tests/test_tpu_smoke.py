"""No measurement path runs without the chip: chip_smoke.py, bench.py and
bench_models.py exit non-zero on a CPU-only machine (no fallback, no
result line), and chip_smoke.py's explicit dry run is marked as such on
every line it prints."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script, *args, **env):
    e = {k: v for k, v in os.environ.items()
         if k not in ("PT_BENCH_CPU", "XLA_FLAGS")}
    e.update(JAX_PLATFORMS="cpu", **env)
    return subprocess.run([sys.executable, os.path.join(ROOT, script), *args],
                          env=e, cwd=ROOT, capture_output=True, text=True,
                          timeout=300)


def test_chip_smoke_without_a_chip_fails_naming_the_platform():
    r = _run("chip_smoke.py")
    assert r.returncode != 0
    assert "'cpu'" in r.stderr and "not 'tpu'" in r.stderr
    assert r.stdout == "", "no result may be printed without a chip"


@pytest.mark.parametrize("script", ["bench.py", "bench_models.py"])
def test_bench_without_a_chip_fails(script):
    r = _run(script)
    assert r.returncode != 0
    assert "not a TPU" in r.stderr
    assert r.stdout == ""


def test_chip_smoke_dry_run_is_marked_on_every_line():
    r = _run("chip_smoke.py", "--dry-run-cpu", "--phases", "train,train4")
    assert r.returncode == 0, r.stderr[-2000:]
    lines = r.stdout.strip().splitlines()
    assert lines and all(l.startswith("DRY RUN platform=cpu ")
                         for l in lines)
    summary, result = (json.loads(l[len("DRY RUN platform=cpu "):])
                       for l in lines[-2:])
    device = {"platform": "cpu", "kind": "cpu", "count": 4}
    # the last line is the driver's contract: these keys and no others
    assert result == {"ok": True, "device": device}
    assert summary["ok"] is True and summary["claim"] is None
    assert summary["device"] == device
    assert summary["phases"]["train"] == summary["phases"]["train4"] \
        == "passed"
    assert summary["phases"]["serve"] == "not run"
