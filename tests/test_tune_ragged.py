"""tools/tune_ragged.py smoke lane (ISSUE 12): the offline ragged-tile
autotuner's sweep/verify/persist/reload loop must be proven on CPU
before it runs unattended on a chip, and a persisted tile
must actually reach a constructed ServingEngine — as a STATIC kernel
arg, with token-identical outputs and zero serving-time retraces.
"""
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TUNER = os.path.join(ROOT, "tools", "tune_ragged.py")

from paddle_tpu import _tuning_defaults as TD
from paddle_tpu.models.llama import LlamaConfig
from paddle_tpu.models import llama_spmd as M
from paddle_tpu.models.llama_serving import Request, ServingEngine

CFG = LlamaConfig.tiny(vocab=64, hidden=32, layers=2, heads=4, kv_heads=2,
                       ffn=64, seq=128)


@pytest.fixture(scope="module")
def params():
    return M.init_params(CFG, seed=0, dtype=jnp.float32)


def test_smoke_sweep_verifies_persists_reloads(tmp_path):
    out = str(tmp_path / "TUNED.kernels.smoke.json")
    r = subprocess.run(
        [sys.executable, TUNER, "--smoke", "--out", out, "--iters", "1"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=420)
    assert r.returncode == 0, r.stderr + r.stdout
    with open(out) as f:
        data = json.load(f)
    entry = data["ragged"]["cpu"]
    assert {"block_q", "block_pages", "smoke", "trials"} <= set(entry)
    assert entry["smoke"] is True
    # every surviving trial was held to the reference by tolerance
    assert all(t["close"] for t in entry["trials"]
               if t["time_s"] is not None)
    assert len(entry["trials"]) >= 3
    # the tool's machine-readable summary line is its contract
    summary = json.loads(r.stdout.strip().splitlines()[-1])
    assert summary["generation"] == "cpu"
    assert summary["best"] == {"block_q": entry["block_q"],
                               "block_pages": entry["block_pages"]}
    # what was persisted is what the engine-side loader resolves
    assert TD.load_ragged_tile("cpu", path=out) == \
        (entry["block_q"], entry["block_pages"])


def test_tuner_refuses_real_run_without_tpu(tmp_path):
    out = str(tmp_path / "TUNED.kernels.json")
    r = subprocess.run(
        [sys.executable, TUNER, "--out", out],
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120)
    assert r.returncode == 1
    assert "TPU unreachable" in r.stderr
    assert not os.path.exists(out)


def test_engine_picks_up_persisted_tile(tmp_path, monkeypatch, params):
    """A tuned tile file -> ServingEngine statics, and the tuned engine
    is token-identical to the default-tile one (on the CPU both run the
    jnp reference: the tile is the kernel's alone)."""
    path = str(tmp_path / "tiles.json")
    TD.save_ragged_tile("cpu", 16, 2, path=path)
    monkeypatch.setattr(TD, "RAGGED_TILE_FILE", path)

    def run(tuned):
        if not tuned:
            monkeypatch.setattr(TD, "RAGGED_TILE_FILE",
                                str(tmp_path / "absent.json"))
        else:
            monkeypatch.setattr(TD, "RAGGED_TILE_FILE", path)
        eng = ServingEngine(params, CFG, max_seqs=2, max_seq_len=64,
                            page_size=8, use_pallas=False, ragged=True)
        if tuned:
            assert (eng._block_q, eng._block_pages) == (16, 2)
        else:   # untuned chip: both derived from the shapes
            assert (eng._block_q, eng._block_pages) == (None, None)
        eng.submit(Request("g", [1, 5, 9, 3], max_new_tokens=8))
        eng.submit(Request("s", [2, 4, 6], max_new_tokens=8,
                           temperature=0.8, top_k=8, seed=7))
        return {r.rid: r.output for r in eng.run()}

    assert run(tuned=False) == run(tuned=True)


def test_env_override_beats_tile_file(tmp_path, monkeypatch):
    path = str(tmp_path / "tiles.json")
    TD.save_ragged_tile("cpu", 16, 2, path=path)
    monkeypatch.setenv("PT_RAGGED_BLOCK_Q", "24")
    assert TD.load_ragged_tile("cpu", path=path) == (24, 2)
