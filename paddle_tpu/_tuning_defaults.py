"""Single source of truth for tunable kernel defaults and the
effective-config normalizer.

Used by consumers that must agree byte-for-byte:
  * paddle_tpu/ops/flash_attention.py — flash kernel block defaults
  * paddle_tpu/models/llama_serving.py — serving ragged-kernel tile
  * tools/autotune.py / tools/tune_ragged.py — trial dedup / persist
  * tests/test_perf_guard.py          — history grouping key

Deliberately a leaf module with no jax imports; tools/ and tests/ load
it by file path (importlib) to avoid paying for paddle_tpu/__init__.
The serving engine passes the device generation string IN (resolved
via observability.device_telemetry) so this module stays jax-free.
"""
import json
import os

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DEFAULT_FLASH_BLOCK_Q = 128
DEFAULT_FLASH_BLOCK_K = 128

# ragged paged-attention serving kernel tile: q rows a block and pages
# a KV block (0 = derive from the shapes: rows x GQA group fill the
# MXU's 128 rows, a KV block is one lane width of tokens)
DEFAULT_RAGGED_BLOCK_Q = 0
DEFAULT_RAGGED_BLOCK_PAGES = 0

# per-TPU-generation winners persisted by tools/tune_ragged.py; the
# engine loads this ONCE at construction (a static tile — no serving-
# time retrace). Smoke runs must point PT_RAGGED_TILE_FILE elsewhere.
RAGGED_TILE_FILE = os.environ.get("PT_RAGGED_TILE_FILE") or \
    os.path.join(_ROOT, "TUNED.kernels.json")


def flash_block_q():
    return int(os.environ.get("PT_FLASH_BLOCK_Q", DEFAULT_FLASH_BLOCK_Q))


def flash_block_k():
    return int(os.environ.get("PT_FLASH_BLOCK_K", DEFAULT_FLASH_BLOCK_K))


def generation_key(device_kind):
    """Stable slug for a jax `device_kind` string ('TPU v5 lite' ->
    'tpu-v5-lite', 'cpu' -> 'cpu') — the per-generation key tuned
    kernel tiles persist under."""
    s = str(device_kind or "cpu").strip().lower()
    s = "".join(c if c.isalnum() else " " for c in s)
    return "-".join(s.split()) or "cpu"


def load_ragged_tile(device_kind, path=None):
    """Effective (block_q, block_pages) for the serving ragged kernel:
    env override > persisted per-generation winner > builtin default.
    0 means 'derive from the shapes' throughout. Never raises — a
    missing/corrupt tile file silently falls back to the builtins (a
    serving engine must come up on an untuned chip)."""
    bq, bp = DEFAULT_RAGGED_BLOCK_Q, DEFAULT_RAGGED_BLOCK_PAGES
    try:
        with open(path or RAGGED_TILE_FILE) as f:
            entry = (json.load(f).get("ragged") or {}).get(
                generation_key(device_kind)) or {}
        bq = int(entry.get("block_q", bq))
        bp = int(entry.get("block_pages", bp))
    except (OSError, ValueError, TypeError):
        pass
    bq = int(os.environ.get("PT_RAGGED_BLOCK_Q", bq))
    bp = int(os.environ.get("PT_RAGGED_BLOCK_PAGES", bp))
    return bq, bp


def save_ragged_tile(device_kind, block_q, block_pages, path=None,
                     extra=None):
    """Atomically merge one generation's winning tile into the tile
    file (read-modify-write via os.replace, the TUNED.json idiom) and
    return the written entry."""
    path = path or RAGGED_TILE_FILE
    data = {}
    try:
        with open(path) as f:
            data = json.load(f)
    except (OSError, ValueError):
        data = {}
    entry = {"block_q": int(block_q), "block_pages": int(block_pages)}
    if extra:
        entry.update(extra)
    data.setdefault("ragged", {})[generation_key(device_kind)] = entry
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(data, f, indent=2, sort_keys=True)
        f.write("\n")
    os.replace(tmp, path)
    return entry


def effective_knobs(entry):
    """Normalize a history row / trial cfg dict to its EFFECTIVE tuning
    knobs: absent/None block sizes mean the kernel defaults, and
    absent/0/None n_micro all mean no gradient accumulation."""
    return (int(entry.get("block_q") or DEFAULT_FLASH_BLOCK_Q),
            int(entry.get("block_k") or DEFAULT_FLASH_BLOCK_K),
            int(entry.get("n_micro") or 0))
