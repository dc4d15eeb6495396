"""Central registry of paddle_tpu environment knobs.

Every ``PT_*`` / ``PADDLE_TPU_*`` environment variable the tree reads
is declared HERE — name, default, one-line doc, type — and tpuracer's
TPL010 rule enforces it: an env read whose name is not declared below
is a lint error, and serving/observability code must read knobs
through the accessors in this module rather than `os.environ`
directly. `tools/gen_env_docs.py` renders the registry into
docs/env.md, so the operator-facing knob table can never drift from
the code.

This module is stdlib-only and importable standalone (tools load it
via importlib without triggering `paddle_tpu/__init__`), so CI boxes
without an accelerator stack can generate docs and lint against it.

Accessor semantics (chosen to match the historical call sites):

  * `env_str`    missing -> default, else the raw string.
  * `env_int` / `env_float`
                 missing OR empty/whitespace -> default.
  * `env_bool`   missing -> default; set -> False iff the stripped
                 value is "" or "0", True otherwise.

All accessors take `env=` (any mapping) so tests and fault drills can
inject an environment without mutating `os.environ`. Pattern knobs
(name containing ``*``, e.g. ``PT_SLO_*_TTFT_S``) declare a family:
concrete members resolve through the family's type and doc, with the
call site supplying the per-member default.
"""
from __future__ import annotations

import fnmatch
import os
from dataclasses import dataclass

__all__ = ["Knob", "declare", "knobs", "knob", "is_declared",
           "env_raw", "env_str", "env_int", "env_float", "env_bool"]

_UNSET = object()


@dataclass(frozen=True)
class Knob:
    """One declared environment knob. `default` is the value accessors
    return when the variable is unset (None = "auto/disabled" — the
    call site computes the effective value); `kind` is the accessor
    type ('str'|'int'|'float'|'bool'); `section` groups the docs
    table."""
    name: str
    default: object
    doc: str
    kind: str = "str"
    section: str = "general"

    @property
    def is_pattern(self):
        return "*" in self.name


_REGISTRY: dict = {}


def declare(name, default, doc, *, kind="str", section="general"):
    """Register one knob. Raises on duplicates, on names outside the
    PT_*/PADDLE_TPU_* namespaces, and on unknown kinds — the registry
    is the contract, so it validates loudly at import time."""
    if not (name.startswith("PT_") or name.startswith("PADDLE_TPU_")):
        raise ValueError(
            f"env knob {name!r}: must start with PT_ or PADDLE_TPU_")
    if name in _REGISTRY:
        raise ValueError(f"env knob {name!r} declared twice")
    if kind not in ("str", "int", "float", "bool"):
        raise ValueError(f"env knob {name!r}: unknown kind {kind!r}")
    if not doc or not str(doc).strip():
        raise ValueError(f"env knob {name!r}: doc line required")
    k = Knob(name=name, default=default, doc=" ".join(str(doc).split()),
             kind=kind, section=section)
    _REGISTRY[name] = k
    return k


def knobs():
    """All declared knobs, sorted by (section, name) — the docs-table
    order."""
    return sorted(_REGISTRY.values(), key=lambda k: (k.section, k.name))


def knob(name):
    """Exact or family (pattern) match; None when undeclared."""
    k = _REGISTRY.get(name)
    if k is not None:
        return k
    for pat, cand in _REGISTRY.items():
        if "*" in pat and fnmatch.fnmatchcase(name, pat):
            return cand
    return None


def is_declared(name):
    return knob(name) is not None


def _resolve(name, default):
    k = knob(name)
    if k is None:
        raise KeyError(
            f"env knob {name!r} is not declared in paddle_tpu/_env.py "
            "— add a declare(...) entry (TPL010 enforces this)")
    if default is _UNSET:
        if k.is_pattern:
            raise KeyError(
                f"env knob {name!r} matches family {k.name!r}: the "
                "call site must supply the per-member default")
        return k.default
    return default


def env_raw(name, env=None):
    """The raw string value, or None when unset. Still requires the
    name to be declared."""
    if knob(name) is None:
        _resolve(name, _UNSET)          # raises the undeclared error
    src = os.environ if env is None else env
    return src.get(name)


def env_str(name, default=_UNSET, env=None):
    default = _resolve(name, default)
    src = os.environ if env is None else env
    v = src.get(name)
    return default if v is None else v


def env_int(name, default=_UNSET, env=None):
    default = _resolve(name, default)
    src = os.environ if env is None else env
    v = src.get(name)
    if v is None or not str(v).strip():
        return default
    return int(str(v).strip())


def env_float(name, default=_UNSET, env=None):
    default = _resolve(name, default)
    src = os.environ if env is None else env
    v = src.get(name)
    if v is None or not str(v).strip():
        return default
    return float(str(v).strip())


def env_bool(name, default=_UNSET, env=None):
    default = _resolve(name, default)
    src = os.environ if env is None else env
    v = src.get(name)
    if v is None:
        return bool(default)
    return str(v).strip() not in ("", "0")


# ---------------------------------------------------------------------------
# The knob catalogue. Section names become docs/env.md headings; keep
# docs to ONE line — gen_env_docs renders them into a table cell.

# -- serving -----------------------------------------------------------
declare("PT_SERVE_TIMELINE", True,
        "Per-request timeline + SLO accounting plane (0 disables; "
        "token outputs are identical either way).",
        kind="bool", section="serving")
declare("PT_SERVE_PULSE", True,
        "Pulse telemetry plane: ring time-series, /debug/pulse, "
        "anomaly capture bundles (0 disables).",
        kind="bool", section="serving")
declare("PT_SERVE_TIMING", False,
        "Attach a timing block (e2e/ttft/phase split) to HTTP "
        "completion responses.", kind="bool", section="serving")
declare("PT_SERVE_RAGGED", True,
        "Serve through the unified ragged step (0 falls back to the "
        "padded batch step).", kind="bool", section="serving")
declare("PT_FAULTS", "",
        "Fault-injection plan spec, e.g. 'crash@step:p=0.01;seed=7' "
        "(empty disables; see serving/faults.py).",
        kind="str", section="serving")
declare("PT_ANOMALY_FLOOR_S", 0.05,
        "Step-stall anomaly sentinel: absolute floor of the "
        "slow-step threshold in seconds.",
        kind="float", section="serving")

# -- SLO targets -------------------------------------------------------
declare("PT_SLO_*_TTFT_S", None,
        "Per-class time-to-first-token budget override in seconds "
        "(defaults: INTERACTIVE 1.0, BATCH 10.0).",
        kind="float", section="slo")
declare("PT_SLO_*_TPOT_S", None,
        "Per-class time-per-output-token budget override in seconds "
        "(defaults: INTERACTIVE 0.1, BATCH 1.0).",
        kind="float", section="slo")

# -- pulse plane -------------------------------------------------------
declare("PT_PULSE_DEPTH", 240,
        "Ring depth (samples kept) per pulse signal.",
        kind="int", section="pulse")
declare("PT_PULSE_INTERVAL_S", 1.0,
        "Pulse sampler tick interval in seconds.",
        kind="float", section="pulse")
declare("PT_PULSE_SLO_BURST", 3,
        "SLO-violation burst (per tick) that trips an anomaly "
        "capture.", kind="int", section="pulse")
declare("PT_CAPTURE_DIR", "",
        "Directory for anomaly capture bundles (empty disables "
        "capture).", kind="str", section="pulse")
declare("PT_CAPTURE_MAX", 8,
        "Maximum capture bundles kept on disk (oldest pruned).",
        kind="int", section="pulse")
declare("PT_CAPTURE_MIN_S", 30.0,
        "Minimum seconds between capture bundles (rate limit).",
        kind="float", section="pulse")

# -- fleet plane -------------------------------------------------------
declare("PT_FLEET_HB_S", 0.5,
        "Fleet worker heartbeat interval in seconds.",
        kind="float", section="fleet")
declare("PT_FLEET_HB_MISS_S", 3.0,
        "Heartbeat stall after which the router declares a worker "
        "dead.", kind="float", section="fleet")
declare("PT_FLEET_CALL_TIMEOUT_S", 30.0,
        "Fleet control-plane rpc call timeout in seconds.",
        kind="float", section="fleet")
declare("PT_FLEET_RETRIES", 2,
        "Retries for idempotent fleet control-plane calls.",
        kind="int", section="fleet")
declare("PT_FLEET_FETCH_TIMEOUT_S", 1.0,
        "Per-page budget for prefix-page fetch-on-miss in seconds.",
        kind="float", section="fleet")
declare("PT_FLEET_FETCH_MAX", 8,
        "Maximum prefix pages fetched from peers per local tier "
        "match.", kind="int", section="fleet")
declare("PT_FLEET_SPILL_QUEUE", 128,
        "Bound of the evicted-page spill queue (full queue drops, "
        "never blocks).", kind="int", section="fleet")
declare("PT_FLEET_CLOCK_ALPHA", 0.2,
        "EWMA smoothing factor for per-worker clock-offset "
        "estimation (0 < alpha <= 1; higher tracks faster).",
        kind="float", section="fleet")
declare("PT_FLEET_OBS_POLL_S", 1.0,
        "Router-side fleet observability poll interval in seconds "
        "(worker trigger totals + clock samples).",
        kind="float", section="fleet")
declare("PT_FLEET_CAPTURE_DIR", "",
        "Directory for fleet capture bundles pulled by rank 0 on a "
        "worker pulse trigger (empty disables).",
        kind="str", section="fleet")
declare("PT_FLEET_CAPTURE_MAX", 8,
        "Maximum fleet capture bundles written per router process.",
        kind="int", section="fleet")
declare("PT_FLEET_CAPTURE_MIN_S", 30.0,
        "Minimum seconds between fleet capture bundles (rate limit).",
        kind="float", section="fleet")

# -- observability -----------------------------------------------------
declare("PADDLE_TPU_FLIGHT", True,
        "Flight recorder ring on/off (only the literal '0' "
        "disables).", kind="bool", section="observability")
declare("PADDLE_TPU_FLIGHT_EVENTS", 4096,
        "Flight recorder ring capacity in events.",
        kind="int", section="observability")
declare("PADDLE_TPU_FLIGHT_DIR", "/tmp",
        "Directory flight-recorder dumps are written to.",
        kind="str", section="observability")
declare("PADDLE_TPU_LOG", False,
        "Mirror structured log events to stderr when set to '1'.",
        kind="bool", section="observability")
declare("PADDLE_TPU_LOG_FILE", "",
        "Append structured log events to this file (empty disables).",
        kind="str", section="observability")
declare("PADDLE_TPU_TRACE", False,
        "Lightweight call tracing for debugging when set to '1'.",
        kind="bool", section="observability")
declare("PADDLE_TPU_PROFILE_DIR", "/tmp/pt_profile",
        "Output directory for profiler traces.",
        kind="str", section="observability")
declare("PADDLE_TPU_DEVICE_COST", "1",
        "Device cost model: '0' off, '1' on, 'full' adds per-op "
        "detail.", kind="str", section="observability")
declare("PADDLE_TPU_GEN", "",
        "TPU generation override for the cost model (e.g. 'v5e'); "
        "empty auto-detects.", kind="str", section="observability")
declare("PADDLE_TPU_PEAK_FLOPS", None,
        "Peak FLOP/s override for MFU math (default: per-generation "
        "table).", kind="float", section="observability")
declare("PADDLE_TPU_PEAK_BW", None,
        "Peak HBM bandwidth override in bytes/s for roofline math "
        "(default: per-generation table).",
        kind="float", section="observability")
declare("PADDLE_TPU_RETRACE_WARN", 8,
        "Retrace count per function after which compile telemetry "
        "warns.", kind="int", section="observability")

# -- kernels / tuning --------------------------------------------------
declare("PT_DISABLE_PALLAS", False,
        "Force the pure-jnp reference paths instead of Pallas "
        "kernels when '1'.", kind="bool", section="kernels")
declare("PT_FLASH_BLOCK_Q", 128,
        "Flash attention query tile size.", kind="int",
        section="kernels")
declare("PT_FLASH_BLOCK_K", 128,
        "Flash attention key/value tile size.", kind="int",
        section="kernels")
declare("PT_RAGGED_BLOCK_Q", None,
        "Ragged paged-attention q rows a block, override (0 derives "
        "them from the shapes; default: tuned per generation).",
        kind="int", section="kernels")
declare("PT_RAGGED_BLOCK_PAGES", None,
        "Ragged paged-attention pages a KV block, override (0 derives "
        "one lane width of tokens; default: tuned per generation).",
        kind="int", section="kernels")
declare("PT_RAGGED_TILE_FILE", "",
        "Path of the persisted per-generation ragged kernel tile "
        "table (default: TUNED.kernels.json in the repo).",
        kind="str", section="kernels")
declare("PT_FUSED_CE", False,
        "Fused cross-entropy in the training step when '1'.",
        kind="bool", section="kernels")

# -- distributed -------------------------------------------------------
declare("PT_RPC_BIND", "127.0.0.1",
        "Interface the rpc/bulk servers bind to.",
        kind="str", section="distributed")
declare("PT_RPC_TIMEOUT_S", None,
        "Default rpc_sync timeout in seconds (unset: wait forever, "
        "matching the reference).", kind="float", section="distributed")
declare("PT_RPC_THREADS", 8,
        "Worker threads per rpc agent (serve + callback pools).",
        kind="int", section="distributed")
declare("PT_PS_ENDPOINTS", "",
        "Comma-separated parameter-server endpoints.",
        kind="str", section="distributed")
declare("PT_PS_RANK", 0,
        "This process's rank in the parameter-server world.",
        kind="int", section="distributed")
declare("PT_PS_ROLE", "worker",
        "Parameter-server role of this process ('worker' or "
        "'pserver').", kind="str", section="distributed")
declare("PT_PS_BACKEND", "python",
        "Parameter-server transport backend.",
        kind="str", section="distributed")
declare("PT_PS_CKPT_DIR", "",
        "Parameter-server checkpoint directory (empty disables).",
        kind="str", section="distributed")

# -- io / checkpoint ---------------------------------------------------
declare("PT_DATALOADER_PROCS", False,
        "Use process workers (not threads) in the DataLoader when "
        "'1'.", kind="bool", section="io")
declare("PT_MP_SHM_BYTES", 1 << 30,
        "Shared-memory cache cap in bytes for multiprocessing tensor "
        "reductions.", kind="int", section="io")
declare("PT_AUTO_CKPT_DIR", "",
        "Auto-checkpoint output directory (empty disables the "
        "plane).", kind="str", section="io")
declare("PT_JOB_ID", "default",
        "Job id auto-checkpoint state is keyed under.",
        kind="str", section="io")
declare("PT_CKPT_SAVE_INTER", 900,
        "Auto-checkpoint save interval in seconds.",
        kind="int", section="io")
