"""Repo-level lint configuration.

The interesting judgement calls — *which* non-jitted code counts as a
hot path, *where* an eager `block_until_ready` is legitimate, *which*
packages get lock-discipline analysis — live here rather than in the
rules, so a deployment can retarget tpulint with a JSON file instead
of forking rule code (`tools/tpulint.py --config my.json`).

All patterns are `fnmatch` globs matched against the forward-slash
path of the scanned file (both the full path and every suffix of it,
so `serving/*.py` matches `/root/repo/paddle_tpu/serving/scheduler.py`).
"""
from __future__ import annotations

import fnmatch
import json
from dataclasses import dataclass, field

from .engine import Severity


def _match(patterns, path):
    p = path.replace("\\", "/")
    parts = p.split("/")
    cands = {p} | {"/".join(parts[i:]) for i in range(len(parts))}
    return any(fnmatch.fnmatch(c, pat) for pat in patterns for c in cands)


@dataclass
class LintConfig:
    # Modules whose plain (non-jit) functions still count as hot for
    # TPL001's host-sync checks: the serving runtime's step/pump loops
    # run per decode step, so a stray device->host pull there costs a
    # host<->device round trip per token.
    hot_modules: list = field(default_factory=list)
    # function (or Class.method) names inside hot_modules that form
    # the actual per-step loop; empty = every function in the module.
    hot_functions: list = field(default_factory=list)
    # Where an eager block_until_ready is the *point* (benchmarks,
    # profilers, device warm-up) rather than a pipeline stall.
    bench_paths: list = field(default_factory=list)
    # Packages that get TPL004 lock-discipline analysis.
    lock_scope: list = field(default_factory=list)
    # Files skipped entirely.
    exclude: list = field(default_factory=list)
    # Per-rule severity overrides: {"TPL002": "info"}.
    severity: dict = field(default_factory=dict)
    # The sanctioned async result reader(s) of the serving pump loop:
    # the ONLY functions in a hot module allowed to call
    # jax.device_get. TPL001 skips findings inside them AND flags any
    # device_get in a hot module outside them — the pipelined pump's
    # invariant ("one batched read, issued a step behind") is enforced
    # by lint, not convention.
    sanctioned_sync: list = field(default_factory=list)
    # Packages whose classes/threads enter the whole-program project
    # index (TPL007 lock order, TPL008 ownership, TPL009 blocking).
    concurrency_scope: list = field(default_factory=list)
    # Dotted-name fnmatch patterns of calls that block on the network
    # or a queue — TPL009 flags them under a held lock.
    blocking_calls: list = field(default_factory=list)
    # Lock attr-name globs that exist to serialize one IO channel
    # (socket write mutexes); TPL009 ignores them by design.
    io_locks: list = field(default_factory=list)
    # Packages migrated to the paddle_tpu._env accessors: TPL010 bans
    # raw os.environ reads of declared knobs there.
    env_migrated: list = field(default_factory=list)
    # Glob patterns (relative to the invocation cwd) of the markdown
    # files holding the pt_* metric tables TPL011 cross-checks.
    metrics_docs: list = field(default_factory=list)

    # ---- queries used by the rules -----------------------------------
    def is_hot_module(self, path):
        return _match(self.hot_modules, path)

    def is_hot_function(self, qualname):
        """qualname is 'func' or 'Class.method'."""
        if not self.hot_functions:
            return True
        leaf = qualname.rsplit(".", 1)[-1]
        return any(fnmatch.fnmatch(qualname, pat)
                   or fnmatch.fnmatch(leaf, pat)
                   for pat in self.hot_functions)

    def is_bench_path(self, path):
        return _match(self.bench_paths, path)

    def is_sanctioned_sync(self, qualname):
        """qualname is 'func' or 'Class.method' — the async result
        reader(s) allowed to device_get in the pump loop."""
        leaf = qualname.rsplit(".", 1)[-1]
        return any(fnmatch.fnmatch(qualname, pat)
                   or fnmatch.fnmatch(leaf, pat)
                   for pat in self.sanctioned_sync)

    def in_lock_scope(self, path):
        return _match(self.lock_scope, path)

    def in_concurrency_scope(self, path):
        return _match(self.concurrency_scope, path)

    def in_env_migrated(self, path):
        return _match(self.env_migrated, path)

    def is_excluded(self, path):
        return _match(self.exclude, path)

    def severity_for(self, rule_id, default):
        s = self.severity.get(rule_id)
        return Severity.parse(s) if s is not None else default

    # ---- construction -------------------------------------------------
    @classmethod
    def default(cls):
        return cls(
            hot_modules=[
                "paddle_tpu/serving/*.py",
                "paddle_tpu/models/llama_serving.py",
                # the pulse plane samples on a daemon thread riding the
                # scrape cadence — a device pull there would serialize
                # against the pump's dispatch stream just the same
                "paddle_tpu/observability/pulse.py",
                # fleet observability runs on router/worker daemon
                # threads between rpc round trips — same rule
                "paddle_tpu/observability/fleet_obs.py",
            ],
            hot_functions=[
                # ServingEngine per-token loop + its helpers
                "ServingEngine.step", "ServingEngine._spec_step",
                "ServingEngine._prefill_step", "ServingEngine._admit",
                "ServingEngine._seed_first_token",
                # device-side sampler + pipelined step pair (ROADMAP
                # item 4): these ARE the per-token hot loop now
                "ServingEngine.step_launch", "ServingEngine.step_finish",
                "ServingEngine.run_pipelined",
                "ServingEngine._note_launch_gap",
                # unified ragged step: flat descriptor builder + its
                # finish twin are the default per-wave hot loop
                "ServingEngine._ragged_launch",
                "ServingEngine._ragged_finish",
                "ServingEngine._bucket_for",
                # lean epilogue (ISSUE 12): the spec rejection
                # sampler's lazy distribution-row pull runs inside the
                # acceptance loop — sync discipline applies (its one
                # read rides _fetch_results)
                "ServingEngine._spec_row_dist",
                # disaggregated handoff (ISSUE 13): harvest runs once
                # per step; export/import move KV pages through the
                # kvtier copy thread's explicit fences — their device
                # transfers must never look like a stray sync
                "ServingEngine._harvest_handoffs",
                "ServingEngine._export_handoff",
                "ServingEngine._import_handoff",
                # scheduler pump + publish run once per engine step
                "RequestScheduler._pump", "RequestScheduler._publish",
                "RequestScheduler._feed_locked",
                "RequestScheduler._step_pipelined",
                "RequestScheduler._finish_pending",
                "RequestScheduler._drain_needed",
                # timeline/SLO plane (ISSUE 14): host-clock-only by
                # contract — marks stamp on the pump and engine loops,
                # finalize judges SLOs, the sentinel's note() runs per
                # step. None of these may ever touch the device.
                "Timeline.mark", "Timeline.count",
                "Timeline.segments", "Timeline.phases",
                "StepAnomalySentinel.note",
                "RequestScheduler._finalize",
                "RequestScheduler._account_slo",
                "RequestScheduler._timeline_entry",
                # pulse plane (ISSUE 15): sampler + bundle writer run
                # on the pulse/scrape threads against host-side
                # snapshots only — zero device syncs by lint, so the
                # observability plane can never stall the pump
                "PulseSampler.sample",
                "PulsePlane.tick",
                "PulsePlane._check_triggers",
                "PulsePlane._write_bundle",
                "RequestScheduler._pulse_snapshot",
                "RequestScheduler._book_depth_locked",
                # fleet plane (ISSUE 16): the bulk-channel serving
                # threads stream tokens and ship exported (host-side
                # numpy) KV pages per request, and the spill/fetch pair
                # runs on the kvtier path — all must stay pure
                # host+socket code with zero device pulls
                "FleetWorker._serve_stream",
                "FleetWorker._serve_handoff",
                "FleetPages._spill_loop",
                "FleetPages.fetch_missing",
                "RemoteRequest._read_loop",
                # fleet observability: the obs poll loop + the pull
                # paths it drives run per tick on the router, and the
                # estimator update runs per rpc reply
                "ClockSkewEstimator.sample",
                "FleetWorker.obs_snapshot",
                "FleetPlane._obs_loop",
                "FleetPlane.obs_sections",
            ],
            bench_paths=[
                "bench*.py", "tools/*.py", "tests/*.py", "examples/*.py",
                "paddle_tpu/profiler/*.py", "paddle_tpu/utils/__init__.py",
                "paddle_tpu/device/*.py",
            ],
            lock_scope=["paddle_tpu/serving/*.py"],
            exclude=[],
            severity={},
            # the engine's batched reader is the one sanctioned
            # device->host sync of the whole step loop
            sanctioned_sync=["ServingEngine._fetch_results"],
            # the thread-heavy planes: serving runtime + the
            # observability daemons that scrape it
            concurrency_scope=[
                "paddle_tpu/serving/*.py",
                "paddle_tpu/observability/*.py",
            ],
            blocking_calls=[
                # raw socket ops (wire.py and friends)
                "*.sendall", "*.recv", "*.recv_into", "*.accept",
                "*.connect", "*.create_connection",
                # rpc layer round trips
                "rpc_sync", "*.rpc_sync",
                "*.store.get", "*.store.set", "*.store.wait",
                "*.all_worker_infos",
                # stdlib network fetches
                "*.urlopen",
            ],
            io_locks=["*_wlock", "*_send_lock", "*_io_lock"],
            env_migrated=[
                "paddle_tpu/serving/*.py",
                "paddle_tpu/observability/*.py",
            ],
            metrics_docs=["docs/*.md"],
        )

    @classmethod
    def from_json(cls, path):
        """Overlay a JSON config file onto the defaults; list fields
        replace, the severity dict merges."""
        with open(path, "r", encoding="utf-8") as f:
            data = json.load(f)
        cfg = cls.default()
        list_keys = ("hot_modules", "hot_functions", "bench_paths",
                     "lock_scope", "exclude", "sanctioned_sync",
                     "concurrency_scope", "blocking_calls", "io_locks",
                     "env_migrated", "metrics_docs")
        for key in list_keys:
            if key in data:
                setattr(cfg, key, list(data[key]))
        if "severity" in data:
            cfg.severity.update(data["severity"])
        unknown = set(data) - set(list_keys) - {"severity"}
        if unknown:
            raise ValueError(f"tpulint config: unknown keys {sorted(unknown)}")
        return cfg


DEFAULT_CONFIG = LintConfig.default()
