#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, one command. It drives the two main paths once, through the
entry points a user calls, at the full width of a model the repo
supports, with seeded random weights and the depth cut:

  device    jax must report a TPU whose device_kind is in the peak table
  kernels   every Pallas family, compiled by Mosaic, vs its jnp reference
  serve     Llama-3-8B widths x 8 layers behind ServingEngine +
            RequestScheduler + ServingServer, driven over HTTP by
            ServingClient: bf16 cache, the same mix again, int8 cache
  serve4    (>= 4 devices, else "skipped: 1 device") the same model as
            four one-chip replicas behind the Router, and with mesh= tp=4
  train     llama_spmd.make_train_step, 4 steps on one repeated batch
  train4    (>= 4 devices) the same steps on a dp2 x tp2 mesh

    python chip_smoke.py

Any failed check raises; nothing is caught and carried past. Without an
accelerator it exits non-zero before printing a result — there is no CPU
fallback. The last two stdout lines are JSON objects: the summary
`{"ok": true, "device": {...}, "phases": {...}, "elapsed_s": ...,
"claim": null}` — this is a smoke test and claims nothing about speed
(seconds printed on the way are set-up bookkeeping, not measurements) —
and then, LAST, the result the driver parses, with exactly these keys:
`{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}`.

`--dry-run-cpu` is the explicit, never-default way to debug the command
itself where there is no chip: tiny shapes on 4 virtual CPU devices,
kernels interpreted, and EVERY line it prints starts with
`DRY RUN platform=cpu`, the final one included, so no output of it can
be taken for a chip result. `--phases a,b` runs a subset (after `device`,
which always runs) while debugging a failing phase; the summary marks
the rest "not run".
"""
from __future__ import annotations

import argparse
import concurrent.futures
import dataclasses
import gc
import json
import os
import sys
import time

PHASES = ("device", "kernels", "serve", "serve4", "train", "train4")

# First-token logprobs of the Pallas engines vs a use_pallas=False
# (dense jnp attention, bucketed prefill) engine on the same prompts.
# Logprobs, not tokens: with random weights rounding flips the argmax
# (1-2 of 16 prompts on the chip). The logits are bf16 and the top one
# lies in [4, 8), where one bf16 ulp is 2**-5 = 0.031, so every
# difference is a whole number of ulps. Measured on a v5e (my chip run,
# PR 22, these 16 prompts): two
# engines that run NO ragged kernel (dense jnp vs dense flash prefill)
# differ by up to 2 ulps. The ragged kernel of PR 26 (a run's rows
# against KV blocks of 256 tokens, summed in another order than the
# page-by-page reference) differs from the jnp engine by up to 2 ulps
# with the bf16 cache, under both pumps, and by up to 3 with the int8
# cache (my chip run, PR 26; the grid kernel it replaced read 3 and 2,
# PR 22): the same few ulps, so the bound stays 5 ulps for every
# configuration.
LOGIT_ULP = 2.0 ** -5
TOL_LOGPROB = 5 * LOGIT_ULP + 1e-3
# dp2 x tp2 vs one chip, same seed and batch: the loss is a mean of
# per-token f32 NLLs over bf16 activations; sharding changes reduction
# order inside the matmuls only (measured gap 0.0001 at loss 10.78, my
# four-chip run, PR 22).
TOL_MESH_LOSS = 0.05


@dataclasses.dataclass(frozen=True)
class Sizes:
    serve_cfg: dict
    serve_layers: int
    max_seqs: int
    max_seq_len: int
    burst_prompts: tuple      # 2 x max_seqs prompt lengths
    new_tokens: int
    train_cfg: dict
    train_batch: int
    train_seq: int
    fleet_requests: int
    fleet_prompt: tuple   # (min, max) prompt length
    fleet_new_tokens: int


REAL = Sizes(
    serve_cfg={},             # LlamaConfig.llama3_8b() widths, untouched
    serve_layers=8,           # ~2.8 B params = 5.6 GB bf16 with the untied head
    max_seqs=8,
    max_seq_len=2176,         # 136 pages: the longest prompt + its new tokens
    burst_prompts=(300, 420, 560, 700, 850, 1000, 1150, 1300,
                   1450, 1600, 1750, 1900, 2000, 330, 640, 1250),
    new_tokens=64,
    # the repo's on-chip training shape (bench.py): the 8B widths with
    # Adam state do not fit 16 GB
    train_cfg=dict(vocab_size=32000, hidden_size=2048,
                   intermediate_size=5504, num_hidden_layers=8,
                   num_attention_heads=16, num_key_value_heads=8,
                   max_position_embeddings=2048),
    train_batch=16,           # fits: 7.2 GB peak (chip run, PR 22)
    train_seq=2048,
    fleet_requests=48,    # P(a replica of 4 draws none) ~ 4e-6
    fleet_prompt=(96, 400),
    fleet_new_tokens=16,
)

TINY = Sizes(
    # head_dim 128, group 4; 4 KV heads so tp=4 divides them
    serve_cfg=dict(vocab_size=512, hidden_size=2048, intermediate_size=512,
                   num_attention_heads=16, num_key_value_heads=4,
                   max_position_embeddings=256),
    serve_layers=2,
    max_seqs=2,
    max_seq_len=96,
    burst_prompts=(20, 33, 48, 25),
    new_tokens=6,
    train_cfg=dict(vocab_size=512, hidden_size=256, intermediate_size=512,
                   num_hidden_layers=2, num_attention_heads=4,
                   num_key_value_heads=2, max_position_embeddings=128),
    train_batch=4,
    train_seq=128,
    fleet_requests=12,
    fleet_prompt=(12, 40),
    fleet_new_tokens=4,
)


class Smoke:
    def __init__(self, dry):
        self.dry = dry
        self.sz = TINY if dry else REAL
        self.t0 = time.perf_counter()
        self.status = {p: "not run" for p in PHASES}
        # carried from the one-chip phases to the four-chip ones
        self.serve_cfg = None
        self.serve_params = None
        self.first_logprobs = {}      # prompt length -> logprob (bf16 engine)
        self.train_first_loss = None

    def enough_devices(self, phase):
        import jax
        n = jax.device_count()
        if n < 4:
            self.phase_done(phase, f"skipped: {n} device")
        return n >= 4

    # -- reporting -----------------------------------------------------
    def say(self, msg):
        prefix = "DRY RUN platform=cpu " if self.dry else ""
        print(f"{prefix}{msg}", flush=True)

    def phase_done(self, phase, note="passed"):
        import jax
        gc.collect()          # engines are cyclic: free their pools now
        self.status[phase] = note
        stats = jax.devices()[0].memory_stats() or {}
        self.say(f"{phase}: {note.upper()} at {time.perf_counter() - self.t0:.0f}s"
                 f"  peak_bytes_in_use={stats.get('peak_bytes_in_use')}")

    # -- device --------------------------------------------------------
    def device(self):
        import importlib.metadata as md

        import jax
        import jaxlib
        from paddle_tpu.observability import compile_telemetry
        from paddle_tpu.observability import device_telemetry
        dev = jax.devices()[0]
        try:
            libtpu = md.version("libtpu")
        except md.PackageNotFoundError:
            libtpu = "absent"
        self.device_info = {"platform": dev.platform,
                            "kind": dev.device_kind,
                            "count": len(jax.devices())}
        self.say(f"device: platform={dev.platform} kind={dev.device_kind!r} "
                 f"count={len(jax.devices())} jax={jax.__version__} "
                 f"jaxlib={jaxlib.__version__} libtpu={libtpu}")
        self.say("device: compile cache "
                 f"{compile_telemetry.ensure_compile_cache()} "
                 "(JAX_COMPILATION_CACHE_DIR "
                 f"{'set' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'unset'})")
        flops, bw = device_telemetry.device_peaks()    # raises: unknown kind
        self.say(f"device: peaks for {device_telemetry.device_generation()}: "
                 f"{flops:.3g} FLOP/s bf16, {bw:.3g} B/s HBM "
                 "(device_telemetry.PEAK_SPECS)")
        self.phase_done("device")

    # -- kernels -------------------------------------------------------
    def kernels(self):
        sys.path.insert(0, os.path.join(os.path.dirname(
            os.path.abspath(__file__)), "tools"))
        import validate_tpu_kernels as V
        for name, check in V.CHECKS:
            t = time.perf_counter()
            errs = check(interpret=self.dry, small=self.dry)
            self.say(f"kernels: PASS {name} tol={V.TOL_BF16} "
                     f"({time.perf_counter() - t:.1f}s) {errs}")
        self.phase_done("kernels")

    # -- serve ---------------------------------------------------------
    def _prompt(self, n, salt=0):
        import numpy as np
        vocab = self.serve_cfg.vocab_size
        return np.random.RandomState(1000 * salt + n).randint(
            1, vocab, n).tolist()

    def _build_serve_model(self):
        import jax.numpy as jnp
        from paddle_tpu.models import llama_spmd
        from paddle_tpu.models.llama import LlamaConfig
        base = LlamaConfig(**self.sz.serve_cfg) if self.sz.serve_cfg \
            else LlamaConfig.llama3_8b()
        self.serve_cfg = dataclasses.replace(
            base, num_hidden_layers=self.sz.serve_layers)
        self.serve_params = llama_spmd.init_params(
            self.serve_cfg, seed=0, dtype=jnp.bfloat16)
        c = self.serve_cfg
        self.say(f"serve: model hidden={c.hidden_size} "
                 f"ffn={c.intermediate_size} heads={c.num_attention_heads}/"
                 f"{c.num_key_value_heads} vocab={c.vocab_size} "
                 f"layers={c.num_hidden_layers} (depth cut), bf16, seed 0")

    def _engine(self, **kw):
        import jax.numpy as jnp
        from paddle_tpu.models.llama_serving import ServingEngine
        if self.dry:
            # on the chip the engine picks Pallas by itself; the CPU dry
            # run has to ask for the kernels, interpreted
            kw = {"use_pallas": True, "interpret": True, **kw}
        kw.setdefault("max_seqs", self.sz.max_seqs)
        return ServingEngine(self.serve_params, self.serve_cfg,
                             max_seq_len=self.sz.max_seq_len,
                             page_size=16, dtype=jnp.bfloat16, **kw)

    def _burst(self, client, prompts, new_tokens, workers):
        """`prompts` concurrently over HTTP; every one must finish with
        exactly the asked token count. Returns the response dicts."""
        def one(p):
            return client.complete(p, max_tokens=new_tokens, logprobs=True)
        with concurrent.futures.ThreadPoolExecutor(workers) as pool:
            outs = list(pool.map(one, prompts))
        for p, out in zip(prompts, outs):
            assert out["state"] == "done" and out["n"] == new_tokens \
                and len(out["tokens"]) == new_tokens, \
                f"prompt of {len(p)}: {out['state']} n={out.get('n')}"
            assert all(0 <= t < self.serve_cfg.vocab_size
                       for t in out["tokens"])
        return outs

    def _assert_healthy(self, tag, stats):
        """`stats`: one scheduler's /healthz payload. A refused kernel or
        a crashed step shows up here — the pump turns a step exception
        into a warm restart, never into a client-visible error."""
        from paddle_tpu.observability import flight_recorder
        bad = (stats["requests"]["failed"], stats["recovery"]["restarts"],
               stats["recovery"]["quarantined"])
        assert bad == (0, 0, 0), (
            f"{tag}: failed/restarts/quarantined={bad}; "
            f"{flight_recorder.RECORDER.events(kind='engine.restart')[-2:]}")

    def _serve_config(self, tag, cache):
        from paddle_tpu.observability import compile_telemetry
        from paddle_tpu.serving import (RequestScheduler, ServingClient,
                                        ServingServer)
        sz = self.sz
        before = compile_telemetry.REGISTRY.totals()
        engine = self._engine(cache_dtype=cache)
        assert engine._use_pallas is True
        assert engine._interpret is self.dry
        assert engine.ragged and engine.tok_buf is not None
        server = ServingServer(RequestScheduler(engine), port=0).start()
        try:
            client = ServingClient(port=server.port, timeout=900.0)
            lens = sz.burst_prompts
            first = client.complete(self._prompt(lens[0], salt=1),
                                    max_tokens=8, logprobs=True)
            assert first["state"] == "done" and first["n"] == 8, first
            streamed, final = [], None
            for ev in client.stream_complete(self._prompt(lens[1], salt=2),
                                             max_tokens=12):
                streamed += ev.get("tokens", []) if not ev.get("done") else []
                final = ev if ev.get("done") else final
            assert final is not None and final["state"] == "done" \
                and streamed == final["tokens"] and len(streamed) == 12, final
            warm = compile_telemetry.REGISTRY.totals()
            outs = self._burst(client, [self._prompt(n) for n in lens],
                               sz.new_tokens, workers=len(lens))
            health = client.healthz()
        finally:
            server.stop()
        self._assert_healthy(f"serve[{tag}]", health)
        assert health["requests"]["completed"] == len(lens) + 2, health
        after = compile_telemetry.REGISTRY.totals()
        self.say(
            f"serve[{tag}]: PASS blocking + streamed + burst of {len(lens)} "
            f"(prompts {min(lens)}..{max(lens)}, {sz.new_tokens} new) all "
            f"done; device_steps={health['device_steps']} "
            f"preemptions={health['preemptions']} failed=0 restarts=0 "
            f"quarantined=0; compiles={after['compiles'] - before['compiles']}"
            f" in {after['compile_seconds'] - before['compile_seconds']:.1f}s"
            f" (cache hits {after['cache_hits'] - before['cache_hits']}), "
            f"during the burst {after['compiles'] - warm['compiles']}")
        return ({n: out["logprobs"][0] for n, out in zip(lens, outs)},
                after["compiles"] - before["compiles"],
                after["compiles"] - warm["compiles"])

    def _reference_logprobs(self):
        """First-token logprobs of the burst prompts from an engine that
        runs no Pallas kernel: bucketed prefill, dense jnp attention."""
        from paddle_tpu.models.llama_serving import Request
        engine = self._engine(use_pallas=False, interpret=False,
                              ragged=False, max_seqs=2)
        assert engine._use_pallas is False
        lens = self.sz.burst_prompts
        for n in lens:
            engine.submit(Request(n, self._prompt(n), max_new_tokens=1,
                                  logprobs=True))
        done = {r.rid: r.logprobs[0] for r in engine.run()}
        assert sorted(done) == sorted(lens)
        return done

    def _compare_logprobs(self, tag, got, ref, what="the no-Pallas engine"):
        gaps = [abs(got[n] - ref[n]) for n in sorted(ref)]
        assert max(gaps) < TOL_LOGPROB, (
            f"{tag}: first-token logprob off by {max(gaps)} "
            f"(tol {TOL_LOGPROB}); per prompt {gaps}")
        self.say(f"{tag}: PASS first-token logprobs within {max(gaps):.4f} "
                 f"({max(gaps) / LOGIT_ULP:.1f} bf16 ulps of the logit) of "
                 f"{what} on {len(ref)} prompts (tol {TOL_LOGPROB:.3f})")

    def serve(self):
        self._build_serve_model()
        lp_bf16, _, _ = self._serve_config("bf16 cache", None)
        # the same mix again on a new engine: a repeated mix must not
        # compile anything (every engine program is shape-stable)
        lp_again, compiles, _ = self._serve_config("bf16 cache, again", None)
        assert compiles == 0, f"repeated mix compiled {compiles} programs"
        lp_int8, _, in_burst = self._serve_config("int8 cache", "int8")
        assert in_burst == 0, f"int8 burst compiled {in_burst} programs"
        ref = self._reference_logprobs()
        self._compare_logprobs("serve[bf16 cache]", lp_bf16, ref)
        self._compare_logprobs("serve[bf16 cache, again]", lp_again, ref)
        self._compare_logprobs("serve[int8 cache]", lp_int8, ref)
        self.first_logprobs = lp_bf16
        self.phase_done("serve")

    # -- train ---------------------------------------------------------
    def _train(self, mesh, tag):
        """4 steps on one repeated batch; returns the losses."""
        import jax
        import jax.numpy as jnp
        import numpy as np
        from paddle_tpu.models import llama_spmd
        from paddle_tpu.models.llama import LlamaConfig
        from paddle_tpu.observability import compile_telemetry
        from paddle_tpu.ops.flash_attention import pallas_disabled
        sz = self.sz
        cfg = LlamaConfig(**sz.train_cfg)
        params = llama_spmd.place_params(
            llama_spmd.init_params(cfg, seed=0, dtype=jnp.bfloat16),
            cfg, mesh)
        opt = llama_spmd.init_opt_state(params)
        step = llama_spmd.make_train_step(cfg, mesh, remat=True, lr=3e-4,
                                          fused_ce=True)
        rng = np.random.RandomState(0)
        batch = (rng.randint(0, cfg.vocab_size, (sz.train_batch, sz.train_seq)),
                 rng.randint(0, cfg.vocab_size, (sz.train_batch, sz.train_seq)))
        if not self.dry:
            # the attention really is the Pallas kernel: no escape hatch
            # set, and the traced step carries pallas_calls
            assert not pallas_disabled(), "PT_DISABLE_PALLAS is set"
            jaxpr = str(step.trace(params, opt, jnp.asarray(0), batch).jaxpr)
            assert "pallas_call" in jaxpr, "train step has no pallas_call"
        hits = compile_telemetry.REGISTRY.totals()["cache_hits"]
        losses, t = [], time.perf_counter()
        for i in range(4):
            params, opt, loss = step(params, opt, jnp.asarray(i), batch)
            losses.append(float(loss))
            if i == 0:
                first_s = time.perf_counter() - t
        assert all(np.isfinite(losses)), losses
        assert all(b < a for a, b in zip(losses, losses[1:])), \
            f"{tag}: loss not falling on a repeated batch: {losses}"
        self.say(f"{tag}: PASS 4 steps batch={sz.train_batch} "
                 f"seq={sz.train_seq} bf16 remat fused_ce mesh="
                 f"{dict(mesh.shape)} losses="
                 f"{[round(x, 4) for x in losses]} first call "
                 f"{first_s:.1f}s (compile included; cache hits "
                 f"{compile_telemetry.REGISTRY.totals()['cache_hits'] - hits})")
        return losses

    def train(self):
        import jax
        import numpy as np
        from jax.sharding import Mesh
        self.serve_params = None      # 5.6 GB the train step needs
        mesh = Mesh(np.asarray(jax.devices()[:1]), ("dp",))
        self.train_first_loss = self._train(mesh, "train")[0]
        self.phase_done("train")

    # -- four chips ----------------------------------------------------
    def _replicas(self):
        from paddle_tpu.serving import (Router, ServingClient, ServingServer,
                                        build_replicas)
        sz = self.sz
        replicas = build_replicas(lambda i: self._engine(), 4)
        pools = [r.engine.k_pool.devices() for r in replicas]
        weights = [r.engine.params["lm_head"].devices() for r in replicas]
        assert len(set(map(frozenset, pools))) == 4 and pools == weights, \
            f"replicas share devices: pools={pools} weights={weights}"
        server = ServingServer(Router(replicas), port=0).start()
        try:
            client = ServingClient(port=server.port, timeout=900.0)
            lo, hi = sz.fleet_prompt
            lens = [lo + (hi - lo) * i // (sz.fleet_requests - 1)
                    for i in range(sz.fleet_requests)]
            self._burst(client, [self._prompt(n, salt=3) for n in lens],
                        sz.fleet_new_tokens, workers=16)
            stats = [r.stats() for r in replicas]
        finally:
            server.stop()
        done = [s["requests"]["completed"] for s in stats]
        for s in stats:
            self._assert_healthy(f"replica {s['replica_id']}", s)
        assert all(done) and sum(done) == len(lens), \
            f"requests completed per replica: {done}"
        assert [r.engine.k_pool.devices() for r in replicas] == pools
        self.say(f"serve4[replicas]: PASS Router over 4 replicas on "
                 f"{sorted(str(next(iter(p))) for p in pools)}; completed per "
                 f"replica {done}")

    def _tp_serve(self):
        from paddle_tpu.models.llama_serving import Request
        from paddle_tpu.parallel.mesh import create_mesh
        import jax
        mesh = create_mesh({"tp": 4}, devices=jax.devices()[:4])
        engine = self._engine(mesh=mesh, max_seqs=2)
        assert engine._use_pallas is True and not engine.ragged
        assert len(engine.k_pool.devices()) == 4
        lens = self.sz.burst_prompts[:4]
        for n in lens:
            engine.submit(Request(n, self._prompt(n),
                                  max_new_tokens=self.sz.fleet_new_tokens,
                                  logprobs=True))
        done = {r.rid: r for r in engine.run()}
        assert sorted(done) == sorted(lens)
        assert all(len(r.output) == self.sz.fleet_new_tokens
                   for r in done.values())
        self._compare_logprobs(
            "serve4[tp=4]", {n: done[n].logprobs[0] for n in lens},
            {n: self.first_logprobs[n] for n in lens},
            what="the one-chip engine")

    def serve4(self):
        if not self.enough_devices("serve4"):
            return
        assert self.first_logprobs, "serve4 compares against serve: run it"
        self._replicas()
        gc.collect()
        self._tp_serve()
        self.phase_done("serve4")

    def train4(self):
        if not self.enough_devices("train4"):
            return
        assert self.train_first_loss is not None, \
            "train4 compares against train: run it"
        import jax
        from paddle_tpu.parallel.mesh import create_mesh
        mesh = create_mesh({"dp": 2, "tp": 2}, devices=jax.devices()[:4])
        loss = self._train(mesh, "train4")[0]
        gap = abs(loss - self.train_first_loss)
        assert gap < TOL_MESH_LOSS, \
            f"first-step loss {loss} vs one chip {self.train_first_loss}"
        self.say(f"train4: PASS first-step loss within {gap:.4f} of the "
                 f"one-chip run (tol {TOL_MESH_LOSS})")
        self.phase_done("train4")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--dry-run-cpu", action="store_true",
                    help="debug the command at tiny size on 4 virtual CPU "
                         "devices, kernels interpreted; every line is "
                         "marked DRY RUN and nothing it prints is a result")
    ap.add_argument("--phases", default=",".join(PHASES),
                    help=f"comma-separated subset of {PHASES}")
    args = ap.parse_args()
    phases = ["device"] + args.phases.split(",")
    unknown = set(phases) - set(PHASES)
    if unknown:
        ap.error(f"unknown phases {sorted(unknown)}")
    if args.dry_run_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                   " --xla_force_host_platform_device_count=4")

    import jax
    platform = jax.devices()[0].platform
    if platform != "tpu" and not args.dry_run_cpu:
        sys.exit(f"chip_smoke: jax found platform {platform!r}, not 'tpu'. "
                 "This script proves the system on an accelerator and has "
                 "no CPU fallback (--dry-run-cpu debugs the command only).")
    import paddle_tpu  # noqa: F401 — as a user does; also turns x64 on

    smoke = Smoke(args.dry_run_cpu)
    for phase in PHASES:
        if phase in phases:
            getattr(smoke, phase)()
    summary = {"ok": True, "device": smoke.device_info,
               "phases": smoke.status,
               "elapsed_s": round(time.perf_counter() - smoke.t0, 1),
               "claim": None}
    smoke.say(json.dumps(summary))
    # the driver reads the LAST line and accepts these keys and no others
    smoke.say(json.dumps({"ok": True, "device": smoke.device_info}))


if __name__ == "__main__":
    main()
