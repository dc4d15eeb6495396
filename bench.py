"""Benchmark: Llama pretrain step on one TPU chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.
Metric: tokens/sec/chip on a Llama block-scaled pretrain step (bf16,
flash attention, remat, AdamW w/ fp32 master) + estimated MFU vs chip
peak. vs_baseline = MFU / 0.40 (BASELINE.json north-star: ≥40% MFU).
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np


def _data_rng():
    """Benchmark input data: a fixed seed (PT_BENCH_DATA_SEED overrides)
    so two runs of one config see the same tokens. Shared by bench.py
    and bench_models.py."""
    return np.random.RandomState(
        int(os.environ.get("PT_BENCH_DATA_SEED", "0")))


def _tuned_defaults():
    """Winning config from tools/autotune.py, if one was ever captured."""
    here = os.path.dirname(os.path.abspath(__file__))
    try:
        with open(os.path.join(here, "TUNED.json")) as f:
            data = json.load(f)
    except (OSError, json.JSONDecodeError):
        return {}
    if data.get("smoke"):
        # a smoke-mode search wrote here (PT_TUNE_OUT override or a
        # copied TUNED.smoke.json) — fake numbers must not become the
        # on-chip defaults
        return {}
    return data.get("best", {})


def _bench_backend():
    """jax's backend for a bench run: "tpu", or "cpu" when PT_BENCH_CPU=1
    asks for the explicit smoke. Anything else exits non-zero — one
    process, one chip, no fallback. Shared with bench_models.py."""
    import jax
    cpu_smoke = os.environ.get("PT_BENCH_CPU") == "1"
    if cpu_smoke:
        jax.config.update("jax_platforms", "cpu")
    backend = jax.default_backend()
    if backend != "tpu" and not cpu_smoke:
        sys.exit(f"{os.path.basename(sys.argv[0])}: jax runs on "
                 f"{backend!r}, not a TPU, and there is no fallback. "
                 "PT_BENCH_CPU=1 is the explicit CPU smoke (counts, not "
                 "rates).")
    return backend


def main():
    backend = _bench_backend()
    on_tpu = backend == "tpu"
    import jax
    import jax.numpy as jnp

    # apply tuned flash block sizes BEFORE paddle_tpu imports: the kernel
    # module reads PT_FLASH_BLOCK_Q/K at import time
    tuned = _tuned_defaults() if on_tpu else {}
    for var, key in (("PT_FLASH_BLOCK_Q", "block_q"),
                     ("PT_FLASH_BLOCK_K", "block_k")):
        if var not in os.environ and key in tuned:
            os.environ[var] = str(tuned[key])

    from paddle_tpu.models.llama import LlamaConfig
    from paddle_tpu.models import llama_spmd as M
    from paddle_tpu.observability.device_telemetry import (
        PEAK_SPECS, device_generation)
    gen = device_generation()       # an unknown device_kind raises
    peak = PEAK_SPECS[gen].flops

    if on_tpu:
        cfg = LlamaConfig(vocab_size=32000, hidden_size=2048,
                          intermediate_size=5504, num_hidden_layers=8,
                          num_attention_heads=16, num_key_value_heads=8,
                          max_position_embeddings=2048)
        # defaults: TUNED.json (autotuner winner) when present, else the
        # best hand-measured config on v5e (r2 sweep: batch 16 →
        # 23.5k tok/s; batch 8 worse; remat=false OOMs)
        batch = int(os.environ.get("PT_BENCH_BATCH", tuned.get("batch", 16)))
        seq = int(os.environ.get("PT_BENCH_SEQ", tuned.get("seq", 2048)))
        iters, dtype = 10, jnp.bfloat16
        remat = os.environ.get("PT_BENCH_REMAT",
                               str(tuned.get("remat", "true")).lower())
        remat = {"true": True, "false": False}.get(remat, remat)
    else:  # PT_BENCH_CPU=1 smoke
        cfg = LlamaConfig.tiny(vocab=512, hidden=128, layers=2, heads=4,
                               kv_heads=2, ffn=256)
        batch, seq, iters, dtype = 2, 128, 3, jnp.float32
        remat = True

    n_micro = int(os.environ.get("PT_BENCH_NMICRO",
                                 str(tuned.get("n_micro", 0)))) or None
    # fused linear+CE head (no (B,S,V) logits materialization) — the
    # biggest single-chip MFU lever at vocab 32000; swept by autotune
    fused_ce = os.environ.get(
        "PT_FUSED_CE", "1" if tuned.get("fused_ce") else "0") == "1"
    if n_micro and batch % n_micro:
        # an indivisible n_micro would trip the grad-accum assert
        # during trace
        print(f"# n_micro={n_micro} does not divide batch={batch}; "
              "disabling grad accumulation", file=sys.stderr)
        n_micro = None
    from jax.sharding import Mesh
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("dp",))
    params = M.init_params(cfg, seed=0, dtype=dtype)
    opt = M.init_opt_state(params)
    step = M.make_train_step(cfg, mesh, n_micro=n_micro, remat=remat, lr=3e-4,
                             fused_ce=fused_ce)

    rng = _data_rng()
    x = rng.randint(0, cfg.vocab_size, (batch, seq))
    y = rng.randint(0, cfg.vocab_size, (batch, seq))
    # PT_BENCH_DOCS=N: packed-document pretrain — N equal documents per
    # row, cross-document attention blocked by the flashmask kernel
    # (block-skip turns the saved attention into real tok/s)
    docs = int(os.environ.get("PT_BENCH_DOCS", "0"))
    if docs > 0:
        assert seq % docs == 0, f"seq {seq} not divisible by docs {docs}"
        doc_ids = np.repeat(np.arange(docs),
                            seq // docs)[None].repeat(batch, 0)
        data = (x, y, doc_ids)
    else:
        data = (x, y)

    # compile + warmup. A kernel the compiler refuses, or a config
    # that does not fit, is an error: nothing is retried on another path
    params, opt, loss = step(params, opt, jnp.asarray(0), data)
    jax.block_until_ready(loss)

    t0 = time.perf_counter()
    for i in range(iters):
        params, opt, loss = step(params, opt, jnp.asarray(i + 1), data)
    jax.block_until_ready(loss)
    dt = (time.perf_counter() - t0) / iters

    tokens_per_step = batch * seq
    tok_per_sec = tokens_per_step / dt

    # Model FLOPs/token — STRICT convention (VERDICT r2 item 2):
    #   * 6*N counts matmul parameters only. The input-embedding lookup
    #     is a gather, not a matmul → EXCLUDED. The lm_head projection
    #     is a real matmul → kept (one V*H term, not two).
    #   * attention is charged at the FULL (non-causal) 12*L*H*S
    #     fwd+bwd even though the kernel is causal, so numbers stay
    #     comparable with the reference's convention.
    # mfu_legacy (both V*H terms) is also printed: it is the convention
    # rounds 1-2 reported, kept for cross-round comparability.
    H, L, F, V = (cfg.hidden_size, cfg.num_hidden_layers,
                  cfg.intermediate_size, cfg.vocab_size)
    kv = cfg.num_key_value_heads * (H // cfg.num_attention_heads)
    n_layers = L * (2 * H * H + 2 * H * kv + 3 * H * F)
    attn = 12 * L * H * seq
    flops_strict = 6 * (n_layers + V * H) + attn
    flops_legacy = 6 * (n_layers + 2 * V * H) + attn
    mfu = flops_strict * tok_per_sec / peak
    mfu_legacy = flops_legacy * tok_per_sec / peak

    attn_label = f"flashmask-{docs}doc" if docs > 0 else "flash-attn"
    remat_label = {True: "remat", False: "no-remat"}.get(
        remat, f"remat-{remat}")
    result = {
        "metric": f"llama-{f'{seq}x{batch}' if on_tpu else 'tiny'} pretrain "
                  f"tokens/sec/chip ({gen}, bf16, {attn_label}, "
                  f"{remat_label})",
        "value": round(tok_per_sec, 1),
        "unit": "tokens/s",
        "vs_baseline": round(mfu / 0.40, 4),
        "extra": {"step_time_s": round(dt, 4), "mfu": round(mfu, 4),
                  "mfu_legacy": round(mfu_legacy, 4),
                  "flops_convention": "6N excl. embedding gather (lm_head "
                                      "kept); attention full 12LHS on a "
                                      "causal kernel",
                  "loss": float(loss), "backend": backend,
                  "fused_ce": fused_ce},
    }
    print(json.dumps(result))
    # perf-regression history: tests/test_perf_guard.py compares the last
    # two same-backend/same-config entries
    try:
        hist = dict(result, ts=time.time(), batch=batch,
                    seq=seq, remat=str(remat), n_micro=n_micro,
                    docs=docs or None, fused_ce=fused_ce,
                    block_q=os.environ.get("PT_FLASH_BLOCK_Q"),
                    block_k=os.environ.get("PT_FLASH_BLOCK_K"))
        here = os.path.dirname(os.path.abspath(__file__))
        with open(os.path.join(here, "BENCH_HISTORY.jsonl"), "a") as f:
            f.write(json.dumps(hist) + "\n")
    except OSError:
        pass


if __name__ == "__main__":
    main()
