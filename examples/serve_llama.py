"""Production serving runtime over the continuous-batching engine:
HTTP frontend + scheduler + metrics (paddle_tpu.serving).

    python examples/serve_llama.py                  # demo: serve + drive
    python examples/serve_llama.py --port 8000 --forever   # stay up
    python examples/serve_llama.py --spec 4 --cache int8

The demo starts the server, drives it with the stdlib client — a
blocking completion, a streamed one, a burst that exercises queueing —
prints the metrics the run produced, and shuts down gracefully
(in-flight requests drain). The wire protocol is tokenizer-free:
prompts and completions are token-id lists (docs/serving.md).
"""
from __future__ import annotations

import os as _os
import sys as _sys

_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))

_os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
import argparse
import threading

import numpy as np

from paddle_tpu.models.llama import LlamaConfig
from paddle_tpu.models import llama_spmd as M
from paddle_tpu.models.llama_serving import ServingEngine
from paddle_tpu.serving import RequestScheduler, ServingClient, ServingServer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=0,
                    help="0 = ephemeral (printed at startup)")
    ap.add_argument("--forever", action="store_true",
                    help="serve until Ctrl-C instead of running the demo")
    ap.add_argument("--max-seqs", type=int, default=4)
    ap.add_argument("--max-queue", type=int, default=32)
    ap.add_argument("--spec", type=int, default=0,
                    help="speculative chunk width G (0 = plain decode)")
    ap.add_argument("--cache", choices=["fp", "int8"], default="fp")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="share KV pages across prompts with a common "
                         "prefix; admissions prefill only their suffix")
    ap.add_argument("--host-tier-mb", type=int, default=0,
                    help="MB of host RAM for the KV spill tier: prefix-"
                         "cache evictions demote pages to host memory "
                         "instead of discarding them (implies "
                         "--prefix-cache); 0 disables")
    ap.add_argument("--replicas", type=int, default=0,
                    help="N>1: router mode — N independent engine "
                         "replicas behind the prefix-affinity router "
                         "(health-aware failover, per-replica /metrics "
                         "labels); implies --prefix-cache per replica")
    ap.add_argument("--faults", default=None, metavar="SPEC",
                    help="seeded fault plan for chaos drills, e.g. "
                         "'step_launch:raise@4' (docs/reliability.md "
                         "has the grammar); PT_FAULTS is the env "
                         "spelling. Crashed steps warm-restart the "
                         "engine and requeue unstreamed requests")
    args = ap.parse_args()

    cfg = LlamaConfig.tiny(vocab=512, hidden=128, layers=2, heads=8,
                           kv_heads=4, ffn=256, seq=256)
    params = M.init_params(cfg, seed=0)

    def make_engine(_i=0):
        from paddle_tpu.serving import FaultPlan
        return ServingEngine(
            params, cfg, max_seqs=args.max_seqs, max_seq_len=256,
            page_size=16,
            cache_dtype="int8" if args.cache == "int8" else None,
            spec_decode=args.spec,
            prefix_cache=(args.prefix_cache or args.replicas > 1
                          or args.host_tier_mb > 0),
            host_tier_bytes=args.host_tier_mb << 20,
            faults=FaultPlan(args.faults) if args.faults else None)

    if args.replicas > 1:
        from paddle_tpu.serving import Router, build_replicas
        sched = Router(build_replicas(make_engine, args.replicas,
                                      max_queue=args.max_queue))
        mode = f"router x{args.replicas} replicas"
    else:
        sched = RequestScheduler(make_engine(), max_queue=args.max_queue)
        mode = "single engine"
    srv = ServingServer(sched, host=args.host, port=args.port).start()
    print(f"serving on {srv.url} [{mode}]  "
          f"(POST /v1/completions, GET /healthz, GET /readyz, "
          f"GET /metrics)")

    if args.forever:
        try:
            threading.Event().wait()
        except KeyboardInterrupt:
            pass
        finally:
            print("draining ...")
            srv.stop(drain=True, timeout=30)
        return

    cl = ServingClient(host=srv.host, port=srv.port)
    print("healthz:", cl.healthz())

    rng = np.random.RandomState(0)
    prompt = list(map(int, rng.randint(1, cfg.vocab_size, 12)))
    out = cl.complete(prompt, max_tokens=24)
    print(f"blocking completion: {out['n']} tokens, state={out['state']}")

    print("streaming:", end=" ", flush=True)
    for ev in cl.stream_complete(prompt, max_tokens=24, temperature=0.8,
                                 seed=7):
        if ev.get("done"):
            print(f" [done n={ev['n']}]")
        else:
            print(*ev["tokens"], end=" ", flush=True)

    # a burst past max_seqs exercises the queue (and, if you shrink
    # --max-queue, 429 backpressure)
    burst = [list(map(int, rng.randint(1, cfg.vocab_size, 8)))
             for _ in range(2 * args.max_seqs)]
    threads = [threading.Thread(target=cl.complete, args=(p,),
                                kwargs={"max_tokens": 16})
               for p in burst]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    snap = cl.metrics()
    if args.replicas > 1:
        # router mode: per-replica snapshots ride under "replicas";
        # the router's own ledger is flat
        done = sum(int(s["pt_serving_requests_completed"]["value"])
                   for s in snap["replicas"].values())
        print(f"metrics: {done} completed over "
              f"{len(snap['replicas'])} replicas, "
              f"{int(snap['pt_router_dispatches']['value'])} dispatches"
              f" ({int(snap['pt_router_affinity_hits']['value'])}"
              f" affinity, {int(snap['pt_router_spills']['value'])}"
              f" spills, {int(snap['pt_router_failovers']['value'])}"
              f" failovers)")
        for rid, s in snap["replicas"].items():
            print(f"  {rid}: {int(s['pt_serving_requests_completed']['value'])}"
                  f" completed, prefix hit rate"
                  f" {s['pt_prefix_hit_rate']['value']:.2f}")
    else:
        ttft = snap["pt_serving_ttft_seconds"]
        print(f"metrics: "
              f"{int(snap['pt_serving_requests_completed']['value'])}"
              f" completed, ttft p50 {ttft['p50'] * 1e3:.1f} ms"
              f" p99 {ttft['p99'] * 1e3:.1f} ms, queue peak"
              f" {int(snap['pt_serving_queue_depth_peak']['value'])},"
              f" device steps"
              f" {int(snap['pt_serving_device_steps']['value'])}")
    print("graceful stop:", srv.stop(drain=True, timeout=30))


if __name__ == "__main__":
    main()
