"""How a configuration of the `llama_dense` family becomes the system under
test: a `ServingEngine` behind a `RequestScheduler` (the path `server.py`
calls), or `llama_spmd.make_train_step` with its state.

Only what defines the deployment is passed on: model sizes, dtypes, the
cache's geometry, the queue's depth, the mesh and the optimizer's stated
hyperparameters. Tiling, buffers, pump mode and every `PT_*` switch stay the
program's choice, so a PR that changes a default is measured here.
The weights are the benchmark's own, made on the device from the seed.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NORMS = ("final_norm", "ln1", "ln2")
DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def shapes(m):
    H, F, V, L = (m["hidden_size"], m["intermediate_size"], m["vocab_size"],
                  m["num_hidden_layers"])
    KV = m["num_key_value_heads"] * (H // m["num_attention_heads"])
    return {"embed": (V, H), "final_norm": (H,), "lm_head": (H, V),
            "layers": {"ln1": (L, H), "wq": (L, H, H), "wk": (L, H, KV),
                       "wv": (L, H, KV), "wo": (L, H, H), "ln2": (L, H),
                       "w_gate": (L, H, F), "w_up": (L, H, F),
                       "w_down": (L, F, H)}}


def init_weights(m, seed, dtype, shardings=None):
    """Seeded normal(0, initializer_range) weights, norms at 1, made on the
    device in ONE jitted call in the type they are used in."""
    std = m.get("initializer_range", 0.02)
    paths, treedef = jax.tree_util.tree_flatten_with_path(
        shapes(m), is_leaf=lambda x: isinstance(x, tuple))

    def make(key):
        keys = jax.random.split(key, len(paths))
        leaves = [jnp.ones(shape, dtype) if path[-1].key in NORMS else
                  (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)
                  for k, (path, shape) in zip(keys, paths)]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    seed = int(seed)
    key = jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)
    return jax.jit(make, out_shardings=shardings)(key)


def program_config(m):
    from paddle_tpu.models.llama import LlamaConfig
    fields = {f for f in LlamaConfig.__dataclass_fields__}
    return LlamaConfig(**{k: v for k, v in m.items() if k in fields})


def build_server(cfg, params, cache_dtype=None):
    """-> (engine, scheduler). `cache_dtype` is the control's switch (int8
    pages); a cell never sets it."""
    from paddle_tpu.models.llama_serving import ServingEngine
    from paddle_tpu.serving import RequestScheduler
    d = cfg["deployment"]
    engine = ServingEngine(
        params, program_config(cfg["model"]), max_seqs=d["max_seqs"],
        max_seq_len=d["max_seq_len"], page_size=d["page_size"],
        num_pages=d["num_pages"], dtype=DTYPES[cfg["precision"]["weights"]],
        cache_dtype=cache_dtype)
    return engine, RequestScheduler(engine, max_queue=d["max_queue"])


def build_trainer(cfg, seed, devices):
    """-> dict(step, params, opt_state, mesh, batch_sharding). bf16
    parameters, float32 master and moments (`init_opt_state`), everything
    else as `make_train_step` defaults it."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from paddle_tpu.models import llama_spmd
    from paddle_tpu.parallel.mesh import create_mesh
    d, m = cfg["deployment"], cfg["model"]
    mesh = create_mesh(dict(d["mesh"]), devices=devices)
    pcfg = program_config(m)
    specs = llama_spmd.param_specs(pcfg, mesh)
    shard = jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), specs,
                                   is_leaf=lambda x: isinstance(x, P))
    params = init_weights(m, seed, DTYPES[cfg["precision"]["weights"]], shard)
    opt = cfg["optimizer"]
    step = llama_spmd.make_train_step(pcfg, mesh, lr=opt["lr"],
                                      clip_norm=opt["clip_norm"])
    return {"step": step, "params": params,
            "opt_state": llama_spmd.init_opt_state(params), "mesh": mesh,
            "param_shardings": shard,
            "batch_sharding": NamedSharding(mesh, P("dp"))}
