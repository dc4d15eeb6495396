"""How a configuration of the `laguna` family becomes the system under test:
the same `ServingEngine` behind a `RequestScheduler` as every other family
(the path `server.py` calls), given a `LagunaConfig`.

Only what defines the deployment is passed on: model sizes, dtypes, the
cache's geometry (pages by cache group: `num_pages` is a dictionary, `full`
and `window`), the queue's depth. Tiling, buffers, pump mode and every
`PT_*` switch stay the program's choice. The weights are the benchmark's
own, made on the device from the seed, in the tree `shapes` lays out: a list
of one dictionary of leaves a layer (layers are not alike, so nothing is
stacked); the reference reads the same tree.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# at import, so that a program without this family fails here, at once
from paddle_tpu.models.laguna import LagunaConfig

NORMS = ("final_norm", "ln1", "ln2")
DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def shapes(m):
    H, V, hd, kv = (m["hidden_size"], m["vocab_size"], m["head_dim"],
                    m["num_key_value_heads"])
    F, E, Fe, Fs = (m["intermediate_size"], m["num_experts"],
                    m["moe_intermediate_size"],
                    m["shared_expert_intermediate_size"])
    layers = []
    for li in range(m["num_hidden_layers"]):
        nh = m["num_attention_heads_per_layer"][li]
        lp = {"ln1": (H,), "wq": (H, nh * hd), "wk": (H, kv * hd),
              "wv": (H, kv * hd), "wg": (H, nh), "wo": (nh * hd, H),
              "ln2": (H,)}
        if m["mlp_layer_types"][li] == "dense":
            lp.update(w_gate=(H, F), w_up=(H, F), w_down=(F, H))
        else:
            lp.update(router=(H, E), w_gate=(E, H, Fe), w_up=(E, H, Fe),
                      w_down=(E, Fe, H), s_gate=(H, Fs), s_up=(H, Fs),
                      s_down=(Fs, H))
        layers.append(lp)
    return {"embed": (V, H), "final_norm": (H,), "lm_head": (H, V),
            "layers": layers}


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


def check_widths(cfg):
    """This family derives no width (`head_dim` is a key of its own). The
    per-layer lists change with the depth and with nothing else: `model`
    holds them whole, as published, or cut to their first
    `num_hidden_layers` entries, which are what the program takes."""
    m, pub = cfg["model"], cfg["published"]
    L = m["num_hidden_layers"]
    return [f"{k}: `model` holds {m[k]!r}, neither the published list nor "
            f"its first {L} entries"
            for k in ("layer_types", "mlp_layer_types",
                      "num_attention_heads_per_layer")
            if list(m[k]) not in (list(pub[k]), list(pub[k][:L]))]


def program_config(m):
    return LagunaConfig.from_dict(m)


def build_server(cfg, params, cache_dtype=None):
    """-> (engine, scheduler). `cache_dtype` is the control's switch (int8
    pages, in both pools); a cell never sets it."""
    from paddle_tpu.models.llama_serving import ServingEngine
    from paddle_tpu.serving import RequestScheduler
    d = cfg["deployment"]
    engine = ServingEngine(
        params, program_config(cfg["model"]), max_seqs=d["max_seqs"],
        max_seq_len=d["max_seq_len"], page_size=d["page_size"],
        num_pages=dict(d["num_pages"]),
        dtype=DTYPES[cfg["precision"]["weights"]], cache_dtype=cache_dtype)
    return engine, RequestScheduler(engine, max_queue=d["max_queue"])
