"""How a configuration of the `longcat_flash` family becomes the system
under test: the same `ServingEngine` behind a `RequestScheduler` as every
other family (the path `server.py` calls), given a `LongcatFlashConfig`.

Only what defines the deployment is passed on: model sizes, dtypes, the
cache's geometry, the queue's depth, and which experts this chip holds
(`n_routed_experts` of `router_experts`, from `first_expert`; the identity
experts are nobody's to hold). Rows a step, tiles, pump mode and every
`PT_*` switch stay the program's choice. The weights are the benchmark's
own, made on the device from the seed, in the tree `shapes` lays out: a
dictionary a double layer, its two attention sublayers and two dense
feed-forwards in lists; the reference reads the same tree.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# at import, so that a program without this family fails here, at once
from paddle_tpu.models.longcat_flash import LongcatFlashConfig

NORMS = ("final_norm", "ln", "q_norm", "kv_norm")
FLOAT32 = ("router_bias",)
DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def shapes(m):
    H, V, nh = m["hidden_size"], m["vocab_size"], m["num_attention_heads"]
    qr, rank = m["q_lora_rank"], m["kv_lora_rank"]
    nope, rope, vd = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                      m["v_head_dim"])
    F, E, Fe = (m["ffn_hidden_size"], m["n_routed_experts"],
                m["expert_ffn_hidden_size"])
    R = m["router_experts"] + m["zero_expert_num"]
    attn = {"ln": (H,), "wq_a": (H, qr), "q_norm": (qr,),
            "wq_b": (qr, nh * (nope + rope)), "wkv_a": (H, rank + rope),
            "kv_norm": (rank,), "wkv_b": (rank, nh * (nope + vd)),
            "wo": (nh * vd, H)}
    ffn = {"ln": (H,), "w_gate": (H, F), "w_up": (H, F), "w_down": (F, H)}
    layer = {"attn": [attn, attn], "ffn": [ffn, ffn], "router": (H, R),
             "router_bias": (R,), "w_gate": (E, H, Fe), "w_up": (E, H, Fe),
             "w_down": (E, Fe, H)}
    return {"embed": (V, H), "final_norm": (H,), "lm_head": (H, V),
            "layers": [layer] * m["num_layers"]}


def init_weights(m, seed, dtype, shardings=None):
    """Seeded normal(0, initializer_range) weights, norms at 1, the
    router's correction bias float32 at `router_bias_range`; made on the
    device in ONE jitted call in the type they are used in."""
    std = m.get("initializer_range", 0.02)
    bias_std = m.get("router_bias_range", std)
    paths, treedef = jax.tree_util.tree_flatten_with_path(
        shapes(m), is_leaf=lambda x: isinstance(x, tuple))

    def leaf(k, name, shape):
        if name in NORMS:
            return jnp.ones(shape, dtype)
        x = jax.random.normal(k, shape, jnp.float32)
        return x * bias_std if name in FLOAT32 else (x * std).astype(dtype)

    def make(key):
        keys = jax.random.split(key, len(paths))
        return jax.tree_util.tree_unflatten(treedef, [
            leaf(k, path[-1].key, shape)
            for k, (path, shape) in zip(keys, paths)])

    seed = int(seed)
    key = jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)
    return jax.jit(make, out_shardings=shardings)(key)


def check_widths(cfg):
    """The widths this family derives: the router scores the published
    count of real experts and every identity expert, and keeps the
    published `moe_topk` of them."""
    m, pub = cfg["model"], cfg["published"]
    out = []
    if m["router_experts"] != pub["n_routed_experts"]:
        out.append(f"router_experts: the router chooses among "
                   f"{m['router_experts']}, published {pub['n_routed_experts']}")
    for k in ("zero_expert_num", "moe_topk"):
        if m[k] != pub[k] or k in cfg["reduced"]:
            out.append(f"{k}: {m[k]}, published {pub[k]}: the router's width "
                       "and its picks a token are not a share's to cut")
    return out


def program_config(m, **kw):
    return LongcatFlashConfig.from_dict(dict(
        m, n_routed_experts=m["router_experts"],
        experts_held=m["n_routed_experts"], **kw))


def build_server(cfg, params, cache_dtype=None):
    """-> (engine, scheduler). `cache_dtype` is the control's switch, the
    one key `drivers/serve.py` hands a control: for this family the type
    the LATENT ROWS are cached in (`LongcatFlashConfig.latent_dtype`:
    float8_e4m3fn, one precision below the file's bfloat16); a cell never
    sets it.

    Once this scheduler has shut down, what was put on the device since
    the engine was built (its pools, token ring and tables) is given back:
    `drivers/serve.py` keeps every request's handle through the reference's
    run, a handle its scheduler and that the engine, so 4 GB of pools would
    else stand beside a reference that needs the room beside 10 GB of
    weights (PERF.md section 7, `[harness-frees-engine]`: the repair belongs
    in the driver; until then it is made here, as `models/glm_dsa.py`
    makes it)."""
    from paddle_tpu.models.llama_serving import ServingEngine
    from paddle_tpu.serving import RequestScheduler
    d = cfg["deployment"]
    # by buffer: the engine's own handles on the weights share theirs
    before = {a.unsafe_buffer_pointer() for a in jax.live_arrays()}
    engine = ServingEngine(
        params, program_config(cfg["model"], latent_dtype=cache_dtype),
        max_seqs=d["max_seqs"], max_seq_len=d["max_seq_len"],
        page_size=d["page_size"], num_pages=d["num_pages"],
        dtype=DTYPES[cfg["precision"]["weights"]])
    sched = RequestScheduler(engine, max_queue=d["max_queue"])
    shut_down = sched.shutdown

    def shutdown(*args, **kw):
        stopped = shut_down(*args, **kw)
        if stopped:
            for a in jax.live_arrays():
                if not a.is_deleted() and \
                        a.unsafe_buffer_pointer() not in before:
                    a.delete()
        return stopped
    sched.shutdown = shutdown
    return engine, sched
