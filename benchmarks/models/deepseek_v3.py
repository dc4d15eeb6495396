"""How a configuration of the `deepseek_v3` family (Moonlight-16B-A3B)
becomes the system under test: `models/deepseek_spmd.make_train_step` with
its state, on the mesh the configuration states (one chip: the share of a
layer that one of `deployment.chips_per_layer` chips holds).

Only what defines the deployment is passed on: model sizes, dtypes, which
experts this chip holds (`n_routed_experts` of `router_experts`, from
`first_expert`), the mesh and the optimizer's stated hyperparameters. Row
blocks, kernel blocks, the loss's chunk and every `PT_*` switch stay the
program's choice. The weights are the benchmark's own, made on the device
from the seed, in the tree `shapes` lays out: `dense` and `moe` stacks over
their layers; the reference reads the same tree.

`TRAINERS` keeps the step objects `build_trainer` made, newest last: the
training driver hands reducers no counters, so
`reducers/train_registry_ratio.py` reads the program's registry through the
step it finds here.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# at import, so that a program without this family fails here, at once
from paddle_tpu.models import deepseek_spmd
from paddle_tpu.models.deepseek import DeepSeekConfig

NORMS = ("final_norm", "ln1", "ln2", "kv_norm")
FLOAT32 = ("router_bias",)
DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
TRAINERS = []


def shapes(m):
    H, V, nh = m["hidden_size"], m["vocab_size"], m["num_attention_heads"]
    rank, nope, rope, vd = (m["kv_lora_rank"], m["qk_nope_head_dim"],
                            m["qk_rope_head_dim"], m["v_head_dim"])
    F, E, Fe = (m["intermediate_size"], m["n_routed_experts"],
                m["moe_intermediate_size"])
    R, Fs = m["router_experts"], Fe * m["n_shared_experts"]
    Ld = min(m["first_k_dense_replace"], m["num_hidden_layers"])
    Lm = m["num_hidden_layers"] - Ld

    def attn(L):
        return {"ln1": (L, H), "wq": (L, H, nh * (nope + rope)),
                "wkv_a": (L, H, rank + rope), "kv_norm": (L, rank),
                "wkv_b": (L, rank, nh * (nope + vd)), "wo": (L, nh * vd, H),
                "ln2": (L, H)}
    out = {"embed": (V, H), "final_norm": (H,), "lm_head": (H, V)}
    if Ld:
        out["dense"] = dict(attn(Ld), w_gate=(Ld, H, F), w_up=(Ld, H, F),
                            w_down=(Ld, F, H))
    if Lm:
        out["moe"] = dict(
            attn(Lm), router=(Lm, H, R), router_bias=(Lm, R),
            w_gate=(Lm, E, H, Fe), w_up=(Lm, E, H, Fe), w_down=(Lm, E, Fe, H),
            s_gate=(Lm, H, Fs), s_up=(Lm, H, Fs), s_down=(Lm, Fs, H))
    return out


def init_weights(m, seed, dtype, shardings=None):
    """Seeded normal(0, initializer_range) weights, norms at 1, the router's
    correction bias float32 at `router_bias_range`; made on the device in
    ONE jitted call in the type they are used in."""
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
    """The width this family derives: the router's is the published count
    of experts, whatever this chip holds."""
    m, pub = cfg["model"], cfg["published"]
    return [] if m["router_experts"] == pub["n_routed_experts"] else [
        f"router_experts: the router chooses among {m['router_experts']}, "
        f"published {pub['n_routed_experts']}"]


def program_config(m):
    fields = set(DeepSeekConfig.__dataclass_fields__)
    return DeepSeekConfig(**dict(
        {k: v for k, v in m.items() if k in fields and v is not None},
        n_routed_experts=m["router_experts"],
        experts_held=m["n_routed_experts"]))


def build_trainer(cfg, seed, devices):
    """-> dict(step, params, opt_state, mesh, batch_sharding). bf16
    parameters, float32 master and moments (`init_opt_state`), everything
    else as `make_train_step` defaults it."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from paddle_tpu.parallel.mesh import create_mesh
    d, m = cfg["deployment"], cfg["model"]
    mesh = create_mesh(dict(d["mesh"]), devices=devices)
    pcfg = program_config(m)
    shard = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), deepseek_spmd.param_specs(pcfg, mesh),
        is_leaf=lambda x: isinstance(x, P))
    params = init_weights(m, seed, DTYPES[cfg["precision"]["weights"]], shard)
    opt = cfg["optimizer"]
    step = deepseek_spmd.make_train_step(pcfg, mesh, lr=opt["lr"],
                                         clip_norm=opt["clip_norm"])
    TRAINERS.append(step)
    return {"step": step, "params": params,
            "opt_state": deepseek_spmd.init_opt_state(params), "mesh": mesh,
            "param_shardings": shard,
            "batch_sharding": NamedSharding(mesh, P())}
