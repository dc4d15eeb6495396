"""How a configuration of the `nemotron_h` family becomes the system under
test: the same `ServingEngine` behind a `RequestScheduler` as every other
family (the path `server.py` calls), given a `NemotronHConfig`.

Only what defines the deployment is passed on: model sizes, dtypes, the
cache's geometry (pages for the attention layers; the Mamba layers' state
a slot follows from `max_seqs`), the queue's depth. Rows a step, tiles, the
preemption policy, pump mode and every `PT_*` switch stay the program's
choice. The weights are the benchmark's own, made on the device from the
seed, in the tree `shapes` lays out: a list of one dictionary of leaves a
layer, by the layer's letter of the pattern; the reference reads the same
tree.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

# at import, so that a program without this family fails here, at once
from paddle_tpu.models.nemotron_h import NemotronHConfig

NORMS = ("final_norm", "ln", "norm_w")
FLOAT32 = ("router_bias", "A_log", "dt_bias", "D")
DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def pattern(m):
    return m["hybrid_override_pattern"][:m["num_hidden_layers"]]


def shapes(m):
    H, V = m["hidden_size"], m["vocab_size"]
    heads, G, N = m["mamba_num_heads"], m["n_groups"], m["ssm_state_size"]
    di = heads * m["mamba_head_dim"]
    conv = di + 2 * G * N
    nh, kv, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                  m["head_dim"])
    E, F = m["n_routed_experts"], m["moe_intermediate_size"]
    S = m["moe_shared_expert_intermediate_size"] * m["n_shared_experts"]
    by_letter = {
        "M": {"ln": (H,), "w_in": (H, di + conv + heads),
              "conv_w": (m["conv_kernel"], conv), "conv_b": (conv,),
              "dt_bias": (heads,), "A_log": (heads,), "D": (heads,),
              "norm_w": (di,), "w_out": (di, H)},
        "*": {"ln": (H,), "wq": (H, nh * hd), "wk": (H, kv * hd),
              "wv": (H, kv * hd), "wo": (nh * hd, H)},
        "E": {"ln": (H,), "router": (H, E), "router_bias": (E,),
              # an expert's up matrix rows its outputs, as a checkpoint's
              # linear layer keeps it (and as the device lays it out)
              "w_up": (E, F, H), "w_down": (E, F, H), "s_up": (H, S),
              "s_down": (S, H)}}
    return {"embed": (V, H), "final_norm": (H,), "lm_head": (H, V),
            "layers": [dict(by_letter[letter]) for letter in pattern(m)]}


def init_weights(m, seed, dtype, shardings=None):
    """Seeded weights, made on the device in ONE jitted call in the type
    they are used in: normal(0, initializer_range); norms at 1; and
    Mamba-2's own start (the configuration's `assumed`): A_log =
    log U(1, 16), dt_bias the inverse softplus of a step log-uniform in
    [time_step_min, time_step_max] floored at time_step_floor, D = 1 (the
    three float32), the convolution's taps and bias U(-1/sqrt(k),
    1/sqrt(k)), and the in-projection's columns for B and C at
    `ssm_bc_range` (the recurrence's output is their product's: at the
    weights' 0.02 the skip term D x is three quarters of y and a fault in
    the state hides behind it); the router's correction bias float32 at
    `router_bias_range`."""
    std = m.get("initializer_range", 0.02)
    bias_std = m.get("router_bias_range", std)
    di = m["mamba_num_heads"] * m["mamba_head_dim"]
    bc = 2 * m["n_groups"] * m["ssm_state_size"]    # after z and x
    bc_gain = m.get("ssm_bc_range", std) / std
    paths, treedef = jax.tree_util.tree_flatten_with_path(
        shapes(m), is_leaf=lambda x: isinstance(x, tuple))

    def leaf(k, name, shape):
        if name in NORMS:
            return jnp.ones(shape, dtype)
        if name == "D":
            return jnp.ones(shape, jnp.float32)
        if name == "A_log":
            return jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0,
                                              16.0))
        if name == "dt_bias":
            dt = jnp.maximum(jnp.exp(jax.random.uniform(
                k, shape, jnp.float32, math.log(m["time_step_min"]),
                math.log(m["time_step_max"]))), m["time_step_floor"])
            return dt + jnp.log(-jnp.expm1(-dt))
        if name in ("conv_w", "conv_b"):
            bound = m["conv_kernel"] ** -0.5
            return jax.random.uniform(k, shape, jnp.float32, -bound,
                                      bound).astype(dtype)
        x = jax.random.normal(k, shape, jnp.float32)
        if name == "w_in":
            x = x.at[:, 2 * di:2 * di + bc].multiply(bc_gain)
        return x * bias_std if name == "router_bias" \
            else (x * std).astype(dtype)

    def make(key):
        keys = jax.random.split(key, len(paths))
        return jax.tree_util.tree_unflatten(treedef, [
            leaf(k, path[-1].key, shape)
            for k, (path, shape) in zip(keys, paths)])

    seed = int(seed)
    key = jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)
    return jax.jit(make, out_shardings=shardings)(key)


def check_widths(cfg):
    """The width this family derives: d_inner is `mamba_num_heads` x
    `mamba_head_dim`, so the count of heads is a width's factor and may
    not be reduced, no more than the groups that share B and C or the
    router's experts. The pattern changes with the depth and with nothing
    else: `model` holds it whole, as published, or cut to its first
    `num_hidden_layers` letters, which are what the program takes."""
    m, pub = cfg["model"], cfg["published"]
    out = [f"{k}: {m[k]}, published {pub[k]}: a factor of a width, not a "
           "share's to cut"
           for k in ("mamba_num_heads", "n_groups", "n_routed_experts",
                     "conv_kernel")
           if m[k] != pub[k] or k in cfg["reduced"]]
    L, whole = m["num_hidden_layers"], pub["hybrid_override_pattern"]
    if m["hybrid_override_pattern"] not in (whole, whole[:L]):
        out.append("hybrid_override_pattern: neither the published pattern "
                   f"nor its first {L} letters")
    return out


def program_config(m, **kw):
    return NemotronHConfig.from_dict(dict(m, **kw))


def build_server(cfg, params, cache_dtype=None):
    """-> (engine, scheduler). `cache_dtype` is the control's switch, the
    one key `drivers/serve.py` hands a control: for this family the type
    the recurrence's STATE is kept in (`NemotronHConfig.ssm_state_dtype`:
    bfloat16, one precision below the file's float32); a cell never sets
    it.

    Once this scheduler has shut down, what was put on the device since
    the engine was built (its pools, the slots' state, token ring and
    tables) is given back: `drivers/serve.py` keeps every request's handle
    through the reference's run, a handle its scheduler and that the
    engine, so 1.6 GB of pages and state would else stand beside a
    reference that needs the room beside 12 GB of weights (PERF.md section
    7, `[harness-frees-engine]`: the repair belongs in the driver; until
    then it is made here, as `models/glm_dsa.py` makes it)."""
    from paddle_tpu.models.llama_serving import ServingEngine
    from paddle_tpu.serving import RequestScheduler
    d = cfg["deployment"]
    kw = {"ssm_state_dtype": cache_dtype} if cache_dtype else {}
    # by buffer: the engine's own handles on the weights share theirs
    before = {a.unsafe_buffer_pointer() for a in jax.live_arrays()}
    engine = ServingEngine(
        params, program_config(cfg["model"], **kw),
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
