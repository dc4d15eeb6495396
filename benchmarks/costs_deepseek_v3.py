"""Parameters, operations and bytes of the `deepseek_v3` family's training
step (Moonlight-16B-A3B), reckoned from ITS shapes (`costs.py` counts a
dense decoder and stays as it is). `m` is a configuration's `model`: the
published keys, `n_routed_experts` the experts HELD of the `router_experts`
the router chooses among. Each counts what the model's equations need ONCE:
the tokens' products, the attended pairs inside documents, the assignments
that reached a held expert. A layer computed again under remat, the scores a
backward kernel rebuilds, a masked half of a diagonal block and a row tile's
rows nobody owns earn no credit, so a share of a peak made from these cannot
pass 100% honestly."""
from __future__ import annotations

_BYTES = {"bfloat16": 2, "float32": 4}


def depth(m):
    """-> (leading dense layers, expert layers) held."""
    dense = min(m["first_k_dense_replace"], m["num_hidden_layers"])
    return dense, m["num_hidden_layers"] - dense


def qk_dim(m):
    return m["qk_nope_head_dim"] + m["qk_rope_head_dim"]


def attention_params(m):
    """W_q, W_dkv, W_ukv, W_o of one layer (the norms apart)."""
    H, nh = m["hidden_size"], m["num_attention_heads"]
    return (H * nh * qk_dim(m)
            + H * (m["kv_lora_rank"] + m["qk_rope_head_dim"])
            + m["kv_lora_rank"] * nh * (m["qk_nope_head_dim"] + m["v_head_dim"])
            + nh * m["v_head_dim"] * H)


def expert_params(m):
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def layer_params_outside_experts(m, experts=None):
    """An expert layer without its routed experts: attention, the shared
    experts, the router over `experts` (default: all it chooses among)."""
    experts = m.get("router_experts", m["n_routed_experts"]) \
        if experts is None else experts
    return (attention_params(m) + m["n_shared_experts"] * expert_params(m)
            + m["hidden_size"] * experts)


def dense_layer_params(m):
    return attention_params(m) + 3 * m["hidden_size"] * m["intermediate_size"]


def norm_params(m):
    dense, sparse = depth(m)
    per_layer = 2 * m["hidden_size"] + m["kv_lora_rank"]
    return (dense + sparse) * per_layer + m["hidden_size"]


def held_params(m):
    """Every parameter this chip holds (the router's bias among them)."""
    dense, sparse = depth(m)
    return (dense * dense_layer_params(m)
            + sparse * (layer_params_outside_experts(m)
                        + m.get("router_experts", m["n_routed_experts"])
                        + m["n_routed_experts"] * expert_params(m))
            + 2 * m["vocab_size"] * m["hidden_size"] + norm_params(m))


def matmul_params_per_token(m):
    """Parameters EVERY token is multiplied by: attention, the dense
    layer's SwiGLU, the shared experts and the router of the expert layers,
    the head (the embedding is a gather; the routed experts are counted by
    assignment)."""
    dense, sparse = depth(m)
    return (dense * dense_layer_params(m)
            + sparse * layer_params_outside_experts(m)
            + m["hidden_size"] * m["vocab_size"])


def latent_flash_flops(m, pairs):
    """The attention kernels' products over `pairs` (query, key) pairs, in
    every head of every layer held, forward and backward: Q K^T over the
    keys' width and P V over the values' (2 operations a product), then dP
    = dO V^T and dV = P^T dO over the values' width and dQ, dK over the
    keys': twice the forward's. The scores rebuilt in both backward kernels
    are recomputation."""
    per_pair = 2 * (qk_dim(m) + m["v_head_dim"])
    return 3 * per_pair * pairs * m["num_attention_heads"] * sum(depth(m))


def moe_needed(m, precision, experts_touched, assignments):
    """-> (bytes, operations) the routed products of ONE step need, forward
    and backward, summed over the expert layers: `assignments` reached a
    held expert and `experts_touched` experts got a row. Each of the three
    matrices is multiplied three times (forward, the gradient by its rows,
    the gradient by the matrix): 6 operations a weight and assignment. A
    touched expert's weights are read forward and backward and their
    gradient written; each assignment's row goes in and out, both ways."""
    wb = _BYTES[precision["weights"]]
    return (3 * experts_touched * expert_params(m) * wb
            + 4 * assignments * m["hidden_size"] * wb,
            6 * assignments * expert_params(m))


def train_flops_per_step(m, tokens, pairs, assignments):
    """6 operations a parameter and token for what every token meets, 6 an
    expert parameter and assignment that reached a held expert (summed over
    the expert layers), and the attention kernels' products. No credit for
    recomputation or padding."""
    return (6 * matmul_params_per_token(m) * tokens
            + 6 * expert_params(m) * assignments
            + latent_flash_flops(m, pairs))
