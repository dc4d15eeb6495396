"""Operations and bytes the `laguna` family's serving step needs, reckoned
from ITS shapes (`costs.py` counts a dense decoder and stays as it is). Each
counts what MUST be read or computed ONCE: the experts that got a row and
never all of them, the KV tokens a step's rows can see (inside the window,
or the context) and never the pool, each weight once. A context re-read by
a second q block, an expert's matrix fetched for two row tiles, a page
fetched whole for the few columns the window's edge leaves in it earn no
credit, so a share of a peak made from these cannot pass 100% honestly."""
from __future__ import annotations

_BYTES = {"bfloat16": 2, "float32": 4, "int8": 1}
GROUP_OF = {"full_attention": "full", "sliding_attention": "window"}


def layers(m):
    """[(cache group, query heads, mlp kind)] of the layers held."""
    L = m["num_hidden_layers"]
    return [(GROUP_OF[t], nh, mlp) for t, nh, mlp in zip(
        m["layer_types"][:L], m["num_attention_heads_per_layer"][:L],
        m["mlp_layer_types"][:L])]


def sparse_layers(m):
    return sum(1 for _, _, mlp in layers(m) if mlp == "sparse")


def expert_params(m):
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def moe_needed(m, precision, experts_touched, assignments):
    """-> (bytes, operations) ONE sparse layer's routed products need for
    one step: the weights of every expert that got a row, once; each
    assignment's row in and out; 2 operations a weight and assignment."""
    wb = _BYTES[precision["weights"]]
    return (experts_touched * expert_params(m) * wb
            + assignments * 2 * m["hidden_size"] * wb,
            assignments * 2 * expert_params(m))


def attn_needed(m, precision, heads, kv_tokens, pairs, rows):
    """-> (bytes, operations) ONE attention layer of `heads` query heads
    needs for one step: the keys and values its rows can see, read once;
    the rows' queries read and outputs written; QK^T and PV over the
    attended pairs."""
    hd = m["head_dim"]
    return (kv_tokens * 2 * m["num_key_value_heads"] * hd
            * _BYTES[precision["kv_cache"]]
            + rows * 2 * heads * hd * _BYTES[precision["weights"]],
            pairs * heads * hd * 4)


def matmul_params_outside_experts(m):
    """Parameters that enter a matrix product for every row: attention with
    its gate, the dense layer's SwiGLU, router and shared expert of the
    sparse layers, the head (the embedding is a gather)."""
    H, hd, kv = m["hidden_size"], m["head_dim"], m["num_key_value_heads"]
    n = H * m["vocab_size"]
    for _, nh, mlp in layers(m):
        n += 2 * H * nh * hd + 2 * H * kv * hd + H * nh
        n += 3 * H * m["intermediate_size"] if mlp == "dense" else \
            H * m["num_experts"] + 3 * H * m["shared_expert_intermediate_size"]
    return n


def kv_bytes_per_token(m, group, cache_dtype="bfloat16"):
    """Keys and values of one token in all the layers of one cache group."""
    n = sum(1 for g, _, _ in layers(m) if g == group)
    return n * 2 * m["num_key_value_heads"] * m["head_dim"] * _BYTES[cache_dtype]


def serve_step_bytes(m, precision, experts_touched, kv_tokens):
    """Bytes one serving step has to read: every weight outside the routed
    experts once, the experts that got a row (summed over the sparse
    layers), and by cache group the KV tokens the step's rows can see
    (`kv_tokens`: {group: tokens})."""
    wb = _BYTES[precision["weights"]]
    return (matmul_params_outside_experts(m) + experts_touched
            * expert_params(m)) * wb + sum(
        n * kv_bytes_per_token(m, g, precision["kv_cache"])
        for g, n in kv_tokens.items())
