"""Parameters, operations and bytes of the `longcat_flash` family, reckoned
from ITS shapes (`costs.py` counts a dense decoder, `costs_laguna.py` and
`costs_glm_dsa.py` theirs; all stay as they are). A layer is a DOUBLE
layer: two attention sublayers, two dense feed-forwards, one router, its
real experts; identity experts have no parameter and no product. Each
function counts what MUST be read or computed ONCE: the latent rows a
step's rows can see, read once a run (never once a q block, never the 640
lanes the device pads a row to); the experts held that got a row; each
weight once. So a share of a peak made from these cannot pass 100%
honestly, whatever implements the step."""
from __future__ import annotations

_BYTES = {"bfloat16": 2, "float32": 4, "int8": 1, "float8_e4m3fn": 1}


def sublayers(m):
    """Attention sublayers, each with a cache row a token: two a layer."""
    return 2 * m["num_layers"]


def attention_params(m):
    """One attention sublayer, its two inner norms included."""
    H, nh, qr, rank = (m["hidden_size"], m["num_attention_heads"],
                       m["q_lora_rank"], m["kv_lora_rank"])
    nope, rope, vd = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                      m["v_head_dim"])
    return (H * qr + qr + qr * nh * (nope + rope) + H * (rank + rope) + rank
            + rank * nh * (nope + vd) + nh * vd * H)


def dense_ffn_params(m):
    return 3 * m["hidden_size"] * m["ffn_hidden_size"]


def expert_params(m):
    return 3 * m["hidden_size"] * m["expert_ffn_hidden_size"]


def router_width(m):
    return m.get("router_experts", m["n_routed_experts"]) + m["zero_expert_num"]


def layer_params(m, experts):
    """One double layer with `experts` real experts: two of each sublayer,
    four norms, the router and its correction bias."""
    H = m["hidden_size"]
    return (2 * attention_params(m) + 2 * dense_ffn_params(m) + 4 * H
            + H * router_width(m) + router_width(m)
            + experts * expert_params(m))


def params_held(m):
    return (m["num_layers"] * layer_params(m, m["n_routed_experts"])
            + 2 * m["vocab_size"] * m["hidden_size"] + m["hidden_size"])


def params_published(pub):
    return params_held(dict(pub, router_experts=pub["n_routed_experts"]))


def cache_bytes_per_token(m, precision):
    """The latent row of one token in every sublayer held."""
    return sublayers(m) * _BYTES[precision["kv_cache"]] * (
        m["kv_lora_rank"] + m["qk_rope_head_dim"])


def latent_attn_needed(m, precision, pairs, kv_tokens, rows):
    """-> (bytes, operations) ONE sublayer's dense latent attention needs
    for one step: each of the `kv_tokens` latent rows the step's rows can
    see (the sum over slots of the longest context: `pt_ragged_kv_tokens`)
    read once, rank + rope values; every one of the `pairs` (row, position)
    pairs costs each head a product over the whole latent row for its
    score and over `rank` for its value; the rows' absorbed queries read
    and latent outputs written."""
    nh, rank = m["num_attention_heads"], m["kv_lora_rank"]
    width = rank + m["qk_rope_head_dim"]
    wb = _BYTES[precision["weights"]]
    return (kv_tokens * width * _BYTES[precision["kv_cache"]]
            + rows * nh * (width + rank) * wb,
            pairs * nh * 2 * (width + rank))


def moe_needed(m, precision, experts_touched, assignments):
    """-> (bytes, operations) ONE layer's grouped expert products need for
    one step: the weights of every HELD expert that got a row, once; each
    assignment's row in and out; 2 operations a weight and assignment.
    Identity assignments are no part of `assignments`: they cost nothing."""
    wb = _BYTES[precision["weights"]]
    return (experts_touched * expert_params(m) * wb
            + assignments * 2 * m["hidden_size"] * wb,
            assignments * 2 * expert_params(m))


def matmul_params_outside_experts(m):
    """Parameters that enter a matrix product for every row: both attention
    sublayers, both dense feed-forwards and the router of every layer, and
    the head (the embedding is a gather; norms are no product)."""
    H = m["hidden_size"]
    per_layer = layer_params(m, 0) - 4 * H - router_width(m) \
        - 2 * (m["q_lora_rank"] + m["kv_lora_rank"])
    return H * m["vocab_size"] + m["num_layers"] * per_layer


def serve_step_needed(m, precision, rows, experts_touched, assignments,
                      pairs, kv_tokens):
    """-> (bytes, operations) one serving step needs: every weight outside
    the real experts once and 2 operations a row for it; the held experts
    that got a row (summed over the layers, as the engine books them); and
    the dense latent attention of every sublayer (`pairs`, `kv_tokens`: a
    LAYER of the cache group, as the engine books them)."""
    wb = _BYTES[precision["weights"]]
    ab, ao = latent_attn_needed(m, precision, pairs, kv_tokens, rows)
    eb, eo = moe_needed(m, precision, experts_touched, assignments)
    dense = matmul_params_outside_experts(m)
    return (dense * wb + eb + sublayers(m) * ab,
            rows * 2 * dense + eo + sublayers(m) * ao)
