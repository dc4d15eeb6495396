"""Parameters, operations and bytes of the `nemotron_h` family, reckoned
from ITS shapes (`costs.py` counts a dense decoder and stays as it is). A
layer is the mixer its letter of `hybrid_override_pattern` names: `M` a
Mamba-2 mixer, `E` routed experts beside a shared one (not gated: two
matrices an expert), `*` grouped-query attention. Each count is what MUST be
read or computed ONCE at the PUBLISHED widths: the experts that got a row
and never all of them, a slot's state read once and written once a layer
(whatever implements the scan), the KV tokens a step's rows can see and
never the pool, each weight once. What a layout pads or copies in memory,
a matrix fetched for two row tiles, a context re-read by a second q block
earn no credit, so a share of a peak made from these cannot pass 100%
honestly."""
from __future__ import annotations

_BYTES = {"bfloat16": 2, "float32": 4, "int8": 1}
MAMBA, EXPERTS, ATTENTION = "M", "E", "*"


def pattern(m, layers=None):
    """The mixers of the layers held (or of the first `layers`)."""
    return m["hybrid_override_pattern"][:layers or m["num_hidden_layers"]]


def count(m, letter):
    return pattern(m).count(letter)


def d_inner(m):
    return m["mamba_num_heads"] * m["mamba_head_dim"]


def conv_dim(m):
    return d_inner(m) + 2 * m["n_groups"] * m["ssm_state_size"]


def expert_params(m):
    """One routed expert: up and down, no gate."""
    return 2 * m["hidden_size"] * m["moe_intermediate_size"]


def layer_params(m, letter):
    """Every parameter of one layer, its pre-norm among them."""
    H = m["hidden_size"]
    if letter == MAMBA:
        di, heads = d_inner(m), m["mamba_num_heads"]
        return (H + H * (di + conv_dim(m) + heads)
                + (m["conv_kernel"] + 1) * conv_dim(m)    # taps and bias
                + 3 * heads + di + di * H)         # dt_bias, A_log, D; norm
    if letter == ATTENTION:
        hd = m["head_dim"]
        return H + 2 * H * m["num_attention_heads"] * hd \
            + 2 * H * m["num_key_value_heads"] * hd
    E = m["n_routed_experts"]
    shared = m["moe_shared_expert_intermediate_size"] * m["n_shared_experts"]
    return H + H * E + E + E * expert_params(m) + 2 * H * shared


def model_params(m, layers=None):
    """The layers held (or the first `layers` of the pattern), the untied
    embedding and head, the final norm."""
    H = m["hidden_size"]
    return 2 * m["vocab_size"] * H + H + sum(
        layer_params(m, letter) for letter in pattern(m, layers))


def state_bytes_per_slot(m, precision):
    """What ONE Mamba layer keeps a slot -> (the recurrence's state, the
    convolution's carried rows), bytes."""
    return (d_inner(m) * m["ssm_state_size"] * _BYTES[precision["ssm_state"]],
            (m["conv_kernel"] - 1) * conv_dim(m)
            * _BYTES[precision["weights"]])


def kv_bytes_per_token(m, precision):
    """Keys and values of one token in all the attention layers held."""
    return count(m, ATTENTION) * 2 * m["num_key_value_heads"] \
        * m["head_dim"] * _BYTES[precision["kv_cache"]]


def moe_needed(m, precision, experts_touched, assignments):
    """-> (bytes, operations) ONE expert layer's routed products need for
    one step: the two matrices of every expert that got a row, once; each
    assignment's row in and out; 2 operations a weight and assignment."""
    wb = _BYTES[precision["weights"]]
    return (experts_touched * expert_params(m) * wb
            + assignments * 2 * m["hidden_size"] * wb,
            assignments * 2 * expert_params(m))


def scan_needed(m, precision, state_slots, rows):
    """-> (bytes, operations) ONE Mamba layer's recurrence needs for one
    step, whatever implements it: the state of every slot that has a row
    read once and written once; each row's x, dt, B, C in and y out, in
    float32; a state value's decay, its increment dt x B (two products and
    a sum) and its product with C and sum, 5 operations a row."""
    ssm, _ = state_bytes_per_slot(m, precision)
    di = d_inner(m)
    row = 2 * di + m["mamba_num_heads"] \
        + 2 * m["n_groups"] * m["ssm_state_size"]
    return (state_slots * 2 * ssm + rows * row * 4,
            rows * 5 * di * m["ssm_state_size"])


def attn_needed(m, precision, kv_tokens, pairs, rows):
    """-> (bytes, operations) ONE attention layer needs for one step: the
    keys and values its rows can see, read once; the rows' queries read and
    outputs written; QK^T and PV over the attended pairs."""
    hd, nh = m["head_dim"], m["num_attention_heads"]
    return (kv_tokens * 2 * m["num_key_value_heads"] * hd
            * _BYTES[precision["kv_cache"]]
            + rows * 2 * nh * hd * _BYTES[precision["weights"]],
            pairs * nh * hd * 4)


def row_params(m):
    """Parameters that enter a matrix product for EVERY row: a Mamba
    layer's two projections, attention's four, an expert layer's router
    and shared expert (the head takes the rows that are unembedded, the
    embedding is a gather)."""
    H = m["hidden_size"]
    di = d_inner(m)
    shared = m["moe_shared_expert_intermediate_size"] * m["n_shared_experts"]
    per = {MAMBA: H * (di + conv_dim(m) + m["mamba_num_heads"]) + di * H,
           ATTENTION: layer_params(m, ATTENTION) - H,
           EXPERTS: H * m["n_routed_experts"] + 2 * H * shared}
    return sum(per[letter] for letter in pattern(m))


def serve_step_needed(m, precision, *, rows, logit_rows, experts_touched,
                      assignments, state_slots, ssm_rows, kv_tokens, pairs):
    """-> (bytes, operations) one serving step needs: every weight outside
    the routed experts once (the head among them) and each row through it;
    the routed products (`moe_needed`, `experts_touched` and `assignments`
    summed over the expert layers); the recurrence (`scan_needed`, one
    layer's `state_slots` and `ssm_rows`, times the Mamba layers, and the
    carried convolution rows beside the state); attention over what the
    rows can see (one layer's `kv_tokens` and `pairs`, times the attention
    layers)."""
    wb = _BYTES[precision["weights"]]
    head = m["hidden_size"] * m["vocab_size"]
    moe = moe_needed(m, precision, experts_touched, assignments)
    scan = scan_needed(m, precision, state_slots, ssm_rows)
    conv = state_slots * 2 * state_bytes_per_slot(m, precision)[1]
    attn = attn_needed(m, precision, kv_tokens, pairs, rows)
    n_m, n_a = count(m, MAMBA), count(m, ATTENTION)
    return ((row_params(m) + head) * wb + moe[0] + n_m * (scan[0] + conv)
            + n_a * attn[0],
            2 * rows * row_params(m) + 2 * logit_rows * head + moe[1]
            + n_m * scan[1] + n_a * attn[1])
