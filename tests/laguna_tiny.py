"""A Laguna at toy size for the CPU tests: 2 experts a token of 8, a window
of 8, query heads 6 and 8 a KV head over 2 KV heads, one dense layer and
then sparse ones, the published pattern of layer types."""
import numpy as np

import jax
import jax.numpy as jnp

from paddle_tpu.models.laguna import FULL, SLIDING, LagunaConfig
from paddle_tpu.models.llama_serving import Request, ServingEngine

from benchmarks.reference import laguna as reference


def tiny_model(layers=5):
    """The configuration as a config.json's dictionary (what the reference
    takes); `LagunaConfig.from_dict` makes the program's of it."""
    lt = [FULL if i % 4 == 0 else SLIDING for i in range(layers)]
    return dict(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=layers, num_attention_heads=12,
        num_key_value_heads=2, head_dim=8, num_experts=8,
        num_experts_per_tok=2, moe_intermediate_size=16,
        shared_expert_intermediate_size=16, sliding_window=8,
        rope_parameters={
            FULL: {"rope_theta": 500000, "rope_type": "yarn", "factor": 64,
                   "original_max_position_embeddings": 16, "beta_slow": 1,
                   "beta_fast": 4, "attention_factor": 1.4158883083359672,
                   "partial_rotary_factor": 0.5},
            SLIDING: {"rope_type": "default", "rope_theta": 10000,
                      "partial_rotary_factor": 1}},
        layer_types=lt, mlp_layer_types=["dense"] + ["sparse"] * (layers - 1),
        num_attention_heads_per_layer=[12 if t == FULL else 16 for t in lt],
        rms_norm_eps=1e-6, gating=True, moe_routed_scaling_factor=2.5,
        initializer_range=0.2)


def engine(m, params, **kw):
    kw.setdefault("max_seqs", 4)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("page_size", 4)
    kw.setdefault("ragged_tokens", 16)
    kw.setdefault("num_pages", {"full": 65, "window": 40})
    return ServingEngine(params, LagunaConfig.from_dict(m), **kw)


def requests(shapes, seed=0, vocab=128):
    rng = np.random.default_rng(seed)
    return [Request(i, rng.integers(1, vocab, n).tolist(), max_new_tokens=k,
                    eos_id=None, logprobs=True)
            for i, (n, k) in enumerate(shapes)]


def against_reference(m, params, req):
    """-> (share of served tokens that are the reference's first choice,
    widest |log p(served token)| difference) for one finished request: the
    reference's full forward over the prompt and everything served."""
    with jax.default_matmul_precision("highest"):
        lg = reference.logits(params, jnp.asarray(
            req.prompt + req.output, jnp.int32), m, q_block=1)
    n = len(req.prompt)
    at = lg[n - 1:-1]
    lp = np.asarray(jax.nn.log_softmax(at, -1))[
        np.arange(len(req.output)), req.output]
    first = np.asarray(at.argmax(-1)) == np.asarray(req.output)
    return first.mean(), np.abs(lp - np.asarray(req.logprobs)).max()
