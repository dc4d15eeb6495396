"""A LongCat-Flash at toy size for the CPU tests: double layers of 4 heads
over a latent of 16 + 4, two dense feed-forwards of 64, a router over 8
real and 4 identity experts that keeps 3, of which this share holds 2
(real experts 2 and 3)."""
import numpy as np

import jax
import jax.numpy as jnp

from paddle_tpu.models.llama_serving import Request, ServingEngine

from benchmarks.models.longcat_flash import program_config
from benchmarks.reference import longcat_flash as reference


def tiny_model(layers=2, held=2, first=2):
    """The configuration as the benchmark's `model` dictionary (what the
    reference takes): `n_routed_experts` HELD of `router_experts`."""
    return dict(
        vocab_size=128, hidden_size=32, ffn_hidden_size=64,
        expert_ffn_hidden_size=16, num_layers=layers, num_attention_heads=4,
        q_lora_rank=16, kv_lora_rank=8, qk_nope_head_dim=8,
        qk_rope_head_dim=4, v_head_dim=8, mla_scale_q_lora=True,
        mla_scale_kv_lora=True, n_routed_experts=held, router_experts=8,
        first_expert=first, zero_expert_num=4, moe_topk=3,
        routed_scaling_factor=6.0, rms_norm_eps=1e-5, rope_theta=10000.0,
        initializer_range=0.2)


def init(m, seed=1):
    """Seeded weights; the correction bias is given values a fault in it
    would show at."""
    from paddle_tpu.models.longcat_flash import init_params
    params = init_params(program_config(m), seed=seed)
    rng = np.random.default_rng(seed)
    for lp in params["layers"]:
        lp["router_bias"] = jnp.asarray(
            rng.normal(size=lp["router_bias"].shape) * 0.05, jnp.float32)
    return params


def engine(m, params, config=None, **kw):
    kw.setdefault("max_seqs", 4)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("page_size", 4)
    kw.setdefault("ragged_tokens", 16)
    kw.setdefault("num_pages", 70)
    return ServingEngine(params, config or program_config(m), **kw)


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
