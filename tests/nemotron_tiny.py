"""A Nemotron-H at toy size for the CPU tests: the published pattern's
first nine letters (`MEMEM*EME`: four Mamba-2 mixers, four expert layers,
one attention layer), 4 heads of 8 over a state of 16 in 2 groups, a
convolution of 4, 2 experts a token of 8, 8 query heads over 2 KV heads."""
import numpy as np

import jax
import jax.numpy as jnp

from paddle_tpu.models import nemotron_h as nh
from paddle_tpu.models.llama_serving import Request, ServingEngine

from benchmarks.reference import nemotron_h as reference


def tiny_model(layers=9):
    """The configuration as a config.json's dictionary (what the reference
    takes); `NemotronHConfig.from_dict` makes the program's of it."""
    return dict(
        vocab_size=128, hidden_size=32, num_hidden_layers=layers,
        hybrid_override_pattern=nh.PATTERN, num_attention_heads=8,
        num_key_value_heads=2, head_dim=8, mamba_num_heads=4,
        mamba_head_dim=8, ssm_state_size=16, n_groups=2, conv_kernel=4,
        n_routed_experts=8, num_experts_per_tok=2, moe_intermediate_size=24,
        moe_shared_expert_intermediate_size=48, n_shared_experts=1,
        routed_scaling_factor=2.5, norm_topk_prob=True, n_group=1,
        topk_group=1, layer_norm_epsilon=1e-5, time_step_min=0.001,
        time_step_max=0.1, time_step_floor=1e-4, initializer_range=0.2)


def program_config(m, **kw):
    return nh.NemotronHConfig.from_dict(dict(m, **kw))


def init(m, seed=0, dtype=jnp.float32):
    return nh.init_params(program_config(m), seed, dtype)


def engine(m, params, config=None, **kw):
    kw.setdefault("max_seqs", 4)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("page_size", 4)
    kw.setdefault("ragged_tokens", 16)
    kw.setdefault("num_pages", 65)
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
