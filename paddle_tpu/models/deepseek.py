"""DeepSeek-V2/V3-style model: MLA attention + DeepSeekMoE FFN.

Reference parity: PaddleNLP paddlenlp/transformers/deepseek_v2 modeling
(the reference fork's era ships DeepSeek support as a flagship family).
TPU-native design notes:

  * **MLA (Multi-head Latent Attention)**: K/V are generated from a
    low-rank latent `c_kv = x·W_dkv` (dim kv_lora_rank ≪ H), plus a
    decoupled RoPE branch of dim qk_rope_head_dim shared across heads.
    This eager stack up-projects K and V and keeps no cache. The served
    path that caches the latent itself (one row a token for all heads,
    the keys' up-projection absorbed into the query) is
    `models/glm_dsa.py` over `kernels/ragged_latent.py`
    (docs/serving.md § Cache groups by plane). Projections are plain
    matmuls (MXU); attention runs through the flashmask kernels after
    up-projection, keys `qk_nope + qk_rope` wide and values `v_head_dim`
    (the kernels take a value width of their own). The TRAINED path of
    the family is the functional step `models/deepseek_spmd.py`
    (stacked parameters, sigmoid `noaux_tc` routing, dropless experts
    in row blocks, one chip's share of a deployment); this eager stack
    keeps the capacity-bounded softmax gate below.
  * **MoE FFN**: shared experts + routed experts with top-k gating and
    the load-balance aux loss, reusing parallel.moe's EP dispatch.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from .. import nn
from .._core.tensor import Tensor, apply
from ..nn.initializer import Normal
from ..ops.flashmask_attention import flashmask_attention_bhsd
from ..ops.rope import rope_cos_sin
from .llama import LlamaConfig, LlamaMLP
from .moe_llm import MoEDecoderLayer


@dataclass(unsafe_hash=True)
class DeepSeekConfig(LlamaConfig):
    kv_lora_rank: int = 512
    q_lora_rank: int = 0              # 0 = full-rank q projection
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 8
    n_shared_experts: int = 1
    num_experts_per_tok: int = 2
    moe_intermediate_size: int = 0    # 0 = intermediate_size
    first_k_dense_replace: int = 1    # leading dense layers before MoE
    aux_loss_alpha: float = 0.001
    # the published router (`modeling_deepseek.py`), read by the functional
    # training step (`deepseek_spmd.py`); the eager stack below keeps the
    # softmax gate of `parallel/moe.top_k_gating`
    scoring_func: str = "softmax"     # or "sigmoid"
    topk_method: str = "greedy"       # "noaux_tc": top k of score + bias
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    # one chip's share of a deployment (as `GlmDsaConfig` names them): the
    # routed experts HELD here, None = all, from `first_expert`
    experts_held: int | None = None
    first_expert: int = 0

    @classmethod
    def tiny_mla(cls, vocab=128, hidden=64, layers=2, heads=4):
        return cls(vocab_size=vocab, hidden_size=hidden,
                   intermediate_size=hidden * 2, num_hidden_layers=layers,
                   num_attention_heads=heads, num_key_value_heads=heads,
                   kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
                   v_head_dim=16, n_routed_experts=4, n_shared_experts=1,
                   num_experts_per_tok=2, moe_intermediate_size=hidden,
                   max_position_embeddings=256)


class MLAttention(nn.Layer):
    """Multi-head latent attention. Shapes:

    q:        x → (B,S,H·(d_nope+d_rope))   [optionally via q LoRA]
    latent:   x → c_kv (B,S,r) ⊕ k_rope (B,S,d_rope)   ← the cacheable part
    k,v:      c_kv → per-head k_nope (d_nope), v (d_v); k = [k_nope;k_rope]
    """

    def __init__(self, config: DeepSeekConfig):
        super().__init__()
        c = config
        self.nh = c.num_attention_heads
        self.d_nope = c.qk_nope_head_dim
        self.d_rope = c.qk_rope_head_dim
        self.d_v = c.v_head_dim
        self.r = c.kv_lora_rank
        H = c.hidden_size
        init = nn.ParamAttr(initializer=Normal(0.0, c.initializer_range))
        qd = self.nh * (self.d_nope + self.d_rope)
        self.q_proj = nn.Linear(H, qd, weight_attr=init, bias_attr=False)
        # latent: compressed kv + shared rope key
        self.kv_down = nn.Linear(H, self.r + self.d_rope, weight_attr=init,
                                 bias_attr=False)
        self.kv_norm = nn.RMSNorm(self.r, epsilon=c.rms_norm_eps)
        self.kv_up = nn.Linear(self.r, self.nh * (self.d_nope + self.d_v),
                               weight_attr=init, bias_attr=False)
        self.o_proj = nn.Linear(self.nh * self.d_v, H, weight_attr=init,
                                bias_attr=False)
        self.rope_theta = c.rope_theta

    def forward(self, x, cos, sin):
        b, s, H = x.shape
        nh, dn, dr, dv, r = self.nh, self.d_nope, self.d_rope, self.d_v, \
            self.r

        def fn(xr, wq, wdown, gnorm, wup, wo, cosr, sinr):
            q = (xr @ wq).reshape(b, s, nh, dn + dr)
            q_nope, q_rope = q[..., :dn], q[..., dn:]
            down = xr @ wdown                          # (B,S,r+dr)
            c_kv, k_rope = down[..., :r], down[..., r:]
            cf = c_kv.astype(jnp.float32)
            c_kv = (cf * jax.lax.rsqrt(
                jnp.mean(cf * cf, -1, keepdims=True) + 1e-5) *
                gnorm.astype(jnp.float32)).astype(xr.dtype)
            kv = (c_kv @ wup).reshape(b, s, nh, dn + dv)
            k_nope, v = kv[..., :dn], kv[..., dn:]

            def rot(t, cos_, sin_):
                half = t.shape[-1] // 2
                t1, t2 = t[..., :half], t[..., half:]
                rot_t = jnp.concatenate([-t2, t1], axis=-1)
                return t * cos_ + rot_t * sin_

            # decoupled rope: q per head, k shared across heads
            q_rope = rot(q_rope, cosr[None, :, None], sinr[None, :, None])
            k_rope = rot(k_rope, cosr[None], sinr[None])
            k_rope_h = jnp.broadcast_to(k_rope[:, :, None],
                                        (b, s, nh, dr))
            qh = jnp.concatenate([q_nope, q_rope], -1).swapaxes(1, 2)
            kh = jnp.concatenate([k_nope, k_rope_h], -1).swapaxes(1, 2)
            # the flashmask entry takes the values at their own width
            # (d_v != d_qk): no padded P V or dO V product. Static python
            # float: sm_scale is a nondiff argnum of the pallas custom_vjp
            # — a traced array would fail under jit on TPU
            o = flashmask_attention_bhsd(qh, kh, v.swapaxes(1, 2),
                                         causal=True,
                                         sm_scale=1.0 / math.sqrt(dn + dr))
            return o.swapaxes(1, 2).reshape(b, s, nh * dv) @ wo

        return apply(fn, x, self.q_proj.weight, self.kv_down.weight,
                     self.kv_norm.weight, self.kv_up.weight,
                     self.o_proj.weight, Tensor(cos), Tensor(sin),
                     name="mla_attention")


class DeepSeekDecoderLayer(nn.Layer):
    def __init__(self, config: DeepSeekConfig, layer_idx: int):
        super().__init__()
        c = config
        self.input_layernorm = nn.RMSNorm(c.hidden_size,
                                          epsilon=c.rms_norm_eps)
        self.self_attn = MLAttention(c)
        self.post_attention_layernorm = nn.RMSNorm(c.hidden_size,
                                                   epsilon=c.rms_norm_eps)
        if layer_idx < c.first_k_dense_replace:
            self.mlp = LlamaMLP(c)
            self.is_moe = False
        else:
            from ..parallel.moe import MoELayer
            inter = c.moe_intermediate_size or c.intermediate_size
            self.mlp = MoELayer(c.hidden_size, inter,
                                num_experts=c.n_routed_experts,
                                top_k=c.num_experts_per_tok,
                                num_shared_experts=c.n_shared_experts)
            self.is_moe = True

    def forward(self, x, cos, sin):
        h = x + self.self_attn(self.input_layernorm(x), cos, sin)
        m = self.mlp(self.post_attention_layernorm(h))
        if isinstance(m, tuple):
            m = m[0]
        return h + m


class DeepSeekForCausalLM(nn.Layer):
    def __init__(self, config: DeepSeekConfig):
        super().__init__()
        c = self.config = config
        init = nn.ParamAttr(initializer=Normal(0.0, c.initializer_range))
        self.embed_tokens = nn.Embedding(c.vocab_size, c.hidden_size,
                                         weight_attr=init)
        self.layers = nn.LayerList([DeepSeekDecoderLayer(c, i)
                                    for i in range(c.num_hidden_layers)])
        self.norm = nn.RMSNorm(c.hidden_size, epsilon=c.rms_norm_eps)
        self.lm_head = nn.Linear(c.hidden_size, c.vocab_size,
                                 weight_attr=init, bias_attr=False)

    def forward(self, input_ids, labels=None):
        from ..nn import functional as F
        c = self.config
        s = input_ids.shape[1]
        cos, sin = rope_cos_sin(s, c.qk_rope_head_dim, base=c.rope_theta)
        x = self.embed_tokens(input_ids)
        for layer in self.layers:
            x = layer(x, cos, sin)
        logits = self.lm_head(self.norm(x))
        if labels is not None:
            loss = F.cross_entropy(logits, labels, reduction="mean")
            return loss, logits
        return logits
