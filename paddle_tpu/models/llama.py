"""Llama-3 family (reference: PaddleNLP llm/ llama modeling — the
reference repo's north-star workload; structure mirrors
paddlenlp/transformers/llama/modeling.py but built TPU-first).

Two faces:
  * `LlamaForCausalLM` — paddle-style Layer tree (eager + jit-able).
  * `paddle_tpu.models.llama_spmd` — stacked-parameter pure-functional
    pretrain step with dp/pp/tp/sp shardings (the fleet 4D-parallel
    equivalent; used by bench + dryrun_multichip).

TPU choices: RMSNorm in fp32 accumulation, RoPE precomputed tables,
GQA flash attention (pallas), SwiGLU as one fused XLA graph, bf16
params with fp32 master weights in the optimizer.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .._core.tensor import Tensor, apply
from .. import nn
from ..nn import functional as F
from ..nn.initializer import Normal
from ..ops.rope import rope_cos_sin, apply_rotary_emb
from ..ops.flash_attention import flash_attention_bhsd


@dataclass(unsafe_hash=True)  # hashable → usable as a static jit arg
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    initializer_range: float = 0.02
    tie_word_embeddings: bool = False
    dtype: str = "float32"

    def serving_model(self):
        """How `ServingEngine` serves this configuration: its cache spec
        and step (serving/model_spec.py)."""
        from .llama_serving import llama_serving_model
        return llama_serving_model(self)

    @classmethod
    def llama3_8b(cls):
        return cls(vocab_size=128256, hidden_size=4096, intermediate_size=14336,
                   num_hidden_layers=32, num_attention_heads=32,
                   num_key_value_heads=8, max_position_embeddings=8192,
                   rope_theta=500000.0)

    @classmethod
    def tiny(cls, vocab=256, hidden=64, layers=2, heads=4, kv_heads=2, ffn=128,
             seq=128):
        return cls(vocab_size=vocab, hidden_size=hidden, intermediate_size=ffn,
                   num_hidden_layers=layers, num_attention_heads=heads,
                   num_key_value_heads=kv_heads, max_position_embeddings=seq)


class LlamaAttention(nn.Layer):
    def __init__(self, config: LlamaConfig, tp_axis="tp"):
        super().__init__()
        c = config
        self.num_heads = c.num_attention_heads
        self.num_kv_heads = c.num_key_value_heads
        self.head_dim = c.hidden_size // c.num_attention_heads
        init = Normal(0.0, c.initializer_range)
        h = c.hidden_size
        kv = self.num_kv_heads * self.head_dim
        self.q_proj = nn.Linear(h, h, weight_attr=nn.ParamAttr(initializer=init),
                                bias_attr=False)
        self.k_proj = nn.Linear(h, kv, weight_attr=nn.ParamAttr(initializer=init),
                                bias_attr=False)
        self.v_proj = nn.Linear(h, kv, weight_attr=nn.ParamAttr(initializer=init),
                                bias_attr=False)
        self.o_proj = nn.Linear(h, h, weight_attr=nn.ParamAttr(initializer=init),
                                bias_attr=False)
        # megatron TP: qkv column-parallel, o row-parallel
        for p in (self.q_proj.weight, self.k_proj.weight, self.v_proj.weight):
            p.dist_spec = P(None, tp_axis)
        self.o_proj.weight.dist_spec = P(tp_axis, None)

    def forward(self, x, cos, sin, kv_cache=None, causal=True):
        b, s, h = x.shape

        def fn(xr, wq, wk, wv, wo, cosr, sinr, *cache):
            q = (xr @ wq).reshape(b, s, self.num_heads, self.head_dim)
            k = (xr @ wk).reshape(b, s, self.num_kv_heads, self.head_dim)
            v = (xr @ wv).reshape(b, s, self.num_kv_heads, self.head_dim)
            # rope on (B, S, H, D): broadcast cos/sin over head axis
            q, k = apply_rotary_emb(q.swapaxes(1, 2), k.swapaxes(1, 2),
                                    cosr[None, None], sinr[None, None])
            v = v.swapaxes(1, 2)
            if cache:
                ck, cv = cache
                k = jnp.concatenate([ck, k], axis=2)
                v = jnp.concatenate([cv, v], axis=2)
            rep = self.num_heads // self.num_kv_heads
            if rep > 1:
                k = jnp.repeat(k, rep, axis=1)
                v = jnp.repeat(v, rep, axis=1)
            o = flash_attention_bhsd(q, k, v, causal=causal)
            o = o.swapaxes(1, 2).reshape(b, s, h)
            return o @ wo

        args = [x, self.q_proj.weight, self.k_proj.weight, self.v_proj.weight,
                self.o_proj.weight, Tensor(cos), Tensor(sin)]
        if kv_cache is not None:
            args += [kv_cache[0], kv_cache[1]]
        return apply(fn, *args, name="llama_attention")


class LlamaMLP(nn.Layer):
    def __init__(self, config: LlamaConfig, tp_axis="tp"):
        super().__init__()
        c = config
        init = Normal(0.0, c.initializer_range)
        attr = nn.ParamAttr(initializer=init)
        self.gate_proj = nn.Linear(c.hidden_size, c.intermediate_size,
                                   weight_attr=attr, bias_attr=False)
        self.up_proj = nn.Linear(c.hidden_size, c.intermediate_size,
                                 weight_attr=attr, bias_attr=False)
        self.down_proj = nn.Linear(c.intermediate_size, c.hidden_size,
                                   weight_attr=attr, bias_attr=False)
        self.gate_proj.weight.dist_spec = P(None, tp_axis)
        self.up_proj.weight.dist_spec = P(None, tp_axis)
        self.down_proj.weight.dist_spec = P(tp_axis, None)

    def forward(self, x):
        def fn(xr, wg, wu, wd):
            from ..ops.fused import fused_swiglu
            return fused_swiglu(xr, wg, wu, wd)
        return apply(fn, x, self.gate_proj.weight, self.up_proj.weight,
                     self.down_proj.weight, name="llama_mlp")


class LlamaDecoderLayer(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.input_layernorm = nn.RMSNorm(config.hidden_size,
                                          epsilon=config.rms_norm_eps)
        self.self_attn = LlamaAttention(config)
        self.post_attention_layernorm = nn.RMSNorm(config.hidden_size,
                                                   epsilon=config.rms_norm_eps)
        self.mlp = LlamaMLP(config)

    def forward(self, x, cos, sin, kv_cache=None, causal=True):
        h = x + self.self_attn(self.input_layernorm(x), cos, sin, kv_cache,
                               causal)
        return h + self.mlp(self.post_attention_layernorm(h))


class LlamaModel(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        init = Normal(0.0, config.initializer_range)
        self.embed_tokens = nn.Embedding(
            config.vocab_size, config.hidden_size,
            weight_attr=nn.ParamAttr(initializer=init))
        self.embed_tokens.weight.dist_spec = P("tp", None)
        self.layers = nn.LayerList([LlamaDecoderLayer(config)
                                    for _ in range(config.num_hidden_layers)])
        self.norm = nn.RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)
        self._rope_cache = {}

    def rope(self, seq_len, dtype=jnp.float32, offset=0):
        key = (seq_len + offset, str(dtype))
        if key not in self._rope_cache:
            self._rope_cache[key] = rope_cos_sin(
                seq_len + offset, self.config.hidden_size //
                self.config.num_attention_heads, self.config.rope_theta, dtype)
        cos, sin = self._rope_cache[key]
        return cos[offset:], sin[offset:]

    def forward(self, input_ids, position_offset=0, kv_caches=None, causal=True):
        s = input_ids.shape[1]
        cos, sin = self.rope(s, offset=position_offset)
        x = self.embed_tokens(input_ids)
        for i, layer in enumerate(self.layers):
            cache = kv_caches[i] if kv_caches is not None else None
            x = layer(x, cos, sin, cache, causal)
        return self.norm(x)


class LlamaForCausalLM(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.llama = LlamaModel(config)
        if not config.tie_word_embeddings:
            self.lm_head = nn.Linear(
                config.hidden_size, config.vocab_size,
                weight_attr=nn.ParamAttr(
                    initializer=Normal(0.0, config.initializer_range)),
                bias_attr=False)
            self.lm_head.weight.dist_spec = P(None, "tp")
        else:
            self.lm_head = None

    def forward(self, input_ids, labels=None, position_offset=0, kv_caches=None):
        h = self.llama(input_ids, position_offset, kv_caches)
        if self.lm_head is not None:
            logits = self.lm_head(h)
        else:
            from ..tensor.linalg import matmul
            logits = matmul(h, self.llama.embed_tokens.weight, transpose_y=True)
        if labels is not None:
            loss = F.cross_entropy(logits, labels, reduction="mean")
            return loss, logits
        return logits

    def generate(self, input_ids, max_new_tokens=32, temperature=1.0, top_k=0,
                 top_p=1.0, eos_token_id=None):
        from .generation import generate as _gen
        return _gen(self, input_ids, max_new_tokens, temperature, top_k, top_p,
                    eos_token_id)
