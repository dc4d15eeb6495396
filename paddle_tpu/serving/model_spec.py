"""What `ServingEngine` asks of a model: a cache spec and a step.

The engine owns slots, pages, plans, tickets and the pump; a model owns
its layers. They meet here. A configuration class answers
`serving_model()` with a `ServingModel`, and the engine builds one page
pool, one page table and one allocator for each `CacheGroup` (a layer
TYPE: layers whose pages live and die together), then calls `step` with
all of them every wave. Plain data, no jax: `serving/` stays free of
model code.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Mapping, Optional, Tuple

__all__ = ["CacheGroup", "ServingModel"]


@dataclasses.dataclass(frozen=True)
class CacheGroup:
    """The layers of one type and what a token costs them.

    `stacks`: how the group's layers are laid out in pool arrays, each
    `(layers, kv_heads, pages, page, head_dim)`: `(L,)` is one array
    the model scans over (`unified_step`, which writes and reads the
    carried stack through a layer index), `(1,) * L` one array a layer
    (an unrolled model); donated, either is updated in place.
    `window`: None, a slot holds every page of its context; W, a row at
    position p sees columns j with 0 <= p - j < W, and the engine gives
    a page back in the turn its last column falls behind every row the
    slot will still feed."""
    name: str
    stacks: Tuple[int, ...]
    kv_heads: int
    head_dim: int
    window: Optional[int] = None

    @property
    def layers(self):
        return sum(self.stacks)

    def bytes_per_token(self, itemsize):
        """Keys and values of one token in all the group's layers."""
        return 2 * self.layers * self.kv_heads * self.head_dim * itemsize


@dataclasses.dataclass(frozen=True)
class ServingModel:
    """`groups`: the cache spec. `q_group`: the largest number of query
    heads that share a KV head (the ragged kernel's tile is derived for
    it). `step(params, caches, tables, tokens, tok_slot, tok_pos,
    config, page_size, **kw)` is `unified_step`'s descriptor contract
    over every group at once: `caches[g][i]` is `(k, v, k_scale,
    v_scale)` of group g's i-th stack, `tables[g]` its page table; it
    returns `(caches, logits, rec, tok_buf, aux)`, `aux` a dict of
    small device arrays the step's record carries beside the tokens
    (`moe_rows`: a sparse layer x the rows each expert got).
    `step` DONATES `caches` and the pools come back where they lay
    (`unified_step`, `laguna_step`; held to the compiled programs by
    `tests/test_tpu_lowering.py`), so a second step in flight needs no
    further copy of them: that is what lets the scheduler run a ragged
    engine one step deep. A caller rebinds the pools from the result.
    `unsupported`: engine feature -> why this model cannot run under it;
    the engine refuses at construction with that reason."""
    groups: Tuple[CacheGroup, ...]
    q_group: int
    step: Callable
    unsupported: Mapping[str, str] = dataclasses.field(default_factory=dict)
