"""What `ServingEngine` asks of a model: a cache spec and a step.

The engine owns slots, pages, plans, tickets and the pump; a model owns
its layers. They meet here. A configuration class answers
`serving_model()` with a `ServingModel`, and the engine builds one page
pool, one page table and one allocator for each `CacheGroup` (a layer
TYPE: layers whose pages live and die together) and one array for each
`SlotState` (what a layer keeps a SLOT, whatever the context's length),
then calls `step` with all of them every wave. Plain data, no jax:
`serving/` stays free of model code.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Mapping, Optional, Tuple

__all__ = ["CacheGroup", "Plane", "ServingModel", "SlotState"]


@dataclasses.dataclass(frozen=True)
class Plane:
    """One thing a layer keeps a token: `width` values, for each of the
    group's `kv_heads` (`per_head`) or once for all heads, in the cache's
    type or in the plane's own `dtype` (a name: `float8_e4m3fn`)."""
    name: str
    width: int
    per_head: bool = True
    dtype: Optional[str] = None


@dataclasses.dataclass(frozen=True)
class CacheGroup:
    """The layers of one type and what a token costs them.

    `planes`: what a layer keeps a token, each a pool array of its own
    width on the group's one page table (a page lives and dies in all of
    them together). None is the K/V case, keys and values of `head_dim`
    for each of `kv_heads`; a latent-attention layer states its own
    (`models/glm_dsa.py`: one shared latent row and one index key).
    `stacks`: how the group's layers are laid out in pool arrays, each
    `(layers, heads, pages, page, row)` a plane: `(L,)` is one array
    the model scans over (`unified_step`, which writes and reads the
    carried stack through a layer index), `(1,) * L` one array a layer
    (an unrolled model); donated, either is updated in place. A layer
    here is a CACHE layer, something that keeps a row a token: a model
    layer with two attention sublayers is two of them
    (`models/longcat_flash.py`: `(1,) * 2L`), and `layers`,
    `bytes_per_token` and the per-group counters count these.
    `window`: None, a slot holds every page of its context; W, a row at
    position p sees columns j with 0 <= p - j < W, and the engine gives
    a page back in the turn its last column falls behind every row the
    slot will still feed.
    `select`: None, a row attends to all it sees; k, the model picks at
    most k of those positions a row and layer (a learned sparse
    selection): nothing the engine acts on, it counts the rows, the
    columns scored and the positions kept (`pt_dsa_*`)."""
    name: str
    stacks: Tuple[int, ...]
    kv_heads: int
    head_dim: int
    window: Optional[int] = None
    planes: Optional[Tuple[Plane, ...]] = None
    select: Optional[int] = None

    def __post_init__(self):
        if self.planes is None:
            object.__setattr__(self, "planes", (
                Plane("k", self.head_dim), Plane("v", self.head_dim)))

    @property
    def layers(self):
        return sum(self.stacks)

    @property
    def latent(self):
        """Whether a layer keeps a token ONE row for all its heads: the
        kernels of `kernels/ragged_latent.py` walk such a group, at a
        tile of their own (`pt_latent_runs`, `pt_latent_trips`)."""
        return not self.planes[0].per_head

    def bytes_per_token(self, itemsize):
        """What one token keeps in all the group's layers, by plane:
        keys and values in the K/V case. Every plane at the cache's
        `itemsize`: an upper count where a plane states a narrower
        `dtype` of its own (plain data, no jax: no type is looked up
        here)."""
        return self.layers * itemsize * sum(
            p.width * (self.kv_heads if p.per_head else 1)
            for p in self.planes)


@dataclasses.dataclass(frozen=True)
class SlotState:
    """What each of `layers` layers keeps a SLOT and not a token: `shape`
    values in `dtype` (a name; None: the engine's own type), the same
    bytes at position 10 and at position 100,000 (a state-space layer's
    recurrent state, the last rows of its convolution). The engine
    allocates `(layers, max_seqs) + shape`, zero, donates it to `step`
    with the pools and rebinds it from the result. It never touches the
    values: a run of rows that begins at position 0 begins from zero
    state, which the step reads from `tok_pos`, so a slot taken again
    (a new request, or one preempted and fed again from its first
    token) costs the device nothing and leaks nothing; a prompt longer
    than the row buffer carries its state from step to step because the
    slot does."""
    name: str
    layers: int
    shape: Tuple[int, ...]
    dtype: Optional[str] = None

    def bytes_per_slot(self, itemsize):
        """What one slot keeps in all the layers, at `itemsize` bytes a
        value (plain data, no jax: no type is looked up here)."""
        n = self.layers * itemsize
        for d in self.shape:
            n *= d
        return n


@dataclasses.dataclass(frozen=True)
class ServingModel:
    """`groups`: the cache spec by token; `slot_states`: what is kept a
    slot beside it (none, for a model whose every layer keeps pages).
    `q_group`: the largest number of query heads that share a KV head
    (the ragged kernel's tile is derived for it). `step(params, caches,
    tables, tokens, tok_slot, tok_pos, config, page_size, **kw)` is
    `unified_step`'s descriptor contract over every group at once:
    `caches[g][i]` is group g's i-th stack, its planes' pools and then
    their scales (`(k, v, k_scale, v_scale)` in the K/V case),
    `tables[g]` its page table; after the groups' entries `caches`
    holds one array for each of `slot_states`, in their order. It
    returns `(caches, logits, rec, tok_buf, aux)`, `aux` a dict of
    small device arrays the step's record carries beside the tokens
    (`moe_rows`: a sparse layer x the rows each expert held here got;
    `moe_elsewhere`: its assignments to real experts held elsewhere;
    `moe_zero`: its assignments to identity experts, nobody's to hold;
    `ssm_runs`, `ssm_runs_fresh`, `ssm_rows`: the runs of rows a slot's
    state advanced over in one layer, those that began from zero, and
    their rows).
    `step` DONATES `caches`, and pools and states come back where they
    lay (`unified_step`, `laguna_step`, `nemotron_step`; held to the
    compiled programs by `tests/test_tpu_lowering.py`), so a second
    step in flight needs no further copy of them: that is what lets the
    scheduler run a ragged engine one step deep. A caller rebinds them
    from the result.
    `unsupported`: engine feature -> why this model cannot run under it;
    the engine refuses at construction with that reason.
    `rows`: the rows a step should hold (the engine's flat row buffer),
    where the model knows better than the engine's rule, a power of two
    over its slots: a model whose prompts run to tens of thousands of
    tokens starves its slots on a buffer sized for chat.
    `experts`: (the experts a row picks, the experts it picks among) of
    the model's dropless expert layers, None for a model that has none:
    with the buffer's rows they say what `parallel/moe.dropless_experts`
    launches its grouped products over, from which the engine counts the
    row tiles the step's `moe_rows` record spans (`pt_moe_row_tiles`)."""
    groups: Tuple[CacheGroup, ...]
    q_group: int
    step: Callable
    unsupported: Mapping[str, str] = dataclasses.field(default_factory=dict)
    rows: Optional[int] = None
    experts: Optional[Tuple[int, int]] = None
    slot_states: Tuple[SlotState, ...] = ()
