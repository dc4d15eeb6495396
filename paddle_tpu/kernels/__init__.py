"""TPU pallas kernels that are engine-shaped rather than op-shaped.

`paddle_tpu.ops` holds kernels with framework-level contracts (flash
attention, paged decode/verify attention); this package holds kernels
written against the serving engine's own data layout — the ragged
paged-attention core behind `unified_step` (docs/serving.md § Unified
ragged step), its latent siblings (`ragged_latent`), the state-space
layers' segmented scan and carried convolution (`ragged_ssm`) and the
experts' whole-matrix grouped product (`grouped_matmul`), each imported
from its own module. CPU sessions import only the pure-jnp
reference path; the pallas lowering is reached on TPU or under
interpret mode in tests.
"""
from .ragged_paged_attention import (ragged_paged_attention,
                                     ragged_paged_attention_reference,
                                     ragged_runs, ragged_tile)

__all__ = ["ragged_paged_attention", "ragged_paged_attention_reference",
           "ragged_runs", "ragged_tile"]
