"""Every Pallas family must LOWER for the TPU with x64 on, from a CPU host.

The kernel tests run `interpret=True`, which never reaches the Mosaic
lowering — a CPU-only change can therefore break the chip unnoticed (a
Python float inside a kernel body becomes an f64 constant under
`jax_enable_x64`; a primitive without a TPU rule raises). Cross-lowering
(`lowering_platforms=("tpu",)`) builds the Mosaic module without a chip
and without compiling, so this stays a few seconds. What it cannot see
is Mosaic's own compile step: that is `chip_smoke.py`'s kernels phase.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu  # noqa: F401 — turns jax_enable_x64 on, as in production

D, GROUP, PAGE = 128, 4, 16     # the serving shapes: head_dim, GQA group, page


def _lower_tpu(fn, *args, **kwargs):
    assert jax.config.jax_enable_x64
    jitted = fn if hasattr(fn, "trace") else jax.jit(fn)
    text = jitted.trace(*args, **kwargs).lower(
        lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text
    return text


def _bhsd(s=256, h=2):
    return [jnp.ones((1, h, s, D), jnp.bfloat16)] * 3


def _pool(dtype, kvh=2, pages=9):
    k = jnp.ones((kvh, pages, PAGE, D), dtype)
    sc = jnp.ones((kvh, pages, PAGE, 1), jnp.float32) \
        if dtype == jnp.int8 else None
    return k, sc


def test_flash_fwd_bwd_lowers():
    from paddle_tpu.ops.flash_attention import flash_attention_bhsd

    def loss(q, k, v):
        return flash_attention_bhsd(q, k, v, causal=True, use_pallas=True,
                                    interpret=False).astype(jnp.float32).sum()

    _lower_tpu(jax.grad(loss, (0, 1, 2)), *_bhsd())
    # ragged tail block: the masked-operand branches
    _lower_tpu(jax.grad(loss, (0, 1, 2)), *_bhsd(s=200))


def test_varlen_fwd_bwd_lowers():
    from paddle_tpu.ops.varlen_attention import flash_attn_unpadded
    cu = jnp.asarray([0, 100, 256], jnp.int32)
    q = jnp.ones((256, 2, D), jnp.bfloat16)

    def loss(q, k, v):
        o, _ = flash_attn_unpadded(q, k, v, cu, cu, causal=True,
                                   use_pallas=True, interpret=False)
        return o.astype(jnp.float32).sum()

    _lower_tpu(jax.grad(loss, (0, 1, 2)), q, q, q)


@pytest.mark.parametrize("dropout", [0.0, 0.3])
def test_flashmask_fwd_bwd_lowers(dropout):
    from paddle_tpu.ops.flashmask_attention import flashmask_attention_bhsd
    s = 256
    sri = jnp.full((1, 2, s, 1), s, jnp.int32)

    def loss(q, k, v):
        return flashmask_attention_bhsd(
            q, k, v, sri, causal=True, use_pallas=True, interpret=False,
            dropout=dropout, dropout_seed=7).astype(jnp.float32).sum()

    _lower_tpu(jax.grad(loss, (0, 1, 2)), *_bhsd(s))


@pytest.mark.parametrize("cache", [jnp.bfloat16, jnp.int8])
def test_paged_decode_and_verify_lower(cache):
    from paddle_tpu.ops.paged_attention import (paged_attention,
                                                paged_verify_attention)
    kvh, b = 2, 2
    kp, sc = _pool(cache, kvh)
    table = jnp.zeros((b, 4), jnp.int32)
    lens = jnp.asarray([40, 7], jnp.int32)
    q = jnp.ones((b, kvh * GROUP, D), jnp.bfloat16)
    _lower_tpu(lambda q, kp: paged_attention(
        q, kp, kp, table, lens, use_pallas=True, interpret=False,
        k_scale=sc, v_scale=sc), q, kp)
    qv = jnp.ones((b, kvh * GROUP, 4, D), jnp.bfloat16)
    _lower_tpu(lambda q, kp: paged_verify_attention(
        q, kp, kp, table, lens, use_pallas=True, interpret=False,
        k_scale=sc, v_scale=sc), qv, kp)


@pytest.mark.parametrize("cache", [jnp.bfloat16, jnp.int8])
def test_ragged_lowers_without_barrier(cache):
    from paddle_tpu.kernels.ragged_paged_attention import (
        ragged_paged_attention)
    kvh, t = 2, 16
    kp, sc = _pool(cache, kvh)
    table = jnp.zeros((2, 4), jnp.int32)
    slot = jnp.zeros((t,), jnp.int32)
    pos = jnp.arange(t, dtype=jnp.int32) - 2
    q = jnp.ones((t, kvh * GROUP, D), jnp.bfloat16)
    text = _lower_tpu(lambda q, kp: ragged_paged_attention(
        q, kp, kp, table, slot, pos, use_pallas=True, interpret=False,
        k_scale=sc, v_scale=sc, block_pages=2), q, kp)
    assert "optimization_barrier" not in text


@pytest.mark.parametrize("cache", [None, "int8"])
def test_engine_unified_step_lowers(monkeypatch, cache):
    """The engine's OWN first unified_step call (captured, not
    re-imagined), re-lowered for the chip with use_pallas=True."""
    from paddle_tpu.models import llama_serving as ls
    from paddle_tpu.models import llama_spmd as spmd
    from paddle_tpu.models.llama import LlamaConfig
    cfg = LlamaConfig(vocab_size=256, hidden_size=GROUP * 2 * D,
                      intermediate_size=256, num_hidden_layers=2,
                      num_attention_heads=GROUP * 2,
                      num_key_value_heads=2)
    params = spmd.init_params(cfg, seed=0, dtype=jnp.bfloat16)
    eng = ls.ServingEngine(params, cfg, max_seqs=2, max_seq_len=64,
                           page_size=PAGE, dtype=jnp.bfloat16,
                           cache_dtype=cache, use_pallas=False)
    seen = []
    real = ls.unified_step

    def capture(*a, **kw):
        seen.append((a, kw))
        return real(*a, **kw)

    monkeypatch.setattr(ls, "unified_step", capture)
    eng.submit(ls.Request(0, np.arange(1, 20).tolist(), max_new_tokens=2))
    eng.step()
    assert seen, "engine did not dispatch unified_step"
    a, kw = seen[0]
    kw = dict(kw, use_pallas=True, interpret=False)
    text = _lower_tpu(real.__wrapped__, *a, **kw)
    # the step's own, behind a layer's q, k and v products
    # ([weight-slices]); the attention reference's are not on this path
    assert text.count("optimization_barrier") == 1


def test_mesh_train_step_lowers(monkeypatch):
    """GSPMD cannot partition a Mosaic kernel: on a dp x tp mesh the
    flash kernel must sit in its own shard_map or lowering raises."""
    import importlib
    from paddle_tpu.models import llama_spmd as spmd
    from paddle_tpu.models.llama import LlamaConfig
    from paddle_tpu.parallel.mesh import create_mesh
    fa = importlib.import_module("paddle_tpu.ops.flash_attention")
    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    cfg = LlamaConfig(vocab_size=256, hidden_size=2 * D,
                      intermediate_size=256, num_hidden_layers=1,
                      num_attention_heads=2, num_key_value_heads=2)
    mesh = create_mesh({"dp": 2, "tp": 2}, devices=jax.devices()[:4])
    params = spmd.place_params(
        spmd.init_params(cfg, seed=0, dtype=jnp.bfloat16), cfg, mesh)
    step = spmd.make_train_step(cfg, mesh, fused_ce=True, donate=False)
    ids = np.zeros((4, 256), np.int32)
    text = _lower_tpu(step, params, spmd.init_opt_state(params),
                      jnp.asarray(0), (ids, ids))
    assert text.count("tpu_custom_call") >= 3      # fwd, dq, dkv


def test_deepseek_train_step_lowers(monkeypatch):
    """The functional step of the DeepSeek-V3 family with its kernels on:
    the flashmask kernels at a value width of their own (keys 128 + 64,
    values 128), the experts' grouped products in row blocks and their
    hand-written backward pass, the fused loss."""
    import importlib
    from paddle_tpu.models import deepseek_spmd as ds
    from paddle_tpu.models.deepseek import DeepSeekConfig
    from paddle_tpu.parallel.mesh import create_mesh
    fm = importlib.import_module("paddle_tpu.ops.flashmask_attention")
    monkeypatch.setattr(fm, "_on_tpu", lambda: True)
    cfg = DeepSeekConfig(
        vocab_size=512, hidden_size=256, intermediate_size=512,
        num_hidden_layers=2, num_attention_heads=2, num_key_value_heads=2,
        kv_lora_rank=128, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, n_routed_experts=8, experts_held=2, first_expert=2,
        n_shared_experts=2, num_experts_per_tok=3, moe_intermediate_size=128,
        first_k_dense_replace=1, scoring_func="sigmoid",
        topk_method="noaux_tc", routed_scaling_factor=2.446)
    mesh = create_mesh({"dp": 1}, devices=jax.devices()[:1])
    params = ds.init_params(cfg, seed=0, dtype=jnp.bfloat16)
    step = ds.make_train_step(cfg, mesh)
    ids = np.zeros((2, 256), np.int32)
    text = _lower_tpu(step.jitted, params, ds.init_opt_state(params),
                      jnp.asarray(0), (ids, ids, ids))
    # fwd, dq, dkv in each of the two stacks (dense, expert layers)
    assert text.count("tpu_custom_call") >= 6
    assert "ragged_dot" in text


# ---------------------------------------------------------------------------
# Mosaic's own compile step, for a chip that is described and not attached
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu, or its lock is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(v5e):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(v5e.devices[0])


def _kernel_grids(text):
    """The grid of every Pallas kernel in a compiled program's text, in
    the program's order: a `tpu_custom_call` carries its Mosaic module as
    bytecode, whose function states its `iteration_bounds`."""
    import base64
    import json
    import re
    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir
    ctx = mlir.make_ir_context()
    ctx.allow_unregistered_dialects = True
    grids = []
    for line in text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        at = line.index("backend_config=") + len("backend_config=")
        config, _ = json.JSONDecoder().raw_decode(line[at:])
        body = base64.b64decode(config["custom_call_config"]["body"])
        with ctx:
            bounds = re.search(r"iteration_bounds = array<i64: ([\d, ]*)>",
                               str(ir.Module.parse(body)))
        grids.append(tuple(int(n) for n in bounds.group(1).split(",")))
    return grids


def _sample_shapes(arg, slots):
    """A step's per-slot sampling arrays (`_sample_record`), as shapes."""
    return {"temp": arg((slots,), jnp.float32),
            "top_k": arg((slots,), jnp.int32),
            "top_p": arg((slots,), jnp.float32),
            "key": arg((slots, 2), jnp.uint32),
            "eos": arg((slots,), jnp.int32),
            "remaining": arg((slots,), jnp.int32)}


_CALLED = r"(?:calls|to_apply|body|condition|true_computation|" \
          r"false_computation)=%?([\w.-]+)"


def _computations(text):
    """A compiled module's text -> {computation: its lines}, the entry
    under `ENTRY`."""
    import re
    comps, name = {}, None
    for line in text.splitlines():
        m = re.match(r"\s*(ENTRY\s+)?%?([\w.-]+)\s.*->.*\{\s*$", line)
        if m:
            name = "ENTRY" if m.group(1) else m.group(2)
            comps[name] = []
        elif name is not None:
            comps[name].append(line)
    return comps


def _branches(line):
    """The branch computations of a `conditional`'s line, in order."""
    import re
    return [x.strip().lstrip("%") for grp in
            re.findall(r"branch_computations=\{([^}]*)\}", line)
            for x in grp.split(",")]


def _reach(comps, start, through_conditionals=True):
    """Every computation `start` reaches: fusions, calls, loops and, if
    asked, the branches of conditionals."""
    import re
    seen, todo = set(), [start]
    while todo:
        c = todo.pop()
        if c in seen:
            continue
        seen.add(c)
        for line in comps[c]:
            if through_conditionals or " conditional(" not in line:
                todo += re.findall(_CALLED, line) + _branches(line)
    return seen


def _vocab_sorts(text, rows, vocab):
    """The `sort`s over a (rows, vocab) operand in a compiled step's
    text -> (in the step's own body, under a `conditional`'s branch).
    The body is every computation the entry reaches without going
    through a conditional's branch: fusions, calls, loops."""
    import re
    comps = _computations(text)
    # the result is the values, or (values, their places) on the chip
    sort = re.compile(r"= [^=]*\[%d,%d\][^=]* sort\(" % (rows, vocab))

    def count(names):
        return sum(bool(sort.search(ln)) for c in names for ln in comps[c])
    body = _reach(comps, "ENTRY", False)
    return count(body), count(_reach(comps, "ENTRY") - body)


def _share_sums(text, rows, picks, width):
    """Hold a compiled step to where a share's results go (`parallel/moe.
    dropless_experts`, PERF.md Findings PR 47): every conditional whose
    branches launch grouped products returns the (rows, width) float32
    SUM and nothing else, and no (rows x picks, width) float32 value,
    the spread over every assignment, is defined anywhere but under the
    branch that launches the products over every assignment (the one
    whose row tiles read 256). -> the number of such conditionals."""
    import re
    comps = _computations(text)
    tiling = re.compile(r'ragged_dot_tiling="(\d+),')
    spread = re.compile(r"= f32\[%d,%d\]" % (rows * picks, width))
    every_row, layers = set(), 0
    for lines in comps.values():
        for line in lines:
            if " conditional(" not in line:
                continue
            under = [_reach(comps, b) for b in _branches(line)]
            tiles = [sorted({int(t) for c in names for ln in comps[c]
                             for t in tiling.findall(ln)}) for names in under]
            if not any(tiles):
                continue
            layers += 1
            assert len(tiles) == 2 and [256] in tiles, tiles
            assert re.search(r"= \(f32\[%d,%d\]\S*\) conditional\("
                             % (rows, width), line), line[:300]
            every_row |= under[tiles.index([256])]
    made = [ln.strip()[:200] for c in set(comps) - every_row
            for ln in comps[c] if spread.search(ln)]
    assert not made, made
    return layers


def _row_tiles(text):
    """The row tile of every grouped product (`lax.ragged_dot`'s custom
    call) in a compiled step's text, sorted: the TPU compiler writes
    `ragged_dot_tiling="tm,tk,tn"` on each, `tm` the largest power of two
    up to 512 that divides the row buffer's length. `parallel/moe.
    tiled_rows` sizes the buffers by that rule; a libtpu that changes it
    shows here and not as a silent loss (PERF.md, Findings PR 44)."""
    import re
    tilings = re.findall(r'ragged_dot_tiling="(\d+),(\d+),(\d+)"', text)
    assert tilings and all(t[1:] == ("512", "512") for t in tilings), tilings
    return sorted(int(t[0]) for t in tilings)


@pytest.mark.parametrize("cache", [jnp.bfloat16, jnp.int8])
def test_ragged_compiles_for_v5e_at_the_serving_shape(one_chip, cache):
    """The serving cell's shape (32 rows, 32 / 8 heads of 128, pages of
    16, 256 pages a sequence, 3,072 pages) through the TPU compiler:
    what cross-lowering cannot see — a slice off the tiling, a manual
    DMA Mosaic refuses, too much VMEM — raises here, at no chip time.
    Both the derived tile and the one TUNED.kernels.json holds."""
    from paddle_tpu.kernels import ragged_paged_attention
    t, qh, kvh, pages, n_pages, slots = 32, 32, 8, 3072, 256, 32

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = arg((kvh, pages, PAGE, D), cache)
    sc = arg((kvh, pages, PAGE, 1), jnp.float32) if cache == jnp.int8 \
        else None
    for block_pages in (None, 16):
        def fn(q, kp, vp, table, slot, pos, *scales):
            kw = dict(zip(("k_scale", "v_scale"), scales))
            return ragged_paged_attention(
                q, kp, vp, table, slot, pos, use_pallas=True,
                interpret=False, block_pages=block_pages, **kw)
        args = [arg((t, qh, D), jnp.bfloat16), pool, pool,
                arg((slots, n_pages), jnp.int32), arg((t,), jnp.int32),
                arg((t,), jnp.int32)] + ([sc, sc] if sc is not None else [])
        text = jax.jit(fn).lower(*args).compile().as_text()
        assert "tpu_custom_call" in text or "custom-call" in text


def test_flashmask_compiles_for_v5e_at_the_training_shape(v5e, one_chip):
    """`pretrain_4k`'s per-chip shape (4 rows x 16 heads, 4,096 tokens,
    head 128, bfloat16, a document mask), forward and backward, through
    the TPU compiler at the blocks the entry derives: 512 x 512, and the
    three kernels fit the VMEM they ask for (Mosaic refuses one that
    does not). Each kernel's grid is a step a line, (64, 8): the inner
    blocks are a loop's trips inside the step, as many as the line's
    range holds, and no kernel iterates over (64, 8, 8). The same inside
    the step's `shard_map` over a 2 x 2 mesh (a Mosaic kernel is not
    partitioned by the compiler)."""
    import re
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from paddle_tpu.ops import flashmask_attention as fm
    b, h, s = 4, 16, 4096
    assert fm.derived_blocks(s, s, D, jnp.bfloat16) == (512, 512)
    limit = fm._vmem_limit(512, 512, D, jnp.bfloat16, 1)
    assert fm._vmem_bytes(512, 512, D, 2) <= limit == 16 * 2 ** 20

    def attend(q, k, v, sri):
        return fm.flashmask_attention_bhsd(q, k, v, sri, causal=True,
                                           use_pallas=True, interpret=False)

    def loss(q, k, v, sri):
        return attend(q, k, v, sri).astype(jnp.float32).sum()

    qkv = jax.ShapeDtypeStruct((b, h, s, D), jnp.bfloat16, sharding=one_chip)
    sri = jax.ShapeDtypeStruct((b, h, s, 1), jnp.int32, sharding=one_chip)
    text = jax.jit(jax.grad(loss, (0, 1, 2))).lower(
        qkv, qkv, qkv, sri).compile().as_text()
    assert text.count("tpu_custom_call") == 3          # fwd, dq, dkv
    asked = [re.search(r'scoped_memory_configs":\[\{"memory_space":"1",'
                       r'"offset":"\d+","size":"(\d+)"', line).group(1)
             for line in text.splitlines() if "tpu_custom_call" in line]
    assert asked == [str(limit)] * 3
    assert _kernel_grids(text) == [(b * h, s // 512)] * 3

    mesh = Mesh(np.asarray(v5e.devices).reshape(2, 2), ("dp", "tp"))
    spec = P("dp", "tp", None, None)

    def sharded_loss(q, k, v, sri):
        o = jax.shard_map(attend, mesh=mesh, in_specs=(spec,) * 4,
                          out_specs=spec, check_vma=False)(q, k, v, sri)
        return o.astype(jnp.float32).sum()

    whole = lambda n, dtype: jax.ShapeDtypeStruct(
        (2 * b, 2 * h, s, n), dtype, sharding=NamedSharding(mesh, spec))
    text = jax.jit(jax.grad(sharded_loss, (0, 1, 2))).lower(
        *[whole(D, jnp.bfloat16)] * 3, whole(1, jnp.int32)).compile().as_text()
    assert _kernel_grids(text) == [(b * h, s // 512)] * 3


@pytest.mark.parametrize("cache", [jnp.bfloat16, jnp.int8])
def test_unified_step_compiles_for_v5e_with_its_pools_in_place(one_chip,
                                                               cache):
    """`unified_step` at `mistral-7b-v0.3.serve1`'s widths and pool (8 KV
    heads, 3,072 pages of 16 x 128; 2 of its 16 layers are enough) through
    the TPU compiler: the pools are donated and aliased to the outputs,
    the program's temporaries stay under one layer's pool, and no
    operation copies, transposes, slices out or writes back a layer's
    pool or the stack ([donate-pools]: five of them were 36.6 ms of a
    49.5 ms step). And a layer's `wq`, `wk` and `wv` are read by their
    products from HBM as they lie ([weight-slices]): no operation puts
    such a slice in the compiler's fast memory (`S(1)`) and none copies
    one (the transposition in fast memory that was 0.85 ms of a 12.5 ms
    step)."""
    import re
    from paddle_tpu.models import llama_serving as ls
    from paddle_tpu.models.llama import LlamaConfig
    L, H, F, V, kvh, pages, slots, t = 2, 4096, 14336, 32768, 8, 3072, 32, 32
    cfg = LlamaConfig(vocab_size=V, hidden_size=H, intermediate_size=F,
                      num_hidden_layers=L, num_attention_heads=32,
                      num_key_value_heads=kvh, rope_theta=1e6)

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = {"embed": arg((V, H)), "final_norm": arg((H,)),
              "lm_head": arg((H, V)),
              "layers": {"ln1": arg((L, H)), "ln2": arg((L, H)),
                         "wq": arg((L, H, H)), "wk": arg((L, H, kvh * D)),
                         "wv": arg((L, H, kvh * D)), "wo": arg((L, H, H)),
                         "w_gate": arg((L, H, F)), "w_up": arg((L, H, F)),
                         "w_down": arg((L, F, H))}}
    pool = arg((L, kvh, pages, PAGE, D), cache)
    sc = arg((L, kvh, pages, PAGE, 1), jnp.float32) if cache == jnp.int8 \
        else None
    sample = _sample_shapes(arg, slots)
    ring = arg((slots, 4097), jnp.int32)
    compiled = ls.unified_step.__wrapped__.lower(
        params, pool, pool, arg((slots, 256), jnp.int32),
        arg((t,), jnp.int32), arg((t,), jnp.int32), arg((t,), jnp.int32),
        cfg, PAGE, use_pallas=True, interpret=False, k_scale=sc, v_scale=sc,
        sample=sample, need_rows=arg((slots,), jnp.int32), block_pages=16,
        tok_buf=ring, buf_write=arg((slots,), jnp.bool_)).compile()
    mem = compiled.memory_analysis()
    donated = sum(math.prod(a.shape) * a.dtype.itemsize
                  for a in (pool, pool, ring) + ((sc, sc) if sc else ()))
    assert mem.alias_size_in_bytes >= donated     # the ring's rows pad
    assert mem.temp_size_in_bytes < 100e6
    text = compiled.as_text()
    assert "ragged_paged_attention" in text
    # nothing yields a layer's pool or the stack but the parameters ...
    whole = re.compile(r"= \w+\[(?:\d+,)?%d,%d,%d,%d\]\S* (?!parameter|"
                       r"get-tuple-element|bitcast|tuple)([\w-]+)\("
                       % (kvh, pages, PAGE, D))
    assert not whole.findall(text)
    # ... and the scatter, over the stack seen flat, updates its operand
    flat = [ln for ln in text.splitlines()
            if re.search(r"= \w+\[%d,%d\]\S* fusion\("
                         % (L * kvh * pages * PAGE, D), ln)]
    assert len(flat) == 2 and all(
        "scatter" in ln and '"aliasing_operands"' in ln for ln in flat)
    # a layer's slice of the q, k and v weights (or of one stack laid
    # over all three) never lies in fast memory and is never copied
    sliced = re.findall(r"%%([\w.-]+) = bf16\[1,%d,(?:%d|%d|%d)\]\{([^}]*)\} "
                        r"([\w-]+)\(" % (H, H, kvh * D, H + 2 * kvh * D), text)
    assert sliced, "the scan slices no layer's weights: the pattern is stale"
    assert not [(name, op) for name, layout, op in sliced
                if op == "copy" or "S(1)" in layout]
    # the sampler's sort over the vocabulary runs where a wave's rows
    # ask for it: under a conditional, never in the step's own body
    assert _vocab_sorts(text, slots, V) == (0, 1)


def test_flashmask_compiles_for_v5e_at_latent_attentions_widths(one_chip):
    """`sparse_pretrain_8k`'s shape (4 rows x 16 heads, 8,192 tokens, keys
    128 + 64 = 192 wide and values 128, bfloat16, a document mask),
    forward and backward, through the TPU compiler: the three kernels
    carry each operand at its own width (dQ and dK 192, dV 128, no padded
    product), at the 512 x 512 blocks the entry derives, inside the VMEM
    they ask for."""
    from paddle_tpu.ops import flashmask_attention as fm
    b, h, s, d, dv = 4, 16, 8192, 192, 128
    assert fm.derived_blocks(s, s, d, jnp.bfloat16, d_v=dv) == (512, 512)
    assert fm._vmem_bytes(512, 512, d, 2, d_v=dv) <= 16 * 2 ** 20

    def loss(q, k, v, sri):
        return fm.flashmask_attention_bhsd(
            q, k, v, sri, causal=True, use_pallas=True,
            interpret=False).astype(jnp.float32).sum()

    arg = lambda *shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)
    compiled = jax.jit(jax.grad(loss, (0, 1, 2))).lower(
        arg(b, h, s, d), arg(b, h, s, d), arg(b, h, s, dv),
        arg(b, h, s, 1, dtype=jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 3                  # fwd, dq, dkv
    # dQ and dK at the keys' width, dV at the values': no kernel returns
    # a padded result (the operands a kernel streams lie in whole lane
    # tiles, 256 for the keys' 192, and are read 192 wide)
    assert f"bf16[{b * h},{s},{d}]" in text and f"bf16[{b * h},{s},{dv}]" in text
    assert not [ln for ln in text.splitlines() if "tpu_custom_call" in ln
                and f"bf16[{b * h},{s},256]" in ln.split(" custom-call(")[0]]
    assert _kernel_grids(text) == [(b * h, s // 512)] * 3

    # as the layer runs them: under `jax.checkpoint` in a `lax.scan`, so
    # the forward twice, and every kernel still a step a line
    def layers(q, k, v, sri):
        def layer(x, _):
            o = fm.flashmask_attention_bhsd(x, k, v, sri, causal=True,
                                            use_pallas=True, interpret=False)
            return x + jnp.pad(o, ((0, 0),) * 3 + ((0, d - dv),)), None
        x, _ = jax.lax.scan(jax.checkpoint(layer), q, None, length=2)
        return x.astype(jnp.float32).sum()
    text = jax.jit(jax.grad(layers, (0, 1, 2))).lower(
        arg(b, h, s, d), arg(b, h, s, d), arg(b, h, s, dv),
        arg(b, h, s, 1, dtype=jnp.int32)).compile().as_text()
    assert _kernel_grids(text) == [(b * h, s // 512)] * 4   # fwd, fwd, dq, dkv


@pytest.mark.parametrize("heads, window", [(48, None), (64, 512)],
                         ids=["full_group6", "window_group8"])
@pytest.mark.parametrize("cache", [jnp.bfloat16, jnp.int8])
def test_ragged_compiles_for_v5e_at_lagunas_shapes(one_chip, cache, heads,
                                                   window):
    """`reason_saturated`'s two attention shapes (128 rows over 8 KV heads
    of 128, pages of 16, 512 pages a sequence): a query group of 6 over
    the full context in a pool of 18,432 pages, and a group of 8 under
    the static window of 512 in a pool of 4,096, at the tile
    TUNED.kernels.json holds."""
    from paddle_tpu.kernels import ragged_paged_attention
    t, kvh, n_pages, slots = 128, 8, 512, 96
    pages = 4096 if window else 18432

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = arg((kvh, pages, PAGE, D), cache)
    sc = arg((kvh, pages, PAGE, 1), jnp.float32) if cache == jnp.int8 \
        else None

    def fn(q, kp, vp, table, slot, pos, *scales):
        kw = dict(zip(("k_scale", "v_scale"), scales))
        return ragged_paged_attention(
            q, kp, vp, table, slot, pos, use_pallas=True, interpret=False,
            block_pages=16, window=window, **kw)
    args = [arg((t, heads, D), jnp.bfloat16), pool, pool,
            arg((slots, n_pages), jnp.int32), arg((t,), jnp.int32),
            arg((t,), jnp.int32)] + ([sc, sc] if sc is not None else [])
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text or "custom-call" in text


def test_window_none_traces_the_program_it_always_did():
    """`window=None` is no argument at all: the kernel's jaxpr, equation
    for equation, is what it is without the argument."""
    from paddle_tpu.kernels.ragged_paged_attention import (
        ragged_paged_attention)
    kvh, t = 2, 16
    kp, _ = _pool(jnp.bfloat16, kvh)
    table = jnp.zeros((2, 4), jnp.int32)
    slot = jnp.zeros((t,), jnp.int32)
    pos = jnp.arange(t, dtype=jnp.int32) - 2
    q = jnp.ones((t, kvh * GROUP, D), jnp.bfloat16)

    def text(**kw):
        return str(jax.make_jaxpr(lambda q, kp: ragged_paged_attention(
            q, kp, kp, table, slot, pos, use_pallas=True, interpret=False,
            block_pages=2, **kw))(q, kp))
    assert text() == text(window=None)
    assert text(window=8) != text()


@pytest.mark.parametrize("keys", ["bfloat16", "float8_e4m3fn"])
def test_latent_kernels_compile_for_v5e_at_glm5s_shapes(one_chip, keys):
    """`longctx_reason_saturated`'s three latent parts at GLM-5's widths
    (512 rows; 32 index heads of 128 and 64 heads over a latent row of 576
    in 640 lanes; pages of 128, 352 a sequence, a pool of 5,632; the top
    2,048) through Mosaic's own compile step, the index keys in the
    cache's type and in float8 (`GlmDsaConfig.index_key_dtype`)."""
    from paddle_tpu.kernels import ragged_latent as rl
    t, pages, n_pages, slots, nb = 512, 5632, 352, 48, 88

    def arg(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    desc = [arg((slots, n_pages)), arg((t,)), arg((t,))]
    scores = arg((nb, t, 512), jnp.float32)
    for fn, args in [
        (lambda q, w, keys, *d: rl.ragged_index_scores(
            q, w, keys, *d, use_pallas=True),
         [arg((t, 32, D), jnp.bfloat16), arg((t, 32), jnp.float32),
          arg((1, pages, 128, D), jnp.dtype(keys))] + desc),
        (lambda sc, pos: rl.dsa_select(sc, pos, 2048, use_pallas=True),
         [scores, arg((t,))]),
        (lambda q, lat, sc, thr, at, *d: rl.ragged_sparse_latent_attention(
            q, lat, sc, thr, at, *d, rank=512, sm_scale=1 / 16,
            use_pallas=True),
         [arg((t, 64, 640), jnp.bfloat16),
          arg((1, pages, 128, 640), jnp.bfloat16), scores, arg((t,)),
          arg((t,))] + desc)]:
        text = jax.jit(fn).lower(*args).compile().as_text()
        assert "tpu_custom_call" in text or "custom-call" in text


def test_glm_step_compiles_for_v5e_with_its_pools_in_place(one_chip):
    """`glm_step` at `glm-5.serve1`'s widths and pool (one dense and one
    expert layer of its five are enough) through the TPU compiler: every
    pool of both planes is donated and aliased to an output, the
    temporaries stay far under one layer's pool, and the scatters update
    the pools seen flat where they lie."""
    import re
    from paddle_tpu.models import glm_dsa as g
    t, pages, page, slots, max_len = 512, 5632, 128, 48, 45056
    c = g.GlmDsaConfig(vocab_size=19360, num_hidden_layers=2,
                       first_k_dense_replace=1, experts_held=16)

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    params = jax.tree_util.tree_map_with_path(
        lambda p, s: arg(s, jnp.float32 if p[-1].key == "router_bias"
                         else jnp.bfloat16),
        g.param_shapes(c), is_leaf=lambda x: isinstance(x, tuple))
    stack = (arg((1, 1, pages, page, 640)), arg((1, 1, pages, page, 128)),
             None, None)
    sample = _sample_shapes(arg, slots)
    compiled = g.glm_step.__wrapped__.lower(
        params, ((stack, stack),), (arg((slots, max_len // page), jnp.int32),),
        arg((t,), jnp.int32), arg((t,), jnp.int32), arg((t,), jnp.int32), c,
        page, use_pallas=True, interpret=False, sample=sample,
        need_rows=arg((slots,), jnp.int32),
        tok_buf=arg((slots, max_len + 1), jnp.int32),
        buf_write=arg((slots,), jnp.bool_)).compile()
    mem = compiled.memory_analysis()
    pools = 2 * pages * page * (640 + 128) * 2
    assert mem.alias_size_in_bytes == pools
    assert mem.temp_size_in_bytes < 0.6e9
    text = compiled.as_text()
    for kernel in ("ragged_index_scores", "dsa_select",
                   "ragged_sparse_latent_attention"):
        assert kernel in text
    flat = [ln for ln in text.splitlines()
            if re.search(r"= \w+\[%d,(640|128)\]\S* fusion\(" % (pages * page),
                         ln)]
    assert len(flat) == 4 and all("scatter" in ln for ln in flat)
    assert _vocab_sorts(text, slots, c.vocab_size) == (0, 1)
    # the held experts' three products over 1,024 sorted rows of 16
    # groups, launched over 1,088, and over all 4,096 (4,352) in the
    # branch a step takes when they do not hold its assignments
    assert "ragged-dot" in text
    assert _row_tiles(text) == [64] * 3 + [256] * 3
    # and the layer's conditional returns each row's sum: no spread of
    # the products over 512 x 8 assignments but in the 256-tile branch
    assert _share_sums(text, t, c.num_experts_per_tok, c.hidden_size) == 1


def test_laguna_step_compiles_for_v5e_with_its_sort_under_a_conditional(
        one_chip):
    """`laguna_step` at `laguna-xs.2.serve1`'s widths (its leading dense
    layer and one sliding layer of experts; 128 rows, 96 slots, a
    vocabulary of 100,352) through the TPU compiler: the sampler's sort
    of (96, 100,352), a third of `reason_saturated`'s step while it ran
    every step, lies under a conditional and nowhere in the step's own
    body."""
    from paddle_tpu.models import laguna as lg
    t, slots, page, max_len = 128, 96, 16, 8192
    c = lg.LagunaConfig(num_hidden_layers=2)

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(
        arg, lg.param_shapes(c), is_leaf=lambda x: isinstance(x, tuple))
    groups = c.serving_model().groups
    pages = {"full": 18432, "window": 4096}
    caches = tuple(
        tuple((arg((1, g.kv_heads, pages[g.name], page, D)),) * 2
              + (None, None) for _ in g.stacks)
        for g in groups)
    tables = tuple(arg((slots, max_len // page), jnp.int32) for _ in groups)
    sample = _sample_shapes(arg, slots)
    text = lg.laguna_step.__wrapped__.lower(
        params, caches, tables, arg((t,), jnp.int32), arg((t,), jnp.int32),
        arg((t,), jnp.int32), c, page, use_pallas=True, interpret=False,
        sample=sample, need_rows=arg((slots,), jnp.int32), block_pages=16,
        tok_buf=arg((slots, max_len + 1), jnp.int32),
        buf_write=arg((slots,), jnp.bool_)).compile().as_text()
    assert "ragged_paged_attention" in text
    assert _vocab_sorts(text, slots, c.vocab_size) == (0, 1)
    # the sparse layer's three products over 128 x 8 sorted rows of 256
    # experts, launched over 1,056: no tile of 512 rows for decode's
    # three to five rows an expert
    assert "ragged-dot" in text
    assert _row_tiles(text) == [32] * 3


@pytest.mark.parametrize("stored", ["bfloat16", "float8_e4m3fn"])
def test_dense_latent_kernel_compiles_for_v5e_at_longcats_shapes(one_chip,
                                                                 stored):
    """`agent_rollout_saturated`'s attention (256 rows, 64 heads over a
    latent row of 576 in 640 lanes; pages of 128, 96 a sequence, a pool of
    3,072) through Mosaic's own compile step, the latent rows in the
    cache's type and in float8 (`LongcatFlashConfig.latent_dtype`)."""
    from paddle_tpu.kernels import ragged_latent as rl
    t, pages, n_pages, slots = 256, 3072, 96, 96

    def arg(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    text = jax.jit(lambda q, lat, *d: rl.ragged_latent_attention(
        q, lat, *d, rank=512, sm_scale=192 ** -0.5, use_pallas=True)).lower(
        arg((t, 64, 640), jnp.bfloat16),
        arg((1, pages, 128, 640), jnp.dtype(stored)),
        arg((slots, n_pages)), arg((t,)), arg((t,))).compile().as_text()
    assert "ragged_latent_attention" in text


def test_longcat_step_compiles_for_v5e_with_its_pools_in_place(one_chip):
    """`longcat_step` at `longcat-flash-chat.serve1`'s widths and pool (one
    double layer of its four is enough) through the TPU compiler: both
    sublayers' pools are donated and aliased to an output, the
    temporaries stay far under one pool, the dense kernel runs twice and
    the experts' grouped products are there."""
    from paddle_tpu.models import longcat_flash as lc
    t, pages, page, slots, max_len = 256, 3072, 128, 96, 12288
    c = lc.LongcatFlashConfig(vocab_size=16384, num_layers=1, experts_held=16)

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    params = jax.tree_util.tree_map_with_path(
        lambda p, s: arg(s, jnp.float32 if p[-1].key == "router_bias"
                         else jnp.bfloat16),
        lc.param_shapes(c), is_leaf=lambda x: isinstance(x, tuple))
    stack = (arg((1, 1, pages, page, 640)), None)
    compiled = lc.longcat_step.__wrapped__.lower(
        params, ((stack, stack),), (arg((slots, max_len // page), jnp.int32),),
        arg((t,), jnp.int32), arg((t,), jnp.int32), arg((t,), jnp.int32), c,
        page, use_pallas=True, interpret=False,
        sample=_sample_shapes(arg, slots), need_rows=arg((slots,), jnp.int32),
        tok_buf=arg((slots, max_len + 1), jnp.int32),
        buf_write=arg((slots,), jnp.bool_)).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == 2 * pages * page * 640 * 2
    assert mem.temp_size_in_bytes < 0.5e9
    text = compiled.as_text()
    assert "ragged_latent_attention" in text and "ragged-dot" in text
    assert "ragged_sparse_latent_attention" not in text
    # 384 sorted rows of 16 groups launched over 416; all 3,072 (3,328)
    # in the branch that holds a step's every assignment
    assert _row_tiles(text) == [32] * 3 + [256] * 3
    # and the layer's conditional returns each row's sum: no spread of
    # the products over 256 x 12 assignments but in the 256-tile branch
    assert _share_sums(text, t, c.moe_topk, c.hidden_size) == 1
    assert _vocab_sorts(text, slots, c.vocab_size) == (0, 1)


@pytest.mark.parametrize("kept", ["float32", "bfloat16"])
def test_ssm_kernels_compile_for_v5e_at_nemotrons_shapes(one_chip, kept):
    """`kernels/ragged_ssm.py` at `nemotron-3-nano-30b-a3b.serve1`'s widths
    (256 rows, 128 slots, 64 heads of 64 over a state of 128 in 8 groups,
    6,144 convolution channels, 4 layers' state in one array) through the
    TPU compiler, the state float32 and in the control's bfloat16: each
    state is donated and aliased to an output whole, and nothing of its
    size is made beside it."""
    from paddle_tpu.kernels import ragged_ssm
    t, slots, layers, heads, p, g, n, k = 256, 128, 4, 64, 64, 8, 128, 4
    conv_dim = heads * p + 2 * g * n

    def arg(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    def scan(x, dt, a, b, c, state, slot, pos):
        return ragged_ssm.ragged_scan(x, dt, a, b, c, state, 2, slot, pos,
                                      use_pallas=True)

    def conv(u, state, w, bias, slot, pos):
        return ragged_ssm.ragged_conv(u, state, 1, w, bias, slot, pos,
                                      use_pallas=True)

    rows = [arg((t,), jnp.int32)] * 2
    ssm = arg((layers, slots, n, heads * p), jnp.dtype(kept))
    carried = arg((layers, slots, k - 1, conv_dim // 128, 128), jnp.bfloat16)
    for fn, args, at, name in (
            (scan, [arg((t, heads, p)), arg((t, heads)), arg((heads,)),
                    arg((t, g, n)), arg((t, g, n)), ssm] + rows, 5,
             "ragged_ssm_scan"),
            (conv, [arg((t, conv_dim)), carried, arg((k, conv_dim)),
                    arg((conv_dim,))] + rows, 1, "ragged_ssm_conv")):
        compiled = jax.jit(fn, donate_argnums=(at,)).lower(*args).compile()
        mem = compiled.memory_analysis()
        state = args[at]
        assert mem.alias_size_in_bytes == math.prod(state.shape) \
            * state.dtype.itemsize
        assert mem.temp_size_in_bytes < 32e6
        assert name in compiled.as_text()


@pytest.mark.parametrize("k, n, transposed", [(2688, 1856, True),
                                              (1856, 2688, False)])
def test_the_whole_matrix_grouped_product_compiles_for_v5e(one_chip, k, n,
                                                           transposed):
    """`kernels/grouped_matmul` at Nemotron-3-Nano's expert widths (1,568
    sorted rows at tile 32 over 128 matrices of 10 MB, two of them in fast
    memory at once) through the TPU compiler, the up matrix rows its
    outputs and no copy of it made; the compiler's own kernel would take
    these in blocks of 128 a side."""
    import re
    from paddle_tpu.kernels.grouped_matmul import grouped_matmul

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = arg((1568, k)), arg((128, k, n)), arg((128,), jnp.int32)
    mine = (args[0], arg((128, n, k) if transposed else (128, k, n)), args[2])
    text = jax.jit(lambda x, w, gs: grouped_matmul(
        x, w, gs, 32, transposed)).lower(*mine).compile().as_text()
    assert "ragged_dot_experts" in text
    assert not re.search(r"bf16\[128,\d+,\d+\]\S* copy\(", text)
    theirs = jax.jit(lambda x, w, gs: jax.lax.ragged_dot(
        x, w, gs, preferred_element_type=jnp.float32)).lower(
        *args).compile().as_text()
    assert re.findall(r'ragged_dot_tiling="([\d,]+)"', theirs) == \
        ["32,128,128"]


def test_nemotron_step_compiles_for_v5e_with_pools_and_state_in_place(
        one_chip):
    """`nemotron_step` at `nemotron-3-nano-30b-a3b.serve1`'s widths, pool
    and slots (the pattern's first six letters, `MEMEM*`, hold every kind
    of layer) through the TPU compiler: the attention layer's pages and
    both slot states are donated and aliased to an output, no weight and
    no state is copied, the scan and the convolution run once a Mamba
    layer under their own names, the attention kernel takes a query group
    of 16, and the experts' two grouped products, over widths that 512
    does not divide, are the whole-matrix kernel and not the compiler's
    walk over blocks of 128 a side."""
    import re
    from paddle_tpu.models import nemotron_h as nh
    t, pages, page, slots, max_len = 256, 4096, 128, 128, 12288
    c = nh.NemotronHConfig(num_hidden_layers=6)

    def arg(shape, dtype=jnp.bfloat16):
        # no layout stated: the compiler lays a parameter out as the device
        # does. An up matrix kept (experts, 2,688, 1,856) came out with
        # 2,688 last there and was copied row-major for the kernel every
        # step, 1.28 GB a layer (PERF.md, Findings PR 48); kept (experts,
        # 1,856, 2,688), rows its outputs, it lies as the kernel reads it
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    params = jax.tree_util.tree_map_with_path(
        lambda p, s: arg(s, jnp.float32 if p[-1].key in nh.FLOAT32
                         else jnp.bfloat16),
        nh.param_shapes(c), is_leaf=lambda x: isinstance(x, tuple))
    pool = arg((1, 2, pages, page, 128))
    ssm = arg((3, slots, 128, 4096), jnp.float32)
    conv = arg((3, slots, 3, 48, 128))
    compiled = nh.nemotron_step.__wrapped__.lower(
        params, (((pool, pool, None, None),), ssm, conv),
        (arg((slots, max_len // page), jnp.int32),),
        arg((t,), jnp.int32), arg((t,), jnp.int32), arg((t,), jnp.int32), c,
        page, use_pallas=True, interpret=False,
        sample=_sample_shapes(arg, slots), need_rows=arg((slots,), jnp.int32),
        tok_buf=arg((slots, max_len + 1), jnp.int32),
        buf_write=arg((slots,), jnp.bool_)).compile()
    mem = compiled.memory_analysis()
    kept = 2 * 2 * pages * page * 128 * 2 + 3 * slots * (
        128 * 4096 * 4 + 3 * 6144 * 2)
    assert mem.alias_size_in_bytes == kept
    assert mem.temp_size_in_bytes < 0.6e9
    text = compiled.as_text()
    assert "copy(%params" not in text
    assert not re.search(r"\[3,128,128,4096\]\S* copy\(", text)
    for name, calls in (("ragged_ssm_scan", 3), ("ragged_ssm_conv", 3),
                        ("ragged_paged_attention", 1),
                        ("ragged_dot_experts", 4)):
        assert len(re.findall(r'custom_call_target="tpu_custom_call".*'
                              + name, text)) >= calls, name
    assert "ragged_dot_tiling" not in text
    assert _vocab_sorts(text, slots, c.vocab_size) == (0, 1)
