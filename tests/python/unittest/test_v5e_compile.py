"""Compiles for a described TPU v5e, without the chip: what the chip's own
compiler makes of the engine's donating executables at the real widths.
Nothing runs, so nothing here is a time.  All such tests live in this one
file: only one process may hold the TPU's compiler (see the
on-chip-measurement guide), so the topology is described inside a fixture
and never while a module is imported."""
import numpy as onp
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu.serving import GenerationEngine

pytestmark = pytest.mark.gen


@pytest.fixture(autouse=True)
def _kernels_not_their_interpreters(monkeypatch):
    """`tests/benchmark` rehearses `benchmark/run.py --rehearse` in the
    test process, which sets MXNET_PALLAS_INTERPRET in `os.environ` for good
    (`setdefault`): a worker that ran such a test first would lower every
    kernel here as its interpreter, with cache-sized temporaries and no
    custom call.  A compile for the chip takes the kernels themselves."""
    from incubator_mxnet_tpu import config
    monkeypatch.delenv("MXNET_PALLAS_INTERPRET", raising=False)
    monkeypatch.setattr(config, "_OVERRIDES", {
        k: v for k, v in config._OVERRIDES.items()
        if k != "MXNET_PALLAS_INTERPRET"})


@pytest.fixture(scope="module")
def one_chip():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    return SingleDeviceSharding(topo.devices[0])


def _compile_for(eng, chip, slots, max_len, bucket, join=False,
                 prefill=False):
    """Compiles the engine's decode step (and its join, and the prefill)
    for the described chip over `slots` rows of what a prefill of one
    `bucket`-long prompt returns, JAX's persistent cache off.  Returns the
    abstract cache and the compiled executables (False for those not asked
    for); the engine is closed."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache as cc

    sds = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip)
    ints = lambda *shape: sds(jnp.zeros(shape, jnp.int32))
    params = {n: sds(v) for n, v in eng._params.items()}
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        pre = eng._prefill._jit.lower(params, ints(1, bucket), ints(1))
        row = jax.tree_util.tree_map(sds, pre.out_info)
        cache = {"m": {k: jax.ShapeDtypeStruct((slots,) + v.shape[1:],
                                               v.dtype, sharding=chip)
                       for k, v in row["m"].items()},
                 "tok": ints(slots), "pos": ints(slots),
                 "left": ints(slots), "out": ints(slots, max_len)}
        step = eng._decode._jit.lower(params, cache).compile()
        if join:
            join = eng._join._jit.lower(cache, row, ints(2)).compile()
        if prefill:
            prefill = pre.compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()
        eng.close()
    return cache, step, join, prefill


def _kernel_calls(text, name):
    """How many Mosaic custom calls of an optimized program bear `name`: in
    the instruction's own name, not among its operands (the combine reads
    the grouped kernel's result)."""
    return len([l for l in text.splitlines() if " custom-call(" in l
                and name in l.split(" custom-call(")[0]
                and "tpu_custom_call" in l])


def test_sparse_decoder_step_writes_its_cache_in_place(one_chip):
    """The decode step and the join of a SparseDecoder at the published
    widths (2 layers, 4 slots of 4096 rows): the donated cache comes back
    aliased, and the step's temporaries stay far below one cache leaf, so
    there is no cache-sized copy, gather or slice in the compiled program."""
    from incubator_mxnet_tpu.models.sparse_decoder import SparseDecoder

    S, L, layers = 4, 4096, 2
    net = SparseDecoder(1024, 2048, layers, 32, 4, 128, 768, 128, 8, 16, 64,
                        2048, first_held=0, experts_held=2)
    net.collect_params().setattr("grad_req", "null")
    net.initialize(ctx=mx.cpu(0))
    net.cast("bfloat16")
    eng = GenerationEngine(net, bos=1, eos=2, ctx=mx.cpu(0), slots=S,
                           max_len=L, prompt_buckets=(2048,), queue_cap=4)
    _, step, join, _ = _compile_for(eng, one_chip, S, L, 2048, join=True)
    step, join = step.memory_analysis(), join.memory_analysis()
    leaf = S * layers * 4 * L * 128 * 2              # k, or v: 33.5 MB
    total = 2 * leaf + S * layers * L * 64 * 2
    assert step.alias_size_in_bytes >= total
    assert step.temp_size_in_bytes < leaf // 4, step.temp_size_in_bytes
    assert join.alias_size_in_bytes >= total
    assert join.temp_size_in_bytes < leaf // 4, join.temp_size_in_bytes


def test_hybrid_decoder_step_and_prefill_fit_the_chip(one_chip):
    """`HybridDecoder` at the published widths of the benchmark's
    configuration and at its serving size (8 layers, 256 slots of 2048 rows,
    a 1024-token prefill), two of the 64 experts held so that the host's
    copy of the weights stays small: the donated cache comes back aliased,
    recurrent state and all; the state leaf passes through the three
    `gated_delta_step` kernel calls of the period's body and no temporary is
    a tenth of it; and with the experts and vocabulary rows left out here
    added back, the step and the prefill stay under 16 GB."""
    from incubator_mxnet_tpu.models.hybrid_decoder import HybridDecoder

    S, L, V, held = 256, 2048, 1024, 2
    net = HybridDecoder(V, 2048, 8, 4, 16, 2, 256, 64, 16, 32, 128, 128, 4,
                        512, 512, 10, shared_hidden=512, first_held=0,
                        experts_held=held)
    net.collect_params().setattr("grad_req", "null")
    net.initialize(ctx=mx.cpu(0))
    net.cast("bfloat16")
    eng = GenerationEngine(net, bos=1, eos=2, ctx=mx.cpu(0), slots=S,
                           max_len=L, prompt_buckets=(1024,), queue_cap=4)
    cache, step, _, prefill = _compile_for(eng, one_chip, S, L, 1024,
                                           prefill=True)
    m = cache["m"]
    assert m["s"].shape == (S, 6, 32, 128, 128) and m["s"].dtype == "float32"
    assert m["c"].shape == (S, 6, 3, 8192)
    assert m["k"].shape == (S, 2, 2, L, 256)
    state = S * 6 * 32 * 128 * 128 * 4                   # 3.22 GB
    total = state + S * 6 * 3 * 8192 * 2 + 2 * S * 2 * 2 * L * 256 * 2
    mem = step.memory_analysis()
    assert mem.alias_size_in_bytes >= total
    assert mem.temp_size_in_bytes < state // 10, mem.temp_size_in_bytes
    assert step.as_text().count('custom_call_target="tpu_custom_call"') == 3
    # what this test left off the chip: 62 experts a layer, 17 968 rows of
    # the embedding and of the head
    absent = 8 * (64 - held) * 3 * 512 * 2048 * 2 + 2 * (18992 - V) * 2048 * 2
    size = lambda a: a.argument_size_in_bytes + a.output_size_in_bytes \
        - a.alias_size_in_bytes + a.temp_size_in_bytes
    assert size(mem) + absent < 16e9, size(mem) + absent
    # a prefill runs beside the resident cache
    pre = prefill.memory_analysis()
    assert size(pre) + total + absent < 16e9, size(pre) + total + absent


def test_latent_decoder_step_and_prefill_fit_the_chip(one_chip):
    """`LatentDecoder` at the published widths of the benchmark's
    configuration and at its serving size (256 slots of 3072 rows, a
    2048-token prefill), with the dense layer and TWO of the four sparse
    layers (the last layer's experts feed nothing that a prefill returns:
    with one there would be none to compile), two of the ten experts held
    and 1024 vocabulary rows, so that
    the host's copy of the weights stays small: the cache leaves have 512
    and 64 values a row and no head axis, and come back aliased; the
    prefill's experts of 1536 x 5120 run in `held_experts_grouped` (three
    blocks of the hidden width a tile); the step attends through the kernel
    `latent_decode_attention`, once for the dense layer and once in the
    scan's body, over the leaves as they lie: no float32 scores (S, 128,
    3072) and no copy of a layer's rows are among its temporaries, 69.1 MB
    in all (815.7 MB with the einsums, before the kernel); the prefill
    attends through the kernel `latent_prefill_attention`, once for the
    dense layer and once in the scan's body, and holds no
    `latent_decode_attention`: no float32 scores (128, 512, 512) and no
    keys joined to a copy of the rotary key a head, (T, 128, 192), are among
    its instructions, and its temporaries are 476.8 MB (667.1 MB with
    `blocked_causal_attention`, before the kernel); and with the layers,
    experts and vocabulary rows left out here added back, the step and the
    prefill stay under 16 GB."""
    from incubator_mxnet_tpu.models.latent_decoder import LatentDecoder

    S, L, V, held, layers = 256, 3072, 1024, 2, 3
    yarn = {"factor": 40, "original_max_position_embeddings": 4096,
            "beta_fast": 32, "beta_slow": 1, "mscale": 0.707,
            "mscale_all_dim": 0.707}
    net = LatentDecoder(V, 5120, layers, 1, 128, 1536, 512, 128, 64, 128,
                        12288, 1536, 160, 6, 8, 3, routed_scale=16.0,
                        shared_hidden=3072, first_held=0, experts_held=held,
                        rope_scaling=yarn)
    net.collect_params().setattr("grad_req", "null")
    net.initialize(ctx=mx.cpu(0))
    net.cast("bfloat16")
    eng = GenerationEngine(net, bos=1, eos=2, ctx=mx.cpu(0), slots=S,
                           max_len=L, prompt_buckets=(2048,), queue_cap=4)
    cache, step, _, prefill = _compile_for(eng, one_chip, S, L, 2048,
                                           prefill=True)
    m = cache["m"]
    assert m["ckv"].shape == (S, layers, L, 512) and m["ckv"].dtype == "bfloat16"
    assert m["kr"].shape == (S, layers, L, 64) and m["kr"].dtype == "bfloat16"
    assert sorted(m) == ["ckv", "counts", "kr"]
    total = S * layers * L * 576 * 2
    mem = step.memory_analysis()
    assert mem.alias_size_in_bytes >= total
    assert mem.temp_size_in_bytes < 0.4e9, mem.temp_size_in_bytes
    text = step.as_text()
    assert _kernel_calls(text, "latent_decode_attention") == 2
    assert _kernel_calls(text, "held_experts_grouped") == 0
    # the scores of every row, or a layer's rows out of a leaf
    moved = [(op, name, dims) for op, name, dims in _results(text)
             if sorted(dims) in (sorted([S, 128, L]), sorted([S, L, 512]),
                                 sorted([S, L, 64]))]
    assert not moved, moved
    text = prefill.as_text()
    assert _kernel_calls(text, "held_experts_grouped") == 1
    assert _kernel_calls(text, "held_experts_combine") == 1
    assert _kernel_calls(text, "latent_decode_attention") == 0
    assert _kernel_calls(text, "latent_prefill_attention") == 2
    # a chunk's scores, or every head's keys with the rotary key joined on
    scored = [(op, name, dims) for op, name, dims in _results(text)
              if sorted(d for d in dims if d > 1) in ([128, 512, 512],
                                                      [128, 192, 2048])]
    assert not scored, scored
    # what this test left off the chip: two sparse layers (attention
    # 149.23 M, router and shared experts 48.0 M, ten experts of 23.59 M),
    # eight experts of each one here, 11 776 rows of the embedding and of
    # the head; and the two layers' rows of the cache
    absent = 2 * (2 * (149.23e6 + 48.0e6 + 10 * 23.59e6) + 2 * 8 * 23.59e6
                  + 2 * (12800 - V) * 5120)
    rows = S * 2 * L * 576 * 2
    size = lambda a: a.argument_size_in_bytes + a.output_size_in_bytes \
        - a.alias_size_in_bytes + a.temp_size_in_bytes
    assert size(mem) + absent + rows < 16e9, size(mem) + absent + rows
    # a prefill runs beside the resident cache
    pre = prefill.memory_analysis()
    assert pre.temp_size_in_bytes < 0.55e9, pre.temp_size_in_bytes
    assert size(pre) + total + absent + rows < 16e9, \
        size(pre) + total + absent + rows


def _results(text):
    """(op, name, dims) of every instruction of an optimized program that
    has one array as its result: `%name = type[dims]{layout} op(`."""
    import re
    for name, dims, op in re.findall(
            r"%?([\w.\-]+) = \(?\w+\[([\d,]+)\]\S* ([\w\-]+)\(", text):
        yield op, name, [int(n) for n in dims.split(",")]


def _expert_sized(text, held, F, D):
    """Instructions whose result is one layer's held experts, (held, F, D)
    or (held, D, F) under any leading 1s, in HBM: a layer's slice of an
    expert stack, copied out.  A result in VMEM (`S(1)`) is the memory-space
    assignment staging a stack that fits there, whole (`copy-done`) or in
    slices of a layer (`slice-done`, then `ConcatBitcast`), as it does for
    the few experts these tests hold; no layer's held experts at the
    published widths (0.2 GB and more) fit the 128 MiB there."""
    import re
    in_vmem = set(re.findall(r"%?([\w.\-]+) = \(?\w+\[[\d,]*\]\{[^}]*S\(1\)\}",
                             text))
    found = []
    for op, name, dims in _results(text):
        while dims[:1] == [1]:
            dims = dims[1:]
        if dims in ([held, F, D], [held, D, F]) and name not in in_vmem:
            found.append((op, name))
    return found


@pytest.mark.parametrize("model", ["hybrid", "sparse"])
def test_a_prefill_multiplies_its_held_experts_by_the_grouped_kernel(
        one_chip, model):
    """The prefill of each decoder-only model at the published widths of its
    configuration (`HybridDecoder`: one period of four layers, a 1024-token
    bucket; `SparseDecoder`: two layers, a 4096-token bucket; three experts
    held so that the host's copy of the weights stays small), compiled for
    the described v5e: the expert half of every layer is the kernel
    `held_experts_grouped`, which reads the whole expert stacks at
    [layer, expert]: no instruction's result is a layer's held experts (at
    64 held experts of 3 x 512 x 2048 that copy would be 0.4 GB a layer),
    and the decode step, in its few-token form, holds no such kernel."""
    held = 3
    if model == "hybrid":
        from incubator_mxnet_tpu.models.hybrid_decoder import HybridDecoder
        # one period, unrolled: the last layer's experts feed nothing that
        # a prefill returns and are not compiled at all
        F, bucket, calls = 512, 1024, 3
        net = HybridDecoder(1024, 2048, 4, 4, 16, 2, 256, 64, 16, 32, 128,
                            128, 4, F, 512, 10, shared_hidden=512,
                            first_held=0, experts_held=held)
    else:
        from incubator_mxnet_tpu.models.sparse_decoder import SparseDecoder
        F, bucket, calls = 768, 4096, 1
        net = SparseDecoder(1024, 2048, 2, 32, 4, 128, F, 128, 8, 16, 64,
                            2048, first_held=0, experts_held=held)
    net.collect_params().setattr("grad_req", "null")
    net.initialize(ctx=mx.cpu(0))
    net.cast("bfloat16")
    eng = GenerationEngine(net, bos=1, eos=2, ctx=mx.cpu(0), slots=4,
                           max_len=bucket, prompt_buckets=(bucket,),
                           queue_cap=4)
    _, step, _, prefill = _compile_for(eng, one_chip, 4, bucket, bucket,
                                       prefill=True)
    text = prefill.as_text()
    assert _kernel_calls(text, "held_experts_grouped") == calls
    assert not _expert_sized(text, held, F, 2048)
    assert "held_experts_grouped" not in step.as_text()
    # the rows come back to their tokens by the combine, a layer, and not by
    # k gathers of T rows (no gather of the experts' part has the result
    # (T, D), (T, k, D) or (k, T, D); the embedding's gather is (T, D) too)
    assert _kernel_calls(text, "held_experts_combine") == calls
    assert _kernel_calls(step.as_text(), "held_experts_combine") == 0
    import re
    k = 10 if model == "hybrid" else 8
    rows = [line[:120] for line in text.splitlines()
            if re.search(r"= \w+\[(1,)?(%d,%d|%d,%d,%d|%d,%d,%d)\]\S* gather\("
                         % (bucket, 2048, bucket, k, 2048, k, bucket, 2048),
                         line) and "mx.experts" in line]
    assert not rows, rows


def test_nmt_decode_step_writes_its_cache_in_place(one_chip):
    """The decode step of `transformer_nmt_base` at the benchmark cell's
    own size (6 layers, 512 units, 8 heads; 512 slots of 256 rows; at a
    fraction of it XLA parks whole memory leaves in VMEM and the program is
    another): compiled for the described v5e it holds the ragged attention
    kernel, twice a layer, and the kernel `live_rows_write`, once a layer,
    in place of the scatters of the new rows, the donated cache comes back
    aliased, the step's temporaries stay under a quarter of one K leaf,
    and no copy, transpose or select in the optimized program is as large
    as a cache leaf: each row is written where it lies and attention reads
    the leaves as they lie."""
    import jax.numpy as jnp
    from incubator_mxnet_tpu import nd
    from incubator_mxnet_tpu.models.transformer import transformer_nmt_base

    S, L, V = 512, 256, 512
    net = transformer_nmt_base(V, V, max_length=L, dropout=0.0)
    net.collect_params().setattr("grad_req", "null")
    net.initialize(ctx=mx.cpu(0))
    one = nd.array(onp.ones((1, 2), onp.int32), ctx=mx.cpu(0), dtype="int32")
    net(one, one)                       # shapes are deferred until here
    net.cast("bfloat16")
    eng = GenerationEngine(net, bos=1, eos=2, ctx=mx.cpu(0), slots=S,
                           max_len=L, prompt_buckets=(L,), queue_cap=4)
    cache, step, _, _ = _compile_for(eng, one_chip, S, L, L)
    m = cache["m"]
    assert m["k0"].dtype == jnp.float32 and m["mem_k0"].dtype == jnp.bfloat16
    assert m["counts"].shape == (S, 1) and cache["left"].shape == (S,)
    leaf = S * L * 512                                  # elements of a leaf
    total = 6 * leaf * (2 * 4 + 2 * 2)                  # K/V f32, memory bf16
    mem = step.memory_analysis()
    assert mem.alias_size_in_bytes >= total
    assert mem.temp_size_in_bytes < leaf * 4 // 4, mem.temp_size_in_bytes
    text = step.as_text()
    assert _kernel_calls(text, "ragged_decode_attention") == 12
    # the new rows go in by the kernel, once a layer for K and V, not by a
    # scatter of 2048 rows a leaf
    assert _kernel_calls(text, "live_rows_write") == 6
    assert text.count('custom_call_target="tpu_custom_call"') == 18
    assert not [name for op, name, _ in _results(text) if op == "scatter"]
    # results as large as a memory leaf
    moved = [(op, name, dims) for op, name, dims in _results(text)
             if op in ("copy", "copy-start", "transpose", "select")
             and onp.prod(dims) >= leaf]
    assert not moved, moved


def test_looped_decoder_step_and_prefill_fit_the_chip(one_chip):
    """`LoopedDecoder` at the published widths of the benchmark's
    configuration and at its serving size (24 slots of 480 rows, a
    192-token prefill, four passes), with TWO of the 24 layers and 1024
    vocabulary rows so that the host's copy of the weights stays small (the
    two nested scans compile one layer body whatever the depth): the leaves
    are (S, passes x layers, 16, 480, 128) bfloat16 and come back aliased
    through both scans; the step attends through the kernel
    `ragged_decode_attention`, once in the inner scan's body, over the
    stacked leaves as they lie (row blocks of 48), and writes its new rows
    through the kernel `decode_rows_write`, the leaves aliased to its
    results (no scatter is left in the program): no result of the
    optimized program is as large as one (pass, layer)'s rows of a leaf, so
    no layer's slice is copied out in front of the kernel; and with the
    layers and vocabulary rows left out here added back, the step and a
    prefill beside the resident cache stay under 16 GB."""
    from incubator_mxnet_tpu.models.looped_decoder import LoopedDecoder

    S, L, V, layers, R = 24, 480, 1024, 2, 4
    net = LoopedDecoder(V, 2048, layers, 16, 128, 5632, loops=R)
    net.collect_params().setattr("grad_req", "null")
    net.initialize(ctx=mx.cpu(0))
    net.cast("bfloat16")
    eng = GenerationEngine(net, bos=1, eos=2, ctx=mx.cpu(0), slots=S,
                           max_len=L, prompt_buckets=(192,), queue_cap=4)
    cache, step, join, prefill = _compile_for(eng, one_chip, S, L, 192,
                                              join=True, prefill=True)
    m = cache["m"]
    assert sorted(m) == ["counts", "k", "v"]
    assert m["k"].shape == m["v"].shape == (S, R * layers, 16, L, 128)
    assert m["k"].dtype == "bfloat16"
    rows = S * 16 * L * 128                 # one (pass, layer) of a leaf
    total = 2 * R * layers * rows * 2
    mem = step.memory_analysis()
    assert mem.alias_size_in_bytes >= total
    assert mem.temp_size_in_bytes < rows * 2 // 2, mem.temp_size_in_bytes
    assert join.memory_analysis().alias_size_in_bytes >= total
    text = step.as_text()
    assert _kernel_calls(text, "ragged_decode_attention") == 1
    # the step's new rows go in by the kernel, not by a scatter of 384 rows
    assert _kernel_calls(text, "decode_rows_write") == 1
    assert not [name for op, name, _ in _results(text) if op == "scatter"]
    moved = [(op, name, dims) for op, name, dims in _results(text)
             if op in ("copy", "copy-start", "transpose", "select", "slice",
                       "dynamic-slice", "gather")
             and onp.prod(dims) >= rows]
    assert not moved, moved
    assert _kernel_calls(prefill.as_text(), "ragged_decode_attention") == 0
    # what this test left off the chip: 22 layers of 51.39 M, 48 128 rows
    # of the embedding and of the head; and the 22 layers' rows of the cache
    absent = 2 * (22 * 51.39e6 + 2 * (49152 - V) * 2048)
    more_rows = 2 * R * 22 * rows * 2
    size = lambda a: a.argument_size_in_bytes + a.output_size_in_bytes \
        - a.alias_size_in_bytes + a.temp_size_in_bytes
    assert size(mem) + absent + more_rows < 16e9, \
        size(mem) + absent + more_rows
    pre = prefill.memory_analysis()
    # a prefill's row of 24 layers is twelve times this one's
    assert size(pre) + 11 * pre.output_size_in_bytes + total + absent \
        + more_rows < 16e9



def test_window_decoder_step_and_prefill_fit_the_chip(one_chip):
    """`WindowDecoder` at the published widths of the benchmark's
    configuration and at its serving size (nine layers: a full dense layer,
    then two periods of three window layers and a full one; 64 slots of
    9216 rows; an 8192-token prefill), two of the 32 experts held and 1024
    vocabulary rows so that the host's copy of the weights stays small: the
    full layers' leaves are (S, 3, 8, 9216, 128) and the rings
    (S, 6, 8, 512, 128), bfloat16, and come back aliased; the step writes
    its new rows through the kernel `decode_rows_write`, once a layer (the
    dense layer's and the period's four), and no scatter is left; the
    step's temporaries stay under a quarter of one full layer's rows of a
    leaf, so no layer's slice is copied out in front of the attention; the
    step attends through the kernel `grouped_decode_attention`, once a layer
    (the dense layer's and the period's four), over the leaves as they lie:
    no float32 scores of every row, (S, 8, 6, 9216) of a full layer or
    (S, 8, 8, 512) of a ring, are among its instructions, its temporaries
    are 7.9 MB (157.0 MB with the masked einsums, before the kernel) and
    the step's bytes 8.963 GB (9.112 GB); the prefill multiplies the period's four expert halves in the kernel
    `held_experts_grouped` and holds no float32 scores of a query block
    against all 8192 keys of its prompt; and with
    the experts and vocabulary rows left out here added back, the step and
    the prefill beside the resident cache stay under 16 GB."""
    import re
    from incubator_mxnet_tpu.models.window_decoder import WindowDecoder

    S, L, V, held, bucket = 64, 9216, 1024, 2, 8192
    types = ["full_attention"] + (["sliding_attention"] * 3
                                  + ["full_attention"]) * 2
    rope = {"full_attention": {"rope_type": "yarn", "rope_theta": 500000,
                               "factor": 64,
                               "original_max_position_embeddings": 4096,
                               "beta_fast": 64, "beta_slow": 1,
                               "attention_factor": 1.4158883083359672,
                               "partial_rotary_factor": 0.5},
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": 10000}}
    net = WindowDecoder(V, 2048, types, ["dense"] + ["sparse"] * 8,
                        [48 if t == "full_attention" else 64 for t in types],
                        8, 128, 512, 8192, 512, 256, 8, rope,
                        shared_hidden=512, routed_scale=2.5, first_held=0,
                        experts_held=held)
    net.collect_params().setattr("grad_req", "null")
    net.initialize(ctx=mx.cpu(0))
    net.cast("bfloat16")
    eng = GenerationEngine(net, bos=1, eos=2, ctx=mx.cpu(0), slots=S,
                           max_len=L, prompt_buckets=(bucket,), queue_cap=4)
    cache, step, join, prefill = _compile_for(eng, one_chip, S, L, bucket,
                                              join=True, prefill=True)
    m = cache["m"]
    assert sorted(m) == ["counts", "kf", "kw", "vf", "vw"]
    assert m["kf"].shape == m["vf"].shape == (S, 3, 8, L, 128)
    assert m["kw"].shape == m["vw"].shape == (S, 6, 8, 512, 128)
    assert m["kf"].dtype == m["kw"].dtype == "bfloat16"
    total = 2 * S * 8 * 128 * 2 * (3 * L + 6 * 512)         # 8.05 GB
    mem = step.memory_analysis()
    assert mem.alias_size_in_bytes >= total
    assert join.memory_analysis().alias_size_in_bytes >= total
    text = step.as_text()
    assert _kernel_calls(text, "decode_rows_write") == 5
    assert not [name for op, name, _ in _results(text) if op == "scatter"]
    # a layer's rows taken out of a leaf are read inside the attention's
    # fusions: the step's temporaries are not one full layer's rows of a
    # leaf (1.21 GB)
    rows = S * 8 * L * 128 * 2
    assert mem.temp_size_in_bytes < rows // 4, mem.temp_size_in_bytes
    assert _kernel_calls(text, "grouped_decode_attention") == 5
    scores = [line[:120] for line in text.splitlines()
              if re.search(r"= f32\[%d,(8,6,%d|48,%d|8,8,512|64,512)\]"
                           % (S, L, L), line)]
    assert not scores, scores
    assert mem.temp_size_in_bytes < 20e6, mem.temp_size_in_bytes
    # float32 scores of a query block against every key of the prompt, in
    # the attention of either kind: the blocks read only their band
    text = prefill.as_text()
    assert _kernel_calls(text, "held_experts_grouped") == 4
    assert _kernel_calls(text, "held_experts_combine") == 4
    whole = [line[:120] for line in text.splitlines()
             if re.search(r"= f32\[[\d,]*\b512\b[\d,]*\]", line)
             and re.search(r"= f32\[[\d,]*\b%d\b" % bucket, line)
             and re.search(r'op_name="[^"]*mx\.(attn|window)', line)]
    assert not whole, whole
    pre = prefill.memory_analysis()
    assert pre.temp_size_in_bytes < 2.5e9, pre.temp_size_in_bytes
    # what this test left off the chip: 30 experts of 3.146 M in each of
    # the eight sparse layers, 11 520 rows of the embedding and of the head
    absent = 2 * (8 * 30 * 3.146e6 + 2 * (12544 - V) * 2048)
    size = lambda a: a.argument_size_in_bytes + a.output_size_in_bytes \
        - a.alias_size_in_bytes + a.temp_size_in_bytes
    assert size(mem) + absent < 16e9, size(mem) + absent
    assert size(mem) < 9.112e9, size(mem)         # the masked einsums' step
    assert size(pre) + total + absent < 16e9, size(pre) + total + absent
